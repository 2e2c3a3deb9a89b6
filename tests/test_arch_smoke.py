"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, assert output shapes + finiteness, plus prefill/decode agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import abstract_params, build_model


def _batch(cfg, b=2, s=16, key=0):
    rng = np.random.default_rng(key)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.enc_seq, cfg.d_model)), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patch_embeddings"] = jnp.asarray(
            rng.normal(size=(b, cfg.stub_prefix_len, cfg.d_model)), jnp.bfloat16)
    return batch


def test_seeded_init_is_stable_across_processes():
    """Sub-keys derive from a stable digest of each parameter's name, not
    from Python's per-process salted str hash, so key(0) gives the same
    weights in every process (parent/change comparisons rely on it)."""
    cfg = configs.get_smoke("granite-20b")
    params, _ = build_model(cfg).init(jax.random.key(0))
    w_up = np.asarray(params["layers"]["mlp"]["w_up"], np.float64)
    assert w_up.sum() == pytest.approx(-32.70617608368835, rel=1e-9)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_forward_shapes_and_finite(arch):
    cfg = configs.get_smoke(arch)
    model = build_model(cfg, attn_impl="blocked", q_block=8)
    params, _ = model.init(jax.random.key(0))
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    logits, aux = jax.jit(model.forward)(params, batch)
    extra = cfg.stub_prefix_len if cfg.family == "vlm" else 0
    assert logits.shape == (b, s + extra, cfg.vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all(), arch
    assert jnp.isfinite(jnp.asarray(aux, jnp.float32)).all()


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_train_step_smoke(arch):
    """One SGD step: grads exist, are finite, loss decreases over 3 steps."""
    cfg = configs.get_smoke(arch)
    model = build_model(cfg, attn_impl="blocked", q_block=8)
    params, _ = model.init(jax.random.key(0))
    batch = _batch(cfg, 2, 16)
    labels = jnp.roll(batch["tokens"], -1, axis=1)

    def loss_fn(p):
        logits, aux = model.forward(p, batch)
        logits = logits[:, -labels.shape[1]:]
        from repro.models.common import softmax_xent
        return softmax_xent(logits, labels) + 0.01 * aux

    step = jax.jit(lambda p: (loss_fn(p), jax.grad(loss_fn)(p)))
    losses = []
    for _ in range(3):
        loss, grads = step(params)
        losses.append(float(loss))
        gnorm = jax.tree.reduce(
            lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
            grads, 0.0)
        assert jnp.isfinite(gnorm) and gnorm > 0, arch
        params = jax.tree.map(lambda p, g: p - 0.05 * g.astype(p.dtype),
                              params, grads)
    assert losses[-1] < losses[0], (arch, losses)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_prefill_decode_matches_forward(arch):
    """Greedy next-token from (prefill + decode_step) == from full forward."""
    cfg = configs.get_smoke(arch)
    model = build_model(cfg, attn_impl="blocked", q_block=8)
    params, _ = model.init(jax.random.key(1))
    b, s = 2, 12
    batch = _batch(cfg, b, s)
    max_seq = 32

    caches = model.init_cache(b, max_seq)
    logits_pf, caches = jax.jit(model.prefill)(params, batch, caches)
    # full forward logits at the last prompt position must agree
    logits_full, _ = jax.jit(model.forward)(params, batch)
    np.testing.assert_allclose(
        np.asarray(logits_pf[:, 0], np.float32),
        np.asarray(logits_full[:, -1], np.float32), rtol=0.1, atol=0.15)

    # one decode step stays finite and has the right shape
    tok = jnp.argmax(logits_pf, axis=-1).astype(jnp.int32)
    pos = s + (cfg.stub_prefix_len if cfg.family == "vlm" else 0)
    logits_d, caches = jax.jit(model.decode_step)(
        params, tok, jnp.int32(pos), caches)
    assert logits_d.shape == (b, 1, cfg.vocab_size)
    assert jnp.isfinite(logits_d.astype(jnp.float32)).all()


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_abstract_params_match_concrete(arch):
    cfg = configs.get_smoke(arch)
    model = build_model(cfg)
    params, axes = model.init(jax.random.key(0))
    sds, axes2 = abstract_params(model)
    concrete_shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    abstract_shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), sds)
    assert concrete_shapes == abstract_shapes
    assert axes == axes2
    # every param has an axes entry of matching rank
    is_axes = lambda x: isinstance(x, tuple) and all(
        i is None or isinstance(i, str) for i in x)
    jax.tree.map(lambda a, p: None if len(a) == len(p.shape) else 1 / 0,
                 axes, params, is_leaf=is_axes)
