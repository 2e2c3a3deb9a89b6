"""Property-based tests on system invariants (hypothesis) + algorithmic
equivalences: chunkwise==recurrent for mLSTM/SSD, ring-cache==full-cache
sliding window, head padding==function preservation, MoE conservation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.models import build_model
from repro.models.mamba2 import ssd_chunkwise, ssd_step
from repro.models.xlstm import mlstm_chunkwise, mlstm_step
from repro.parallel.sharding import spec_for


# ---------------------------------------------------------------------------
# chunkwise-parallel == step recurrence (the sub-quadratic forms are exact)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), chunk=st.sampled_from([2, 4, 8, 16]))
def test_mlstm_chunkwise_equals_recurrence(seed, chunk):
    rng = np.random.default_rng(seed)
    b, t, nh, dh = 2, 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, nh, dh)), jnp.float32)
               for _ in range(3))
    logi = jnp.asarray(rng.normal(size=(b, t, nh)) - 1.0, jnp.float32)
    logf = jnp.asarray(-np.abs(rng.normal(size=(b, t, nh))), jnp.float32)
    C0 = jnp.zeros((b, nh, dh, dh))
    n0 = jnp.zeros((b, nh, dh))
    h_chunk, (C1, n1) = mlstm_chunkwise(q, k, v, logi, logf, (C0, n0),
                                        chunk=chunk)
    # sequential reference
    C, n = C0, n0
    hs = []
    for i in range(t):
        h, (C, n) = mlstm_step(q[:, i:i+1], k[:, i:i+1], v[:, i:i+1],
                               logi[:, i:i+1], logf[:, i:i+1], (C, n))
        hs.append(h)
    h_seq = jnp.concatenate(hs, axis=1)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(C1), np.asarray(C), rtol=1e-4,
                               atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), chunk=st.sampled_from([2, 4, 8]))
def test_ssd_chunkwise_equals_recurrence(seed, chunk):
    rng = np.random.default_rng(seed)
    b, t, nh, p, n = 2, 16, 2, 4, 6
    x = jnp.asarray(rng.normal(size=(b, t, nh, p)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, t, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, t, n)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, t, nh))) * 0.5, jnp.float32)
    a = jnp.asarray(-np.abs(rng.normal(size=(nh,))), jnp.float32)
    S0 = jnp.zeros((b, nh, n, p))
    y_chunk, S1 = ssd_chunkwise(x, bm, cm, dt, a, S0, chunk=chunk)
    S, ys = S0, []
    for i in range(t):
        y, S = ssd_step(x[:, i:i+1], bm[:, i:i+1], cm[:, i:i+1],
                        dt[:, i:i+1], a, S)
        ys.append(y)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("scan", ["ssd", "mlstm"])
def test_chunkwise_grads_finite_over_a_long_decay(scan):
    """A 256-step chunk whose decay sums to -256: exp of the masked upper
    triangle would overflow, and its gradient through where() would be
    NaN (as a zamba2-1.2b train step at published widths showed)."""
    rng = np.random.default_rng(0)
    b, t, nh, p = 1, 256, 2, 4
    x = jnp.asarray(rng.normal(size=(b, t, nh, p)), jnp.float32)
    if scan == "ssd":
        bm = jnp.asarray(rng.normal(size=(b, t, p)), jnp.float32)

        def loss(x, dt):     # a = -1: per-step log decay dt * a = -1
            y, _ = ssd_chunkwise(x, bm, bm, dt, -jnp.ones((nh,)),
                                 jnp.zeros((b, nh, p, p)), chunk=t)
            return jnp.sum(y)
        d = jnp.ones((b, t, nh), jnp.float32)
    else:
        def loss(x, logf):
            h, _ = mlstm_chunkwise(x, x, x, jnp.zeros_like(logf), logf,
                                   (jnp.zeros((b, nh, p, p)),
                                    jnp.zeros((b, nh, p))), chunk=t)
            return jnp.sum(h)
        d = -jnp.ones((b, t, nh), jnp.float32)
    for g in jax.grad(loss, argnums=(0, 1))(x, d):
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# query-head padding is function-preserving


def test_padded_heads_preserve_function():
    cfg = configs.get_smoke("deepseek-coder-33b")      # 8 heads, kv=2
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        jnp.int32)}
    logits, _ = jax.jit(model.forward)(params, batch)

    # padded variant: 8 -> 12 query heads, wq/wo extended with zeros
    cfgp = cfg.replace(pad_q_heads=12)
    modelp = build_model(cfgp, q_block=8)
    paramsp, _ = modelp.init(jax.random.key(0))

    def graft(dst, src):
        """Interleave original heads per KV group; zero the padding."""
        out = jax.tree.map(lambda x: x, dst)
        lay_d, lay_s = out["layers"], src["layers"]
        kvh, g, g_pad = 2, 4, 6
        wq = jnp.zeros_like(lay_d["attn"]["wq"])
        wo = jnp.zeros_like(lay_d["attn"]["wo"])
        for grp in range(kvh):
            wq = wq.at[:, :, grp * g_pad:grp * g_pad + g].set(
                lay_s["attn"]["wq"][:, :, grp * g:(grp + 1) * g])
            wo = wo.at[:, grp * g_pad:grp * g_pad + g].set(
                lay_s["attn"]["wo"][:, grp * g:(grp + 1) * g])
        lay_d["attn"]["wq"] = wq
        lay_d["attn"]["wo"] = wo
        for k in ("wk", "wv"):
            lay_d["attn"][k] = lay_s["attn"][k]
        for k in ("norm1", "norm2"):
            lay_d[k] = lay_s[k]
        lay_d["mlp"] = lay_s["mlp"]
        for k in ("embedding", "unembed", "final_norm"):
            out[k] = src[k]
        return out

    paramsp = graft(paramsp, params)
    logitsp, _ = jax.jit(modelp.forward)(paramsp, batch)
    np.testing.assert_allclose(np.asarray(logitsp, np.float32),
                               np.asarray(logits, np.float32),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# gemma3 ring cache == full-cache sliding window


def test_window_ring_decode_matches_full_forward():
    cfg = configs.get_smoke("gemma3-27b")
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    b, s = 2, 24
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)

    caches = model.init_cache(b, 64)
    logits_pf, caches = jax.jit(model.prefill)(
        params, {"tokens": tokens}, caches)
    # decode 4 more tokens greedily; compare each against full forward
    cur = tokens
    step = jax.jit(model.decode_step)
    for i in range(4):
        nxt = jnp.argmax(logits_pf, axis=-1).astype(jnp.int32)
        logits_d, caches = step(params, nxt, jnp.int32(s + i), caches)
        cur = jnp.concatenate([cur, nxt], axis=1)
        full, _ = jax.jit(model.forward)(params, {"tokens": cur})
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full[:, -1], np.float32), rtol=0.15, atol=0.2)
        logits_pf = logits_d


# ---------------------------------------------------------------------------
# MoE invariants


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_moe_router_weights_normalized_and_conserved(seed):
    from repro.models.moe import moe_apply, moe_init
    from repro.models.common import ParamBuilder
    from repro.parallel.sharding import Sharder
    cfg = configs.get_smoke("deepseek-moe-16b")
    pb = ParamBuilder(jax.random.key(seed % 100))
    moe_init(pb, cfg, None)
    params = {k: (v if not isinstance(v, dict) else v)
              for k, v in pb.params.items()}
    # strip the [L] axis builder adds nothing here (L=None)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)) * 0.1, jnp.bfloat16)
    y, aux = moe_apply(x, params, cfg, Sharder(None))
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(aux) >= 0.99  # load-balance loss >= 1 at optimum E*sum(f*p)


def test_moe_capacity_drops_tokens_but_stays_finite():
    from repro.models.moe import moe_apply, moe_init
    from repro.models.common import ParamBuilder
    from repro.parallel.sharding import Sharder
    cfg = configs.get_smoke("deepseek-moe-16b").replace(capacity_factor=0.1)
    pb = ParamBuilder(jax.random.key(0))
    moe_init(pb, cfg, None)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, cfg.d_model)),
                    jnp.bfloat16)
    y, _ = moe_apply(x, pb.params, cfg, Sharder(None))
    assert np.isfinite(np.asarray(y, np.float32)).all()


# ---------------------------------------------------------------------------
# sharding spec properties


@settings(max_examples=30, deadline=None)
@given(d0=st.sampled_from([1, 3, 16, 48, 64]),
       d1=st.sampled_from([2, 8, 16, 256]))
def test_spec_divisibility_always_respected(d0, d1):
    import jax as _jax
    mesh = _jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(_jax.sharding.AxisType.Auto,) * 2)
    spec = spec_for(mesh, ("embed", "mlp"), (d0, d1))
    for dim, ax in zip((d0, d1), tuple(spec) + (None,) * 2):
        if ax is not None:
            size = mesh.shape[ax] if isinstance(ax, str) else int(
                np.prod([mesh.shape[a] for a in ax]))
            assert dim % size == 0


def test_spec_no_axis_reuse():
    import jax as _jax
    mesh = _jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(_jax.sharding.AxisType.Auto,) * 2)
    # both logical axes want "model": second must drop
    spec = spec_for(mesh, ("vocab", "mlp"), (16, 16))
    axes_used = [s for s in spec if s is not None]
    flat = []
    for a in axes_used:
        flat.extend(a if isinstance(a, tuple) else (a,))
    assert len(flat) == len(set(flat))
