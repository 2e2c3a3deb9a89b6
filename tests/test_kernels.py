"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle across
shape/dtype sweeps, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.stream import ops as stream_ops, ref as stream_ref
from repro.kernels.stream import stream as stream_k
from repro.kernels.dpa_matmul import ops as dpa_ops, ref as dpa_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref

I = dict(interpret=True)


# ---------------------------------------------------------------------------
# stream


@pytest.mark.parametrize("rows,cols", [(64, 128), (256, 128), (128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_suite(rows, cols, dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(rows, cols)), dtype)
    b = jnp.asarray(rng.normal(size=(rows, cols)), dtype)
    x = 1.7
    np.testing.assert_allclose(stream_k.stream_copy(a, **I), stream_ref.copy(a))
    np.testing.assert_allclose(
        np.asarray(stream_k.stream_scale(a, x, **I), np.float32),
        np.asarray(stream_ref.scale(a, x), np.float32), rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(stream_k.stream_add(a, b, **I), np.float32),
        np.asarray(stream_ref.add(a, b), np.float32), rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(stream_k.stream_triad(a, b, x, **I), np.float32),
        np.asarray(stream_ref.triad(a, b, x), np.float32),
        rtol=2e-2, atol=1e-2)


def test_stream_write_read():
    out = stream_k.stream_write((64, 128), 3.5, **I)
    np.testing.assert_allclose(out, stream_ref.write((64, 128), 3.5))
    a = jnp.asarray(np.random.default_rng(1).normal(size=(64, 128)), jnp.float32)
    np.testing.assert_allclose(stream_k.stream_read(a, block_rows=16, **I),
                               stream_ref.read(a, block_rows=16), rtol=1e-5)


# ---------------------------------------------------------------------------
# dpa_matmul


@pytest.mark.parametrize("variant,tol", [("fma_f32", 1e-5), ("dpa2", 2e-2),
                                         ("dpa4", 0)])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 256, 128, 128, 128, 128),
    (256, 512, 128, 128, 128, 256),
    (64, 128, 64, 64, 64, 128),
])
def test_dpa_matmul(variant, tol, m, k, n, bm, bn, bk):
    rng = np.random.default_rng(2)
    if variant == "dpa4":
        a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
        b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    else:
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    got = dpa_ops.matmul(a, b, variant=variant, interpret=True)
    want = dpa_ref.matmul(a, b, variant)
    if variant == "dpa4":
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol * k ** 0.5 + 1e-6, atol=tol * 4)


def test_quantized_linear_close_to_fp():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 64)) / np.sqrt(128), jnp.float32)
    got = dpa_ops.quantized_linear(x, w, interpret=True)
    want = x @ w
    err = np.abs(np.asarray(got - want)) / (np.abs(np.asarray(want)) + 1e-2)
    assert np.median(err) < 0.05


@settings(max_examples=20, deadline=None)
@given(m=st.sampled_from([64, 128]), k=st.sampled_from([128, 256]),
       n=st.sampled_from([64, 128]), seed=st.integers(0, 2**16))
def test_dpa4_exact_int_property(m, k, n, seed):
    """int8 DPA accumulation is EXACT (no rounding) — paper's DPA4 claim."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    got = dpa_ops.matmul(a, b, variant="dpa4", interpret=True)
    want = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("b,h,s,t,d", [
    (1, 2, 128, 128, 64),
    (2, 1, 256, 256, 128),
    (1, 2, 128, 256, 64),   # cross-length (q shorter than kv)
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, h, s, t, d, causal, dtype):
    if causal and s != t:
        pytest.skip("causal with s<t needs offset semantics")
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, t, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, t, d)), dtype)
    got = fa_ops.attention(q, k, v, causal=causal, interpret=True)
    want = fa_ref.attention(q, k, v, causal=causal)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_window(window):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=True, window=window,
                           block_q=64, block_kv=64, interpret=True)
    want = fa_ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16),
       bq=st.sampled_from([32, 64, 128]),
       bkv=st.sampled_from([32, 64, 128]))
def test_flash_block_shape_invariance(seed, bq, bkv):
    """Property: output independent of the VMEM block decomposition."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, 1, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, 128, 64)), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                           interpret=True)
    want = fa_ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


# ---------------------------------------------------------------------------
# paged decode attention
#
# One tolerance for every comparison below, from bfloat16 rounding of the
# output. Each output is bfloat16 of magnitude under 4 (asserted), where one
# ulp is 2^-6. The sides round at different points: the kernel keeps scores
# in float32 and rounds its output once; the model's reference also rounds
# the score product and the probabilities to bfloat16. That moves an output
# by about one ulp (measured: at most 2^-6), so two ulps are allowed:
# PAGED_TOL = 2^-5. A wrong mask, head or block moves an output by O(1).

PAGED_TOL = 2 * 2.0 ** -6


def _paged_case(kvh, ratio, block=8, n_table=4, n_layers=2, dh=128, seed=0):
    """Four live slots at lengths 1, block, block + 1 and max_seq, each on
    a shuffled block table, and a free slot on the null block at length 1.
    Returns the pools, the contiguous per-slot caches they hold, and the
    query, layer, tables and lengths."""
    rng = np.random.default_rng(seed)
    h, max_seq = kvh * ratio, block * n_table
    lengths = np.array([1, block, block + 1, max_seq, 1], np.int32)
    n_live = len(lengths) - 1
    n_pool = n_live * n_table + 1
    tables = np.zeros((len(lengths), n_table), np.int32)   # free: null block
    tables[:n_live] = rng.permutation(np.arange(1, n_pool)).reshape(
        n_live, n_table)
    shape = (n_layers, n_pool, block, kvh, dh)
    pools = [rng.normal(size=shape).astype(np.float32) for _ in "kv"]
    layer = n_layers - 1
    conts = [p[layer][tables].reshape(len(lengths), max_seq, kvh, dh)
             for p in pools]
    q = rng.normal(size=(len(lengths), h, dh))
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    return (bf(q), bf(pools[0]), bf(pools[1]), [bf(c) for c in conts],
            jnp.int32(layer), jnp.asarray(tables), jnp.asarray(lengths))


def _paged_kernel(q, kp, vp, layer, tables, lengths):
    from jax.experimental.pallas import tpu as pltpu
    # uninitialised VMEM reads as NaN, so a buffer row the kernel reads but
    # never fetched shows
    return pa_ops.attention(q, kp, vp, layer, tables, lengths,
                            blocks_per_chunk=2,
                            interpret=pltpu.InterpretParams())


def _contiguous_decode(q, k, v, lengths, cfg):
    """The contiguous decode path's masked attention over [B, T] caches."""
    from repro.models import common
    t = jnp.arange(k.shape[1])
    pos = lengths[:, None] - 1
    m = common._mask(pos, t, causal=True, window=None, is_global=True)
    m = m & (t[None, :] < lengths[:, None])[:, None, :]
    k = common._repeat_kv(k, cfg.q_heads)
    v = common._repeat_kv(v, cfg.q_heads)
    return common.attention_scores(q[:, None], k, v, m)[:, 0]


@pytest.mark.parametrize("kvh,ratio", [(4, 1), (2, 7), (2, 8), (1, 48)])
def test_paged_attention_matches_reference_and_contiguous(kvh, ratio):
    from repro import configs
    from repro.models import common
    from repro.parallel.sharding import Sharder
    q, kp, vp, (kc, vc), layer, tables, lengths = _paged_case(kvh, ratio)
    cfg = configs.get_smoke("qwen3-32b").replace(
        num_heads=kvh * ratio, num_kv_heads=kvh, head_dim=q.shape[-1])
    got = np.asarray(_paged_kernel(q, kp, vp, layer, tables, lengths),
                     np.float32)
    assert np.isfinite(got).all()            # the free slot too
    assert np.abs(got).max() < 4
    reference = common._paged_attention_reference(
        q[:, None], kp, vp, layer, tables, lengths - 1, cfg, Sharder(None),
        scores_f32=True)[:, 0]
    contiguous = _contiguous_decode(q, kc, vc, lengths, cfg)
    # the model's reference is the contiguous path's arithmetic, bit for bit
    np.testing.assert_array_equal(np.asarray(reference, np.float32),
                                  np.asarray(contiguous, np.float32))
    for want in (reference, contiguous,
                 pa_ref.paged_attention(q, kp, vp, layer, tables, lengths)):
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   atol=PAGED_TOL, rtol=0)


def test_paged_attention_reads_only_live_blocks():
    """Blocks no slot's length reaches are never read: filled with NaN,
    they leave every output exactly as it was."""
    q, kp, vp, _, layer, tables, lengths = _paged_case(2, 8, seed=1)
    want = _paged_kernel(q, kp, vp, layer, tables, lengths)
    block = kp.shape[2]
    live = np.zeros(kp.shape[1], bool)
    for row, n in zip(np.asarray(tables), np.asarray(lengths)):
        live[row[:-(-n // block)]] = True
    dead = jnp.asarray(~live)[None, :, None, None, None]
    nan = jnp.asarray(np.nan, kp.dtype)
    got = _paged_kernel(q, jnp.where(dead, nan, kp), jnp.where(dead, nan, vp),
                        layer, tables, lengths)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
