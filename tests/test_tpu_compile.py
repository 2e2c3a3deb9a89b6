"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with libtpu and compiles for a topology it is
only told about. That catches what the Pallas interpreter and the CPU
backend never see: block shapes that break the (8, 128) tiling rule, kernels
that use more VMEM than a core has, programs that do not fit HBM. Nothing
here runs, so nothing here is a timing.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and test workers that disagree about which tests
exist run none at all.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.hw import TPU_V5E
from repro.kernels.dpa_matmul.dpa_matmul import dpa_matmul
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.stream import stream
from repro.models import abstract_params, build_model
from repro.serve.paging import resolve_kv_block_size
from repro.serve.step import make_paged_decode_step, make_paged_slot_prefill


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        # libtpu reads this when it loads; unset, or naming no directory,
        # it logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get(
            "TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs"))))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    q = _sds((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q))


@pytest.mark.parametrize("variant,dtype", [("fma_f32", jnp.float32),
                                           ("dpa2", jnp.bfloat16),
                                           ("dpa4", jnp.int8)])
def test_dpa_matmul_compiles(one_chip, variant, dtype):
    a = _sds((4096, 4096), dtype, one_chip)
    _assert_kernel(_compile(
        lambda a, b: dpa_matmul(a, b, variant=variant), a, a))


@pytest.mark.parametrize("op", ["copy", "scale", "triad", "read"])
def test_stream_kernel_compiles(one_chip, op):
    a = _sds((8192, 1024), jnp.float32, one_chip)
    fns = {"copy": lambda a, b: stream.stream_copy(a),
           "scale": lambda a, b: stream.stream_scale(a, 1.5),
           "triad": lambda a, b: stream.stream_triad(a, b, 1.5),
           "read": lambda a, b: stream.stream_read(a)}
    _assert_kernel(_compile(fns[op], a, a))


@pytest.fixture(scope="module")
def granite_paged(one_chip):
    """granite-20b at its published widths, cut to one layer, with the
    paged-KV serving layout that chip_smoke.py drives (batch 8, max_seq
    2048, auto block size, one pool block per slot block plus the null
    block)."""
    cfg = configs.get("granite-20b").replace(num_layers=1)
    model = build_model(cfg, q_block=64)
    params, _ = abstract_params(model)
    params = jax.tree.map(lambda p: _sds(p.shape, p.dtype, one_chip), params)
    batch, max_seq = 8, 2048
    block = resolve_kv_block_size("auto", max_seq, True)
    n_slot_blocks = max_seq // block
    pool = jax.eval_shape(
        lambda: model.init_cache(batch * n_slot_blocks + 1, block))
    pool = jax.tree.map(lambda c: _sds(c.shape, c.dtype, one_chip), pool)
    return model, params, pool, batch, n_slot_blocks


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < TPU_V5E.mem_gb * 1e9, used


def test_granite_paged_decode_step_compiles(one_chip, granite_paged):
    model, params, pool, batch, nb = granite_paged
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)
    compiled = _compile(make_paged_decode_step(model), params,
                        i32(batch, 1), i32(batch), i32(batch, nb), pool)
    _fits_one_chip(compiled)


def test_granite_bucketed_paged_prefill_compiles(one_chip, granite_paged):
    model, params, pool, _, nb = granite_paged
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)
    compiled = _compile(make_paged_slot_prefill(model, bucketed=True),
                        params, i32(1, 1024), i32(), i32(), i32(nb), pool)
    _fits_one_chip(compiled)
