"""The comparison that decides ``correct`` must fail a broken timed path.

Each test drives a whole run of a serving cell on the CPU at the
rehearsal sizes of the cell's configuration file (``--rehearse``: the
harness's look for a chip is skipped, everything else runs), with one
fault planted in the engine underneath, and expects ``correct`` to come
out false. A last test runs the same path unbroken and expects it true, so
the faults are what fails.

    python -m pytest benchmarks/chip/tests
"""
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402

bench._env(chip=False)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import serve_driver  # noqa: E402
from repro.serve.state import PrefillOutcome  # noqa: E402

CHAT = "qwen3-32b.chat-prefix"
OFFLINE = "qwen3-32b.offline-unshared"


def _run(workload, hook, seed=3):
    spec = bench.load_spec(workload)
    return serve_driver.run(spec, seed=seed, seconds=10.0, trace=False,
                            t_start=time.perf_counter(), root=bench.ROOT,
                            rehearse=True, engine_hook=hook)


def altered_decode_token(eng):
    """Every decoded token is replaced by its successor where the fused
    step produces it."""
    step, vocab = eng.adapter.decode_step, eng.model.cfg.vocab_size
    eng.adapter.decode_step = lambda t, p: (step(t, p) + 1) % vocab


def altered_first_token(eng):
    """The token that prefill samples is replaced by its successor."""
    prefill, vocab = eng.adapter.prefill, eng.model.cfg.vocab_size

    def wrong(slot, req):
        out = prefill(slot, req)
        if out.first_token is None:
            return out
        return PrefillOutcome((out.first_token + 1) % vocab,
                              out.cached_tokens, out.computed_tokens)
    eng.adapter.prefill = wrong


def state_unchanged(eng):
    """The decode step returns the KV pool as it found it."""
    ad = eng.adapter
    step = ad.decode_step

    def frozen(t, p):
        before = ad.caches
        out = step(t, p)
        ad.caches = before
        return out
    ad.decode_step = frozen


def half_batch_left_out(eng):
    """The second half of the batch's rows is not computed: those slots get
    token 0."""
    step = eng.adapter.decode_step

    def half(t, p):
        out = step(t, p)
        return out.at[out.shape[0] // 2:].set(0)
    eng.adapter.decode_step = half


def stale_prefix_cache(eng):
    """The KV that the prefix cache holds is lost (zeroed) after set-up, so
    prompts served from it attend to nothing."""
    eng.adapter.caches = jax.tree.map(jnp.zeros_like, eng.adapter.caches)


FAULTS = [altered_decode_token, altered_first_token, state_unchanged,
          half_batch_left_out]


@pytest.mark.parametrize("workload", [CHAT, OFFLINE])
@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(workload, fault):
    res = _run(workload, fault)
    assert not res["correct"], res["checks"]


def test_stale_prefix_cache_is_not_correct():
    res = _run(CHAT, stale_prefix_cache)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", [CHAT, OFFLINE])
def test_unbroken_run_is_correct(workload):
    res = _run(workload, None)
    assert res["correct"], res["checks"]
