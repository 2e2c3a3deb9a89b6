"""The control: the plain reference, put in the program's place at the
precision below the configuration's (int8 weights and activations for a
bf16 configuration), must come out as not correct.

A run with ``control=True`` (``bench.py --control``) feeds the control's
choices through the same comparison and limit as the program's; its
``correct`` must be false, and the same run's own program reading must
sit under the limit. On the chip this is run at each cell's own size
(PERF.md); here it runs at the configuration's rehearsal sizes on the CPU,
against each cell's rehearsal limit of the mean gap, over three seeds, in
windows of ``SECONDS`` so that each sample holds some hundred served
tokens (a mean over a few dozen swings too far).

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

jax.config.update("jax_platforms", "cpu")

import serve_driver  # noqa: E402

SECONDS = 10.0


@pytest.mark.parametrize("seed", [21, 22, 23])
@pytest.mark.parametrize("workload", ["qwen3-32b.chat-prefix",
                                      "qwen3-32b.offline-unshared"])
def test_control_is_not_correct(workload, seed):
    spec = bench.load_spec(workload)
    res = serve_driver.run(spec, seed=seed, seconds=SECONDS, trace=False,
                           t_start=time.perf_counter(), root=bench.ROOT,
                           rehearse=True, control=True)
    limit = spec["limits"]["rehearsal_mean_gap"]
    assert not res["correct"], res["checks"]
    assert res["extra"]["program_mean_gap"] <= limit, (res["extra"], limit)
