"""Runs the multi-device test files in a subprocess with 8 host devices.

The main pytest process sees 1 CPU device (smoke tests must run unsharded,
per the dry-run contract), so the sharded-parity suites
(test_distributed.py, test_moe_parallel.py, the guarded test in
test_compress.py) would otherwise be skipped. This wrapper gives them a
dedicated interpreter on the CPU backend with
XLA_FLAGS=--xla_force_host_platform_device_count=8: forced host devices run
the same meshes, sharding rules and collectives, which is what catches a
wrong mesh or a wrong rule. The child never touches an accelerator.
"""
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).parent
REPO = HERE.parent


@pytest.mark.parametrize("target", [
    "tests/test_moe_parallel.py",
    "tests/test_compress.py::test_compressed_psum_matches_fp32_within_tolerance",
    "tests/test_distributed.py",
])
def test_multidevice(target):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", target, "-q", "--no-header", "-p",
         "no:cacheprovider"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"\n{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    assert " passed" in r.stdout and "skipped" not in r.stdout, r.stdout[-3000:]
