"""Chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python benchmarks/chip/bench.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one run. It checks that JAX sees TPU v5e chips, as many as
the cell asks for or more, and fails otherwise (it never falls back to the
CPU); turns on the persistent compile cache inside the checkout; builds
the cell from its configuration and traffic files; warms up exactly the
shapes the cell uses; measures for ``--seconds``; checks what the timed
path produced against a plain float32 reference; and prints one JSON line
last on standard output. With ``--trace 0`` the line holds the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from
the engine's spans and counters and from a profiler trace of a stretch of
the window.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own that this script finds by the names in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``
(``read(run) -> float | None``), ``checks/<workload>.json`` (the limits of
the comparison), ``families/<family>.py`` (the family's weight layout and
work counts) and ``reference/<family>.py``.

Other modes, none of which the benchmark's own runs use:

    --selftest    check the trace reduction, the generator and the work
                  counts on the CPU; prints no device metric
    --rehearse    run a cell end to end on the CPU at the configuration
                  file's ``rehearsal`` sizes, with token lengths scaled to a
                  small engine; the line is marked as no device run and
                  carries no metrics
    --control     put the int8 control in the program's place in the
                  comparison: ``correct`` must come out false; the
                  program's own widest gap is printed beside it
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _env(chip: bool):
    """Keep every file the run writes inside the checkout: the compile
    cache at a fixed path (unless the caller names one) and the TPU
    runtime's logs (under /tmp otherwise). CPU modes keep no cache."""
    if chip:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              str(ROOT / ".jax_cache"))
    if "TPU_LOG_DIR" not in os.environ:
        (ROOT / ".tpu_logs").mkdir(exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]


def load_spec(workload: str) -> dict:
    """The cell's entry, its configuration, traffic, limits and metrics."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    spec = {
        "cell": cell,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (HERE / "checks" / f"{workload}.json").read_text()),
    }
    in_cell = lambda m: "workloads" not in m or workload in m["workloads"]
    spec["end_to_end"] = [m for m in bench["end_to_end"] if in_cell(m)]
    e2e = {m["name"] for m in spec["end_to_end"]}
    spec["per_layer"] = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m
            else m["moves"] in e2e)]
    return spec


def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def require_chips(n_chips: int):
    """The devices JAX found, when they are at least ``n_chips`` chips of a
    kind the peaks table holds; otherwise exit non-zero, naming what was
    found."""
    import jax
    import peaks
    devs = jax.devices()
    d = devs[0]
    found = f"platform={d.platform!r} kind={d.device_kind!r} count={len(devs)}"
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {found}")
    if d.device_kind not in peaks.PEAKS:
        raise SystemExit(f"bench: no peaks for this chip ({sorted(peaks.PEAKS)}"
                         f" known), found {found}")
    if len(devs) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chip(s), found "
                         f"{found}")
    return devs


def metrics_of(entries, run: dict) -> dict:
    out = {}
    for m in entries:
        v = reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(args, spec, rehearse: bool) -> dict:
    """One run of the cell; returns the result line as a dict. The driver
    is the module ``<driver>_driver`` that the traffic file names; it
    returns the checks, counts and the run record the readers take."""
    import jax
    import trace_reduce

    driver = importlib.import_module(f"{spec['traffic']['driver']}_driver")
    res = driver.run(
        spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, root=ROOT, rehearse=rehearse, control=args.control)
    run = res["run"]
    devs = jax.devices()
    run["device_kind"] = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    tr = run["trace"]
    if tr is not None:
        trace = trace_reduce.load(trace_reduce.find_trace(tr["dir"]))
        tr["reduced"] = trace_reduce.reduce(trace, driver.STEP_NAMES)
        line["breakdown"] = trace_reduce.breakdown(trace, tr["reduced"])
        device["busy_s"] = tr["reduced"]["busy_s"]
        device["window_s"] = tr["reduced"]["window_s"]
        shutil.rmtree(tr["dir"], ignore_errors=True)
    if rehearse:
        line["rehearsal"] = "CPU rehearsal size: not a device run, no metrics"
        line["counts"] = res["counts"]
    else:
        line["metrics"] = metrics_of(
            spec["per_layer"] if args.trace else spec["end_to_end"], run)
    line["device"] = device
    print("bench: " + json.dumps(res["extra"]), file=sys.stderr)
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in res["checks"]}
    for c in res["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return line


def selftest() -> None:
    """The trace reduction's arithmetic, the loader on a trace recorded here
    on the CPU, the generator's fixed work per seed, and the work counts'
    agreement with a direct count."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import families.dense as dense
    import trace_reduce
    import traffic

    trace_reduce.selftest()

    # dalek: allow[bare-jit] a program of the self-test's own, not the system's
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory(dir=ROOT) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("decode"):
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
        t = trace_reduce.load(glob.glob(f"{d}/**/*.xplane.pb",
                                        recursive=True)[0])
    w = trace_reduce.window_of(t)
    dec = [e for e in t["host"] if e[0] == "decode"]
    assert len(dec) == 3 and all(w[0] <= a <= b <= w[1] for _, a, b in dec), t
    red = trace_reduce.reduce(t)
    assert red["n_devices"] == 0 and red["window_s"] > 0, red  # CPU: no TPU

    mix = traffic.load_mix("chat-prefix")
    sizes = []
    for seed in (1, 2**31 + 5):
        g = traffic.generate(mix, seed=seed, seconds=20, vocab=1000,
                             batch_size=32)
        sizes.append(sorted((len(i.prompt), i.max_new_tokens)
                            for i in g["items"]))
        gaps = np.diff([i.arrival_s for i in g["items"]])
        assert (gaps >= 0).all() and g["items"][-1].arrival_s < 20
    assert sizes[0] == sizes[1], "seeds must get the same work"
    off = traffic.load_mix("offline-unshared")
    g = traffic.generate(off, seed=3, seconds=20, vocab=1000, batch_size=32)
    assert all(len(i.prompt) + i.max_new_tokens <= off["max_total"]
               for i in g["items"])

    cfg = {"num_hidden_layers": 2, "hidden_size": 8,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 4,
           "intermediate_size": 16, "vocab_size": 32}
    fl, by = dense.decode_step(cfg, [5, 7])
    per_tok = 2 * (2 * (8 * 4 * 4 * 2 + 8 * 2 * 4 * 2 + 3 * 8 * 16) + 8 * 32)
    attn = 4 * 4 * 4 * 2 * (5 + 7)
    assert fl == 2 * per_tok + attn, (fl, per_tok, attn)
    kv = 2 * 2 * 2 * 4 * 2 * (5 + 7)
    assert by == (per_tok // 2) * 2 + kv + 8 * 2 * 2, by
    pf, _ = dense.prefill(cfg, 3, 2)     # keys: 4 + 5
    assert pf == 2 * (per_tok // 2 - 8 * 32) * 2 + 2 * 8 * 32 + 4 * 4 * 4 * 2 * 9
    print("selftest ok: trace reduction, generator, work counts "
          "(CPU only, no device metric)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    _env(chip=not (args.selftest or args.rehearse))
    if args.selftest or args.rehearse:
        import jax
        jax.config.update("jax_platforms", "cpu")
        if args.selftest:
            selftest()
            return
    if not args.workload:
        ap.error("--workload is required")
    spec = load_spec(args.workload)
    if args.seconds is None:
        args.seconds = json.loads(
            (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        require_chips(spec["cell"]["chips"])
        enable_compile_cache()
    line = run_cell(args, spec, rehearse=args.rehearse)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
