"""The serving engine's phase spans, the named scopes of its device steps,
and the benchmark readers that take both.

A paged ``ContinuousEngine`` serves a few requests under a CPU profiler
trace: every decode step records ``step_prepare``, ``decode_step`` (with
``device_wait`` and ``telemetry`` inside it) and ``emit`` under one
``step``, each lexical span lands in the profiler's trace, and the
request-lifecycle spans the benchmark reads keep their names and
attributes."""
import collections
import importlib.util
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from profiling import profiled_host_events

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
STEP_PHASES = ("step_prepare", "decode_step", "device_wait", "emit")
# host annotations the benchmark's trace reduction keeps by name
HARNESS_NAMES = ("admit", "decode", "wait", "bench_window")


@pytest.fixture(scope="module")
def paged_run(tmp_path_factory):
    from repro import configs
    from repro.models import build_model
    from repro.serve.engine import ContinuousEngine, Request

    cfg = configs.get_smoke("qwen3-32b")
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(0))
    # 8-token blocks: the longest request's decode crosses a block edge
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=64,
                           kv_block_size=8)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)

    def reqs(base):
        # a shared 32-token prefix: the second wave hits the prefix cache
        return [Request(base + i, np.concatenate(
            [prefix, rng.integers(0, cfg.vocab_size, 4 + i).astype(np.int32)]),
            max_new_tokens=3 + i) for i in range(3)]

    eng.serve(reqs(0))                 # compiles outside the trace
    eng.tracer.clear()
    host = profiled_host_events(lambda: eng.serve(reqs(10)),
                                tmp_path_factory.mktemp("trace"))
    return eng, eng.tracer.spans(), host


def test_every_decode_step_has_its_phases(paged_run):
    _, spans, _ = paged_run
    by_step = collections.defaultdict(collections.Counter)
    for s in spans:
        if "step" in s.attrs:
            by_step[s.attrs["step"]][s.name] += 1
    steps = [s for s in spans if s.name == "decode_step"]
    assert len(steps) >= 4
    for s in steps:
        got = by_step[s.attrs["step"]]
        assert all(got[p] == 1 for p in STEP_PHASES), got
        assert got["telemetry"] == 1
    ids = {s.span_id: s for s in spans}
    for s in spans:
        if s.name in ("device_wait", "telemetry") and "step" in s.attrs:
            parent = ids[s.parent_id]
            assert parent.name == "decode_step"
            assert parent.attrs["step"] == s.attrs["step"]
            assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    prep = [s for s in spans if s.name == "step_prepare"]
    assert all({"blocks_allocated", "blocks_scrubbed", "cow"}
               <= set(s.attrs) for s in prep)
    assert sum(s.attrs["blocks_allocated"] for s in prep) > 0
    emits = [s for s in spans if s.name == "emit"]
    assert sum(s.attrs["finished"] for s in emits) == 3


def test_admission_and_prefill_spans(paged_run):
    _, spans, _ = paged_run
    adm = [s for s in spans if s.name == "admission"]
    assert sum(s.attrs["admitted"] for s in adm) == 3
    assert all(s.attrs["shed"] == 0 for s in adm)
    ids = {s.span_id: s for s in spans}
    waits = [s for s in spans if s.name == "prefill_wait"]
    assert len(waits) == 3
    assert all(ids[s.parent_id].name == "admission" for s in waits)
    tel = [s.attrs["phase"] for s in spans if s.name == "telemetry"]
    assert tel.count("prefill") == 3


def test_lifecycle_spans_keep_names_and_attrs(paged_run):
    _, spans, _ = paged_run
    names = collections.Counter(s.name for s in spans)
    assert names["queued"] == names["prefill"] == names["decode"] == 3
    assert names["finish"] == 3 and "admitted" not in names
    pf = [s for s in spans if s.name == "prefill"]
    assert all({"req_id", "slot", "bucket", "cached_tokens",
                "computed_tokens", "window"} <= set(s.attrs) for s in pf)
    assert sum(s.attrs["cached_tokens"] for s in pf) > 0
    q = [s for s in spans if s.name == "queued"]
    assert all(s.attrs["req_id"] == int(s.track[3:]) for s in q)
    steps = [s for s in spans if s.name == "decode_step"]
    assert all({"active", "queue_depth", "window", "step"} <= set(s.attrs)
               for s in steps)
    assert [s.attrs["step"] for s in steps] == sorted(
        {s.attrs["step"] for s in steps})


def test_profiler_trace_holds_the_program_phases(paged_run):
    eng, spans, host = paged_run
    names = collections.Counter(e[0] for e in host)
    for name in STEP_PHASES + ("telemetry", "admission", "prefill_wait"):
        assert names[name] > 0, name
    assert names["decode_step"] == sum(
        1 for s in spans if s.name == "decode_step")
    # lifecycle handles are not mirrored, and no program annotation takes a
    # name that the benchmark's own annotations use
    assert not {"queued", "prefill", "finish"} & set(names)
    assert not set(HARNESS_NAMES) & set(names)
    nums = sorted(e[3]["step_num"] for e in host if e[0] == "decode_step")
    assert nums == [s.attrs["step"] for s in spans if s.name == "decode_step"]
    assert nums[-1] == eng.n_decode_steps - 1


# -- named scopes of the paged steps ----------------------------------------


@pytest.fixture(scope="module")
def paged_step_text():
    from repro import configs
    from repro.models import abstract_params, build_model
    from repro.serve.step import make_paged_decode_step

    cfg = configs.get_smoke("qwen3-32b")
    model = build_model(cfg, q_block=8)
    params = abstract_params(model)[0]
    b, block, nb = 2, 8, 4
    pool = jax.eval_shape(lambda: model.init_cache(b * nb + 1, block))
    lowered = jax.jit(make_paged_decode_step(model)).lower(
        params, jax.ShapeDtypeStruct((b, 1), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b, nb), jnp.int32), pool)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["kv_gather", "kv_repeat", "attention",
                                   "kv_scatter", "mlp", "lm_head", "sample"])
def test_paged_decode_step_names_its_layers(paged_step_text, scope):
    # an op inside the scope ("attention/dot_general"), or a call made
    # under it ("jit(paged_decode_step)/sample"); a bare function-name
    # location ("attention") does not count
    assert re.search(rf'["/]{scope}/|\)/{scope}"', paged_step_text)


# -- the benchmark's readers of the phase spans ------------------------------


def _reader(name):
    if str(CHIP) not in sys.path:
        sys.path.append(str(CHIP))
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), CHIP / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run_record():
    """A 10 s window at t = 100 s: three decode steps of 100 ms, host work
    around each, one prefill, two gc pauses and a telemetry span that
    crosses the window's start."""
    sp = []

    def step(k, t, prep, wait, tel, emit):
        sp.append(("step_prepare", t, t + prep, {"step": k}))
        t += prep
        a, b = t + 0.002, t + 0.002 + wait
        sp.append(("decode_step", t, b + tel, {"step": k, "active": 2}))
        sp.append(("device_wait", a, b, {"step": k}))
        sp.append(("telemetry", b, b + tel, {"step": k, "phase": "decode"}))
        sp.append(("emit", b + tel, b + tel + emit, {"step": k}))

    step(0, 101.0, 0.001, 0.100, 0.0005, 0.001)   # host 1+2+0.5+1 = 4.5 ms
    step(1, 102.0, 0.003, 0.100, 0.0005, 0.001)   # 6.5 ms
    step(2, 103.0, 0.001, 0.100, 0.0005, 0.002)   # 5.5 ms
    step(3, 109.99, 0.001, 0.100, 0.0, 0.001)     # ends past the window
    sp += [("admission", 104.0, 105.0, {"admitted": 1, "shed": 0}),
           ("prefill_wait", 104.2, 105.0, {}),
           ("telemetry", 99.5, 100.5, {"phase": "prefill"}),
           ("gc", 106.0, 106.01, {"generation": 0}),
           ("gc", 109.99, 110.02, {"generation": 2}),
           ("queued", 98.0, 104.0, {"req_id": 1})]
    return {"window": (100.0, 110.0), "window_s": 10.0, "spans": sp}


# device_wait: 3 x 0.1 + 0.007 (clipped) = 0.307 s; prefill_wait 0.8 s
@pytest.mark.parametrize("name,want", [
    ("decode_host_ms.chat", 5.5),
    ("host_gap_share.offline", 100.0 * (10.0 - 0.307 - 0.8) / 10.0),
    ("telemetry_share.offline", 100.0 * (0.5 + 3 * 0.0005) / 10.0),
    ("gc_pause_share.chat", 100.0 * (0.01 + 0.01) / 10.0),
])
def test_phase_readers_on_a_hand_made_run(name, want):
    assert _reader(name)(_run_record()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", ["decode_host_ms.chat",
                                  "host_gap_share.offline",
                                  "telemetry_share.offline",
                                  "gc_pause_share.chat"])
def test_phase_readers_give_nothing_without_phase_spans(name):
    """A program that records no phase spans (only the lifecycle spans and
    ``decode_step``) leaves these metrics out of its line."""
    run = _run_record()
    run["spans"] = [s for s in run["spans"]
                    if s[0] in ("decode_step", "queued")]
    for s in run["spans"]:
        s[3].pop("step", None)
    assert _reader(name)(run) is None


def test_gc_share_reads_zero_without_pauses():
    run = _run_record()
    run["spans"] = [s for s in run["spans"] if s[0] != "gc"]
    assert _reader("gc_pause_share.chat")(run) == 0.0
