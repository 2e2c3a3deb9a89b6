"""Observability layer tests: typed events, tracer, metrics registry, and
the energy-attributed Perfetto export (round-trip + sum-to-total)."""
import gc
import json
import threading

import numpy as np
import pytest

from profiling import profiled_host_events
from repro.obs import (NULL_SPAN, GcSpans, MetricsRegistry, SpanRecord,
                       TelemetryEvent, Tracer, chrome_trace, coerce_event,
                       events_from_meta, events_to_meta, parse_chrome_trace,
                       span_or_null, span_tree, validate_chrome_trace,
                       window_of, write_chrome_trace)

# -- typed telemetry events ----------------------------------------------------


def test_event_round_trip_flat_dict():
    ev = TelemetryEvent("prefill", 0.25, 32, {"s0": (1, 2)}, window=3,
                        t0=1.5, extra={"cached_tokens": 16})
    d = ev.as_dict()
    assert d["phase"] == "prefill" and d["cached_tokens"] == 16
    back = TelemetryEvent.from_dict(d)
    assert back == ev
    # mapping-style access for legacy consumers
    assert ev["wall_s"] == 0.25 and ev.get("missing") is None
    assert "cached_tokens" in ev and "window" in set(ev.keys())


def test_event_legacy_dict_coercion():
    # pre-schema log entry: no window/t0, unknown keys -> extra
    legacy = {"phase": "decode", "wall_s": 0.1, "n_tokens": 4,
              "groups": {"s1": [7]}, "batch": 4}
    ev = coerce_event(legacy)
    assert ev.window == -1 and ev.t0 == 0.0
    assert ev.groups == {"s1": (7,)} and ev.extra == {"batch": 4}
    assert window_of(ev) is None
    assert window_of(TelemetryEvent("p", 0.1, 1, {}, window=2)) == 2
    assert coerce_event(ev) is ev


def test_events_meta_round_trip():
    evs = [TelemetryEvent("prefill", 0.2, 8, {"s0": (0,)}, window=0),
           {"phase": "decode", "wall_s": 0.1, "n_tokens": 2, "groups": {}}]
    rows = events_to_meta(evs)
    assert all(isinstance(r, dict) for r in rows)
    json.dumps(rows)                               # meta footer serializable
    back = events_from_meta(rows)
    assert back[0] == evs[0]
    assert back[1].phase == "decode" and back[1].window == -1


# -- tracer --------------------------------------------------------------------


def test_tracer_nesting_and_attrs():
    tr = Tracer()
    with tr.span("outer", batch=4) as outer:
        with tr.span("inner") as inner:
            inner.set("window", 0)
        outer.update(done=True)
    recs = tr.spans()
    assert [r.name for r in recs] == ["outer", "inner"]  # start-time order
    by_name = {r.name: r for r in recs}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].attrs == {"batch": 4, "done": True}
    assert by_name["inner"].attrs == {"window": 0}
    assert by_name["outer"].t1 >= by_name["inner"].t1 >= by_name["inner"].t0
    tree = span_tree(recs)
    assert [r.name for r in tree[None]] == ["outer"]
    assert [r.name for r in tree[by_name["outer"].span_id]] == ["inner"]


def test_tracer_begin_is_not_a_parent_and_end_idempotent():
    tr = Tracer()
    h = tr.begin("queued", track="req0")
    with tr.span("step") as sp:
        pass
    h.end(finish_reason="eos")
    h.end(finish_reason="late")                    # idempotent: no-op
    by_name = {r.name: r for r in tr.spans()}
    assert by_name["step"].parent_id is None       # begin() doesn't nest
    assert by_name["queued"].attrs == {"finish_reason": "eos"}
    assert by_name["queued"].track == "req0"


def test_tracer_error_attr_instants_and_ring_drop():
    tr = Tracer(capacity=3)
    with pytest.raises(RuntimeError):
        with tr.span("bad"):
            raise RuntimeError("boom")
    tr.instant("finish", req=7)
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 3 and tr.n_dropped == 3
    assert tr.n_started == 6
    # the ring keeps the newest history
    assert [r.name for r in tr.spans()] == ["s1", "s2", "s3"]
    tr.clear()
    assert len(tr) == 0 and tr.n_dropped == 0
    # the error attr landed before the drop; re-check on a fresh tracer
    tr2 = Tracer()
    with pytest.raises(ValueError):
        with tr2.span("bad2"):
            raise ValueError()
    assert tr2.spans()[0].attrs["error"] == "ValueError"


def test_tracer_thread_safety():
    tr = Tracer()

    def worker(k):
        for i in range(50):
            with tr.span(f"w{k}", i=i):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.spans()
    assert len(recs) == 200 and tr.n_dropped == 0
    assert len({r.span_id for r in recs}) == 200   # ids unique across threads
    # per-thread nesting stacks: top-level spans have no cross-thread parent
    assert all(r.parent_id is None for r in recs)


def test_tracer_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_lexical_spans_land_in_the_profiler_trace(tmp_path):
    tr = Tracer()

    def work():
        h = tr.begin("queued", track="req0")
        with tr.span("outer"):
            with tr.span("inner", batch=2):
                pass
        with tr.span("decode_step", step_num=7, active=3):
            pass
        h.end()
        tr.instant("finish", track="req0")

    host = profiled_host_events(work, tmp_path)
    names = [e[0] for e in host]
    for name in ("outer", "inner", "decode_step"):
        assert names.count(name) == 1, name
    # non-lexical handles and instants stay out of the profiler's trace
    assert "queued" not in names and "finish" not in names
    step = next(e for e in host if e[0] == "decode_step")
    assert step[3].get("step_num") == 7          # a step annotation
    outer = next(e for e in host if e[0] == "outer")
    inner = next(e for e in host if e[0] == "inner")
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    # the step number is recorded on the span too
    rec = {r.name: r for r in tr.spans()}
    assert rec["decode_step"].attrs == {"step": 7, "active": 3}
    assert rec["inner"].attrs == {"batch": 2}


def test_gc_spans_record_pauses_on_their_thread():
    tr = Tracer()
    hook = GcSpans(tr)
    hook.install()
    hook.install()                                 # idempotent
    try:
        with tr.span("step"):
            gc.collect()
        other = threading.Thread(target=gc.collect)
        other.start()
        other.join()
    finally:
        hook.remove()
    gc.collect()                                   # removed: not recorded
    assert hook not in gc.callbacks
    recs = tr.spans()
    pauses = [r for r in recs if r.name == "gc"]
    assert len(pauses) == 1                        # not the other thread's
    step = next(r for r in recs if r.name == "step")
    assert pauses[0].parent_id == step.span_id
    assert pauses[0].attrs == {"generation": 2}
    assert step.t0 <= pauses[0].t0 <= pauses[0].t1 <= step.t1


def test_span_or_null():
    with span_or_null(None, "x", a=1) as sp:
        assert sp is NULL_SPAN
    tr = Tracer()
    with span_or_null(tr, "x", a=1) as sp:
        sp.set("b", 2)
    assert tr.spans()[0].attrs == {"a": 1, "b": 2}


# -- metrics registry ----------------------------------------------------------


def test_metrics_counter_gauge_histogram():
    m = MetricsRegistry()
    m.counter("reqs").inc()
    m.counter("reqs").inc(2, reason="eos")
    assert m.counter("reqs").total() == 3.0
    with pytest.raises(ValueError):
        m.counter("reqs").inc(-1)
    m.gauge("depth").set(5)
    m.gauge("depth").add(-2)
    h = m.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == pytest.approx(5.55)
    # same name returns the same instrument; kind mismatch raises
    assert m.counter("reqs") is m.counter("reqs")
    with pytest.raises(TypeError):
        m.gauge("reqs")


def test_metrics_snapshot_byte_deterministic(tmp_path):
    def build():
        m = MetricsRegistry()
        m.counter("b_second").inc(1, zone="z2")
        m.counter("b_second").inc(2, zone="z1")
        m.counter("a_first", "help text").inc()
        m.gauge("g").set(1.25)
        m.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
        return m

    j1, j2 = build().to_json(), build().to_json()
    assert j1 == j2                                # insertion-order invariant
    assert json.loads(j1) == build().snapshot()
    p = tmp_path / "m.json"
    build().write_json(p)
    assert p.read_text() == j1


def test_metrics_prometheus_exposition():
    m = MetricsRegistry()
    m.counter("tokens", "tokens emitted").inc(5)
    m.counter("finished").inc(2, reason="eos")
    m.histogram("step_s", buckets=(0.1,)).observe(0.05)
    text = m.prometheus()
    assert "# HELP tokens tokens emitted" in text
    assert "# TYPE tokens counter" in text
    assert 'finished{reason="eos"} 2' in text
    assert 'step_s_bucket{le="0.1"} 1' in text
    assert 'step_s_bucket{le="+Inf"} 1' in text
    assert "step_s_count 1" in text


# -- export: chrome trace ------------------------------------------------------


def _spans():
    return [
        SpanRecord(0, None, "prefill", "req0", 0.0, 0.2,
                   {"window": 0, "bucket": 16}),
        SpanRecord(1, None, "decode_step", "engine", 0.2, 0.3, {"window": 1}),
        SpanRecord(2, 1, "sample", "engine", 0.25, 0.28, {}),
        SpanRecord(3, None, "finish", "req0", 0.3, 0.3, {"reason": "eos"}),
    ]


def test_chrome_trace_energy_partition_and_round_trip(tmp_path):
    energies, walls = [2.5, 1.5], [0.2, 0.1]
    doc = chrome_trace(_spans(), energies, walls, meta={"process": "t"})
    validate_chrome_trace(doc)
    od = doc["otherData"]
    assert od["energy_total_j"] == pytest.approx(4.0)
    assert od["attributed_j"] == pytest.approx(4.0)      # exact partition
    assert od["n_spans"] == 4 and od["n_windows"] == 2
    # engine track is always the top timeline row (tid 0)
    names = {ev["args"]["name"]: ev["tid"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert names["engine"] == 0

    path = tmp_path / "t.json"
    write_chrome_trace(path, _spans(), window_energies=energies,
                       window_walls=walls, meta={"process": "t"})
    recs, summary = parse_chrome_trace(path)
    assert summary["parsed_attributed_j"] == pytest.approx(
        summary["attributed_j"])
    by_id = {r.span_id: r for r in recs}
    assert by_id[0].attrs["energy_j"] == pytest.approx(2.5)
    assert by_id[1].attrs["energy_j"] == pytest.approx(1.5)
    assert by_id[2].parent_id == 1 and by_id[2].name == "sample"
    assert by_id[3].t1 == by_id[3].t0              # instant survives
    assert by_id[0].track == "req0" and by_id[0].attrs["bucket"] == 16
    assert {r.span_id for r in recs} == {0, 1, 2, 3}
    for r, p in zip(sorted(recs, key=lambda r: r.span_id), _spans()):
        assert r.t0 == pytest.approx(p.t0, abs=1e-6)
        assert r.t1 == pytest.approx(p.t1, abs=1e-6)


def test_chrome_trace_rejects_double_claimed_window():
    spans = [SpanRecord(0, None, "a", "engine", 0.0, 0.1, {"window": 0}),
             SpanRecord(1, None, "b", "engine", 0.1, 0.2, {"window": 0})]
    with pytest.raises(ValueError, match="attributed twice"):
        chrome_trace(spans, [1.0], [0.1])


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"no": "traceEvents"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                               "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "?", "pid": 1, "tid": 0, "ts": 0}]})


def test_write_chrome_trace_session_xor_energies(tmp_path):
    class FakeSession:
        pass

    with pytest.raises(ValueError, match="not both"):
        write_chrome_trace(tmp_path / "t.json", [], session=FakeSession(),
                           window_energies=[1.0])


# -- acceptance: live engine -> timeline, joules sum to the report -------------


@pytest.fixture(scope="module")
def engine_run():
    import jax
    from repro import configs
    from repro.models import build_model
    from repro.serve.engine import ContinuousEngine, Request

    cfg = configs.get_smoke("gemma3-27b")
    model = build_model(cfg, q_block=8)
    params, _ = model.init(jax.random.key(0))
    eng = ContinuousEngine(model, params, batch_size=2, max_seq=32)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    stats = eng.serve(reqs)
    return eng, stats


def test_engine_trace_export_sums_to_report(engine_run, tmp_path):
    eng, _ = engine_run
    path = tmp_path / "serve.json"
    write_chrome_trace(path, eng.tracer, session=eng.tel.session,
                       meta={"process": "test"})
    recs, summary = parse_chrome_trace(path)
    report = eng.tel.session.report()
    # the ISSUE acceptance bar: per-span joules partition the session total
    assert summary["attributed_j"] == pytest.approx(report.energy_j,
                                                    abs=1e-6)
    assert summary["parsed_attributed_j"] == pytest.approx(report.energy_j,
                                                           abs=1e-6)
    # window-referencing spans partition the total; lifecycle spans also
    # carry a tag-bus energy_j attr (request energy) which is NOT part of
    # the window partition and must not be double-counted
    span_sum = sum(r.attrs.get("energy_j", 0.0) for r in recs
                   if "window" in r.attrs or "windows" in r.attrs)
    assert span_sum == pytest.approx(report.energy_j, abs=1e-6)
    # lifecycle spans present per request, engine steps on the engine track
    names = {r.name for r in recs}
    assert {"queued", "prefill", "decode", "finish",
            "decode_step"} <= names
    tracks = {r.track for r in recs}
    assert "engine" in tracks and any(t.startswith("req") for t in tracks)


def test_recorded_trace_replays_into_timeline(engine_run, tmp_path):
    from repro.obs import timeline_from_trace
    from repro.tracestore import TraceReader, record_engine

    eng, _ = engine_run
    path = tmp_path / "run.dkt"
    record_engine(eng.tel, str(path))
    doc = timeline_from_trace(TraceReader(str(path)))
    validate_chrome_trace(doc)
    od = doc["otherData"]
    # the recorded chunks carry the same joules the live session measured,
    # and every window is claimed by exactly one phase span
    assert od["attributed_j"] == pytest.approx(
        eng.tel.session.report().energy_j, abs=1e-6)
    assert od["n_spans"] == len(eng.tel.events)
    phases = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    assert {"prefill", "decode"} <= phases


def test_engine_metrics_registry(engine_run):
    eng, stats = engine_run
    snap = eng.metrics.snapshot()
    assert {"tokens_decoded", "requests_submitted", "requests_finished",
            "decode_step_s", "engine_energy_j"} <= set(snap)
    assert snap["decode_step_s"]["kind"] == "histogram"
    total = eng.metrics.counter("tokens_decoded").total()
    assert total == stats["tokens_decoded"] > 0
    # prometheus text renders without error and mentions the counters
    assert "tokens_decoded" in eng.metrics.prometheus()
