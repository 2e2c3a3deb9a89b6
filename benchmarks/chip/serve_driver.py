"""Serving driver: one cell of a served model, driven through the program's
``ContinuousEngine`` under an open-loop or a backlog mix.

Set-up builds the engine as ``launch.serve.serve`` does, with weights that
the benchmark makes from the seed; warms up exactly the prefill buckets the
mix can reach, the decode step and the pool's block scrubber; and, for a
mix with shared prefixes, serves each prefix once so that the prefix cache
holds them as a running server's would. The window then feeds the engine
its requests on their schedule. The engine has no intake while it runs
(``run()`` drains a list submitted up front), so the harness calls its two
steps itself: ``_admit()`` and ``_decode_once()``.

Times are the host's ``perf_counter``. The harness records, around its own
calls into the engine, when each token was emitted (a wrapper on the
engine's ``_emit``) and which decode step emitted it; the engine's own
spans and counters are read after the window.
"""
from __future__ import annotations

import contextlib
import math
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import check
import families
import traffic
import weights

TRACE_SECONDS = 4.0     # the traced stretch of a ``--trace 1`` run
STEP_NAMES = ("paged_decode_step", "paged_bucketed_slot_prefill")
# A request due in the window that finishes late is late, not wrong: the
# drain waits for it as long as the run's allowance leaves room, and at least
# DRAIN_LIMIT_S past the close; its latency counts the wait.
DRAIN_LIMIT_S = 60.0
RUN_LIMIT_S = 360.0     # a run's whole allowance, from process start
AFTER_DRAIN_S = 90.0    # kept for the reference, the trace and the exit
REHEARSAL = {"batch_size": 4, "max_seq": 256}
OK_FINISH = ("length", "eos")


def resolved(cfg: dict, rehearse: bool) -> dict:
    """The configuration as run: the file's own sizes, or, for a CPU
    rehearsal, those sizes with the file's ``rehearsal`` overrides (small
    enough for a CPU, wide enough that the int8 control still separates
    from bf16)."""
    return dict(cfg, **(cfg["rehearsal"] if rehearse else {}))


def log(t_start: float, what: str):
    print(f"bench: {time.perf_counter() - t_start:8.2f} s {what}",
          file=sys.stderr, flush=True)


class Recorder:
    """Harness-side record of the window: each token's emission time and
    decode step, each call into the engine, and the host annotations that
    name those calls in a profiler trace."""

    def __init__(self, eng, annotate: bool):
        self.tokens: Dict[int, List] = {}
        self.calls: List[tuple] = []
        self.step = -1
        self.annotate = annotate
        emit = eng._emit

        def recorded_emit(slot, tok):
            self.tokens.setdefault(slot.req.req_id, []).append(
                (time.perf_counter(), self.step))
            emit(slot, tok)

        eng._emit = recorded_emit

    @staticmethod
    def detach(eng):
        """Drop the wrapper (it holds the engine in a reference cycle)."""
        del eng._emit

    @contextlib.contextmanager
    def call(self, kind: str, step: int = -1):
        self.step = step
        ann = (jax.profiler.TraceAnnotation(kind) if self.annotate
               else contextlib.nullcontext())
        t = time.perf_counter()
        with ann:
            yield
        self.calls.append((kind, t, time.perf_counter(), step))
        self.step = -1


class Profiler:
    """Starts and stops one ``jax.profiler`` trace over a stretch of the
    window, wrapped in the ``trace_reduce.WINDOW`` annotation."""

    def __init__(self, log_dir: str, start_after: float):
        self.log_dir = log_dir
        self.start_after = max(0.0, start_after)
        self.state = "idle"
        self.t0 = self.t1 = None
        self._ann = None

    def tick(self, now: float, window_t0: float):
        import trace_reduce
        if self.state == "idle" and now >= window_t0 + self.start_after:
            shutil.rmtree(self.log_dir, ignore_errors=True)
            # no Python-function tracer: it records every call in the host
            # loop and slows it; the harness's annotations are enough
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._ann.__enter__()
            self.t0 = time.perf_counter()
            self.started_in = self.t0 - now
            self.state = "on"

    def stop(self):
        if self.state != "on":
            return
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def warm_buckets(eng, mix: dict, scale: float) -> List[int]:
    """Every prefill bucket the mix can reach: all edges from the bucket of
    its shortest possible tail to that of its longest prompt. With shared
    prefixes a tail can be anything from the user turn alone (prefix
    cached) to the whole prompt (prefix evicted)."""
    from repro.serve.step import bucket_for
    lo = traffic._scaled(mix["prompt"], scale)["lognormal"]["min"]
    hi = traffic.max_prompt_tokens(mix, scale)
    b_lo, b_hi = bucket_for(lo, eng.buckets), bucket_for(hi, eng.buckets)
    return [b for b in eng.buckets if b_lo <= b <= b_hi]


def warm_up(eng, mix: dict, scale: float, vocab: int):
    """Compile (or load from the persistent cache) every program the window
    runs, then reset the engine's statistics and prefix cache."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(12345)
    buckets = warm_buckets(eng, mix, scale)
    reqs = [Request(-1 - i, rng.integers(0, vocab, b - 1).astype(np.int32),
                    max_new_tokens=2) for i, b in enumerate(buckets)]
    eng.serve(reqs)
    # a second pass allocates after the first one freed blocks: the pool's
    # scrubber runs (and compiles) here, not in the window
    eng.serve([Request(-100, rng.integers(0, vocab, buckets[0] - 1)
                       .astype(np.int32), max_new_tokens=2)])
    eng.reset_metrics()
    return buckets


def warm_prefixes(eng, gen: dict, mix: dict, scale: float, vocab: int):
    """Serve each shared prefix once, followed by a shortest user turn, so
    the prefix cache holds them as it would on a running server."""
    from repro.serve.engine import Request
    if not gen["prefixes"]:
        return
    rng = np.random.default_rng(54321)
    n = traffic._scaled(mix["prompt"], scale)["lognormal"]["min"]
    eng.serve([Request(-200 - k, np.concatenate(
        [p, rng.integers(0, vocab, n).astype(np.int32)]), max_new_tokens=1)
        for k, p in enumerate(gen["prefixes"])])


class CompileCounter:
    """Counts JAX compile events while ``on``: any compile in the window is
    a fault of the warm-up."""

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if self.on and "compile" in event:
            self.n += 1


def run(spec: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, root, rehearse: bool = False,
        engine_hook: Optional[Callable] = None,
        control: bool = False) -> dict:
    """Set up, measure and check one run of a serving cell. Returns what
    the readers and the result line need. With ``control`` the int8
    control's choices take the program's place in the comparison."""
    from repro.configs.base import ModelConfig
    from repro.models import abstract_params, build_model
    from repro.serve.engine import ContinuousEngine, Request

    mix = spec["traffic"]
    ecfg = dict(spec["config"]["engine"], **(REHEARSAL if rehearse else {}))
    scale = ecfg["max_seq"] / spec["config"]["engine"]["max_seq"]
    wcfg = resolved(spec["config"], rehearse)
    mcfg = ModelConfig(**families.of(wcfg).program_config(wcfg))
    counter = CompileCounter()

    model = build_model(mcfg, q_block=min(64, traffic.max_prompt_tokens(
        mix, scale)))
    params = weights.make_params(wcfg, seed)
    weights.check_layout(params, abstract_params(model)[0])
    log(t_start, "weights made")
    eng = ContinuousEngine(
        model, params, batch_size=ecfg["batch_size"],
        max_seq=ecfg["max_seq"], prefill_buckets=ecfg["prefill_buckets"],
        kv_block_size=ecfg["kv_block_size"],
        prefix_cache=ecfg["prefix_cache"])
    gen = traffic.generate(mix, seed=seed, seconds=seconds,
                           vocab=mcfg.vocab_size,
                           batch_size=ecfg["batch_size"], scale=scale)
    buckets = warm_up(eng, mix, scale, mcfg.vocab_size)
    log(t_start, f"warmed up: buckets {buckets}, compiles "
                 f"{eng.trace_stats.snapshot()}")
    warm_prefixes(eng, gen, mix, scale, mcfg.vocab_size)
    eng.tracer.clear()
    eng.metrics.clear()
    eng.finished = []
    if engine_hook is not None:
        engine_hook(eng)
    rec = Recorder(eng, annotate=trace)
    # the engine's span clock is perf_counter less its epoch
    span_offset = time.perf_counter() - eng.tracer.now()
    compiles_before = eng.trace_stats.compiles()
    setup_s = time.perf_counter() - t_start
    log(t_start, "window opens")

    items = gen["items"]
    open_loop = gen["kind"] != "backlog"
    depth = (mix["arrivals"].get("per_slot", 0) * ecfg["batch_size"])
    reqs: Dict[int, object] = {}
    arrival: Dict[int, float] = {}
    # the traced stretch is the window's last TRACE_SECONDS: steady by then,
    # and the trace is written out after the close, not inside the window
    prof = (Profiler(str(root / ".bench_trace"), seconds - TRACE_SECONDS)
            if trace else None)
    nxt = n_steps = 0

    def submit(item, now):
        r = Request(item.idx, item.prompt, max_new_tokens=item.max_new_tokens)
        reqs[item.idx] = r
        arrival[item.idx] = (t0 + item.arrival_s if open_loop else now)
        eng.submit(r)

    counter.on = True
    t0 = time.perf_counter()
    t_close = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if prof is not None:
            prof.tick(now, t0)
        if open_loop:
            while nxt < len(items) and t0 + items[nxt].arrival_s <= now:
                submit(items[nxt], now)
                nxt += 1
        else:
            while len(eng.queue) < depth:
                if nxt >= len(items):
                    items.extend(gen["more"]())
                submit(items[nxt], now)
                nxt += 1
        with rec.call("admit"):
            eng._admit()
        if eng.slots.n_active:
            with rec.call("decode", n_steps):
                eng._decode_once()
            n_steps += 1
        elif open_loop:
            due = (t0 + items[nxt].arrival_s if nxt < len(items)
                   else t_close)
            with rec.call("wait"):
                time.sleep(max(0.0, min(due, t_close) - time.perf_counter()))
    if prof is not None:
        prof.stop()
        log(t_start, f"trace started in {prof.started_in:.3f} s, written in "
                     f"{time.perf_counter() - prof.t1:.3f} s")

    # drain: every request due in the window runs to its end
    if open_loop:
        while nxt < len(items):
            submit(items[nxt], time.perf_counter())
            nxt += 1
        due_ids = set(reqs)
        limit = max(t_close + DRAIN_LIMIT_S,
                    t_start + RUN_LIMIT_S - AFTER_DRAIN_S)
        while (any(not reqs[i].done for i in due_ids)
               and time.perf_counter() < limit):
            with rec.call("admit"):
                eng._admit()
            if eng.slots.n_active:
                with rec.call("decode", n_steps):
                    eng._decode_once()
                n_steps += 1
    counter.on = False
    log(t_start, f"window closed, drained; {n_steps} decode steps")
    compiles_in_window = (eng.trace_stats.compiles() - compiles_before
                          + counter.n)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    spans = [(s.name, s.t0 + span_offset, s.t1 + span_offset, dict(s.attrs))
             for s in eng.tracer.spans()]
    if open_loop:
        due = [reqs[i] for i in sorted(reqs)]
    else:
        due = [r for r in eng.finished if r.req_id in reqs
               and rec.tokens.get(r.req_id)
               and rec.tokens[r.req_id][-1][0] <= t_close]
    requests = [{
        "id": r.req_id, "arrival": arrival[r.req_id],
        "prompt_len": len(r.prompt), "cached": r.cached_prompt_tokens,
        "tokens": rec.tokens.get(r.req_id, []),
        "finish": r.finish_reason if r.done else "unfinished",
    } for r in due]
    # every request that emitted a token in the window, for work counts
    all_tokens = {i: rec.tokens[i] for i in rec.tokens if i in reqs}
    prompt_len = {i: len(reqs[i].prompt) for i in reqs}
    finished = [r for r in due if r.done and r.finish_reason in OK_FINISH]
    # free the program's serving state before the reference runs
    eng.adapter.caches = None
    Recorder.detach(eng)
    del eng

    failed = sum(1 for q in requests if q["finish"] not in OK_FINISH)
    pad = 64 if rehearse else 512
    ref = check.Reference(wcfg, pad * math.ceil(
        traffic.max_total_tokens(mix, scale) / pad),
        traffic.max_output_tokens(mix, scale))
    picked = check.sample(finished, seed)
    got = ref.gap_stats(params, picked)
    log(t_start, f"reference compared: program's mean gap {got['mean']!r}, "
                 f"widest {got['max']!r}")
    extra = {"sampled_requests": len(picked), "checked_tokens": got["tokens"],
             "program_mean_gap": got["mean"], "program_max_gap": got["max"]}
    if control:
        got = ref.gap_stats(params, picked, control=True)
        extra.update(control_mean_gap=got["mean"], control_max_gap=got["max"])
        log(t_start, f"control compared: its mean gap {got['mean']!r} "
                     f"(widest {got['max']!r}) takes the program's place")
    limits = spec["limits"]
    checks = [
        check.compared("mean_gap", got["mean"],
                       limits["rehearsal_mean_gap" if rehearse
                              else "mean_gap"]),
        check.compared("unfinished", failed if open_loop else 0, 0),
        check.compared("compiles_in_window", compiles_in_window, 0),
        check.compared("tokens_unchecked",
                       max(0, (20 if rehearse else check.MIN_CHECKED)
                           - got["tokens"]), 0),
    ]
    out = {
        "checks": checks,
        "correct": check.passes(checks),
        "attempted": len(requests),
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "extra": dict(extra, generator_late_s=_lateness(
            rec.calls, requests, open_loop)),
        "counts": {"requests": len(requests), "checked_tokens": got["tokens"],
                   "tokens": sum(len(t) for t in all_tokens.values()),
                   "buckets": buckets},
        "run": {
            "kind": gen["kind"], "window": (t0, t_close),
            "window_s": seconds, "setup_s": setup_s,
            "batch_size": ecfg["batch_size"], "cfg": wcfg,
            "requests": requests, "all_tokens": all_tokens,
            "prompt_len": prompt_len, "calls": rec.calls, "spans": spans,
            "buckets": buckets, "trace": None,
        },
    }
    if prof is not None and prof.state == "done":
        out["run"]["trace"] = {"dir": prof.log_dir, "t0": prof.t0,
                               "t1": prof.t1}
    return out


def _lateness(calls, requests, open_loop) -> Optional[float]:
    """How late the generator ran: the largest delay between a request's
    scheduled arrival and the end of the harness call in progress then
    (the next moment it could be submitted)."""
    if not open_loop or not requests:
        return None
    ends = sorted(c[2] for c in calls)
    late = 0.0
    for q in requests:
        i = np.searchsorted(ends, q["arrival"])
        if i < len(ends):
            late = max(late, ends[i] - q["arrival"])
    return late
