"""Helpers that the metric readers share: what lies in the window, decode
steps and their work, percentiles. A reader takes the run record that a
driver returns and gives a number, or None where it finds nothing."""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import families
import peaks


def pct(values, q: float) -> Optional[float]:
    """The ``q``-th percentile by nearest rank (a value that occurred)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q,
                               method="inverted_cdf"))


def end_of_run(run: dict) -> float:
    """The last moment the harness recorded: a request that never emitted
    by then is charged latency up to it."""
    return max([c[2] for c in run["calls"]] + [run["window"][1]])


def in_window(run: dict, t: float) -> bool:
    return run["window"][0] <= t <= run["window"][1]


def decode_contexts(run: dict) -> Dict[int, List[int]]:
    """{decode step: context length of each sequence it advanced}. Output
    token j (j >= 1) of a request was produced by a decode step attending
    over its prompt and j tokens."""
    steps: Dict[int, List[int]] = {}
    for rid, toks in run["all_tokens"].items():
        p = run["prompt_len"][rid]
        for j, (_, step) in enumerate(toks):
            if step >= 0:
                steps.setdefault(step, []).append(p + j)
    return steps


def decode_calls(run: dict) -> Dict[int, tuple]:
    return {c[3]: (c[1], c[2]) for c in run["calls"] if c[0] == "decode"}


def window_decode_steps(run: dict) -> List[int]:
    return [s for s, (a, b) in decode_calls(run).items()
            if in_window(run, a) and in_window(run, b)]


def step_work(run: dict, step_contexts: List[int]) -> tuple:
    """(FLOPs, bytes) of one decode step, by the family's work counts."""
    return families.of(run["cfg"]).decode_step(run["cfg"], step_contexts)


def decode_roofline(run: dict) -> Optional[float]:
    """Least time of a decode step over its device time: the mean least
    time of the decode steps the host ran inside the traced stretch, over
    the mean device time of the decode programs in it."""
    tr = run.get("trace")
    if not tr or "reduced" not in tr:
        return None
    ctx = decode_contexts(run)
    host = [s for s, (a, b) in decode_calls(run).items()
            if tr["t0"] <= a and b <= tr["t1"] and s in ctx]
    devs = [d["steps"].get("paged_decode_step") for d in
            tr["reduced"]["devices"].values()]
    devs = [d for d in devs if d and d["count"]]
    if not host or not devs:
        return None
    least = [peaks.least_time_s(*step_work(run, ctx[s]), run["device_kind"])
             for s in host]
    dev_mean = sum(d["device_s"] / d["count"] for d in devs) / len(devs)
    return 100.0 * (sum(least) / len(least)) / dev_mean


def idle_share(run: dict) -> Optional[float]:
    tr = run.get("trace")
    if not tr or "reduced" not in tr:
        return None
    red = tr["reduced"]
    if not red["n_devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def spans_in_window(run: dict, name: str) -> list:
    return [s for s in run["spans"] if s[0] == name
            and in_window(run, s[1]) and in_window(run, s[2])]


def prefill_flops(run: dict, span) -> float:
    a = span[3]
    return families.of(run["cfg"]).prefill(
        run["cfg"], int(a.get("cached_tokens", 0)),
        int(a.get("computed_tokens", 0)))[0]
