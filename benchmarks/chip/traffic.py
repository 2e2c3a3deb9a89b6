"""One general generator for every traffic mix.

A mix is a data file, ``traffic/<mix>.json``, of parameters: arrivals,
prompt and output lengths, shared prefixes. This module turns one into a
list of requests from ``--seed``. No mix needs code of its own.

Every seed gets the same work in another order. Lengths are the quantiles
of their distribution at evenly spaced points (stratified), shuffled by
the seed; inter-arrival gaps likewise; the number of requests that use
each shared prefix is its popularity share of the whole, rounded. So runs
with different seeds differ in token contents and order only, and the
spread between seeds is the system's, not the generator's.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Item:
    """One request of a mix. ``arrival_s`` is its scheduled send time from
    the window's start (None: backlog traffic, sent when the queue has
    room)."""

    idx: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: Optional[float] = None


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, clipped to [min, max]; ascending."""
    ln = spec["lognormal"]
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(ln["median"]) + ln["sigma"] * z)
    return np.clip(np.rint(x), ln["min"], ln["max"]).astype(np.int64)


def stratified_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (mean ``1 / rate``) at evenly
    spaced quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def zipf_counts(n_prefixes: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests use each prefix under Zipf popularity
    p_k ~ k^-s, rounded by largest remainder so that they sum to ``n``."""
    p = np.arange(1, n_prefixes + 1, dtype=np.float64) ** -s
    want = p / p.sum() * n
    counts = np.floor(want).astype(np.int64)
    order = np.argsort(-(want - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


def generate(mix: dict, *, seed: int, seconds: float, vocab: int,
             batch_size: int, scale: float = 1.0) -> dict:
    """The requests of one run.

    Returns ``{"items": [Item], "more": callable, "prefixes": [np.ndarray],
    "kind": ...}``. ``kind`` is ``"poisson"`` (open loop: ``seconds * rate``
    requests with scheduled arrivals in [0, seconds)) or ``"backlog"`` (a
    stream that the driver keeps ``per_slot * batch_size`` deep in the
    queue; ``more()`` returns its next block of requests). ``scale`` shrinks
    every token length (a CPU rehearsal with a small engine); it is 1 in every
    measured run.
    """
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    prefixes = []
    pre = mix.get("prefixes")
    if pre:
        plen = max(1, int(round(pre["tokens"] * scale)))
        prefixes = [rng.integers(0, vocab, plen).astype(np.int32)
                    for _ in range(pre["count"])]
    # one block of (prompt, output) pairs, paired the same way for every
    # seed; the seed only shuffles whole pairs
    if arr["kind"] == "poisson":
        m = max(1, int(round(arr["rate_per_s"] * seconds)))
    elif arr["kind"] == "backlog":
        m = arr["per_slot"] * batch_size
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    user_b = stratified_lengths(_scaled(mix["prompt"], scale), m)
    out_b = np.random.default_rng(0).permutation(
        stratified_lengths(_scaled(mix["output"], scale), m))
    pid_b = np.full(m, -1)
    if prefixes:
        counts = zipf_counts(len(prefixes), pre["popularity"]["zipf_s"], m)
        pid_b = np.random.default_rng(1).permutation(
            np.repeat(np.arange(len(prefixes)), counts))
    max_total = mix.get("max_total")
    max_total = (None if max_total is None
                 else max(2, int(round(max_total * scale))))
    made = []

    def block(times=None):
        order = rng.permutation(m)
        items = []
        for k in order:
            body = rng.integers(0, vocab, int(user_b[k])).astype(np.int32)
            p = int(pid_b[k])
            prompt = np.concatenate([prefixes[p], body]) if p >= 0 else body
            out = int(out_b[k])
            if max_total is not None:
                out = min(out, max_total - len(prompt))
            i = len(made) + len(items)
            items.append(Item(i, prompt, out,
                              None if times is None else float(times[i])))
        made.extend(items)
        return items

    if arr["kind"] == "poisson":
        gaps = rng.permutation(stratified_gaps(arr["rate_per_s"], m))
        # scale the m gaps onto [0, seconds): every seed sends m requests
        t = np.cumsum(gaps) - gaps[0]
        t = t * (seconds * m / (m + 1)) / max(t[-1], 1e-9) if m > 1 else t
        items = block(t)
    else:
        items = block()
    return {"items": items, "more": block, "prefixes": prefixes,
            "kind": arr["kind"]}


def _scaled(spec: dict, scale: float) -> dict:
    if scale == 1.0:
        return spec
    ln = dict(spec["lognormal"])
    for k in ("median", "min", "max"):
        ln[k] = max(1, int(round(ln[k] * scale)))
    return {"lognormal": ln}


def max_prompt_tokens(mix: dict, scale: float = 1.0) -> int:
    """The longest prompt the mix can send."""
    pre = mix.get("prefixes")
    p = int(round(pre["tokens"] * scale)) if pre else 0
    return p + _scaled(mix["prompt"], scale)["lognormal"]["max"]


def max_total_tokens(mix: dict, scale: float = 1.0) -> int:
    """The longest prompt plus output the mix can produce."""
    tot = max_prompt_tokens(mix, scale) + _scaled(
        mix["output"], scale)["lognormal"]["max"]
    if mix.get("max_total") is not None:
        tot = min(tot, int(round(mix["max_total"] * scale)))
    return tot


def max_output_tokens(mix: dict, scale: float = 1.0) -> int:
    """The most tokens one request of the mix can be served."""
    out = _scaled(mix["output"], scale)["lognormal"]["max"]
    if mix.get("max_total") is not None:
        out = min(out, int(round(mix["max_total"] * scale)))
    return out
