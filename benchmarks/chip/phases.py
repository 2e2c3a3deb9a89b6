"""What the readers of the engine's phase spans share. The engine records,
on its own clock, each decode step's ``step_prepare``, ``decode_step``
(with ``device_wait`` and ``telemetry`` inside it) and ``emit``, all
carrying the step's ``step``; each prefill's ``prefill_wait``; each
admission; and each garbage-collection pause (``gc``). A program that
records none of them gives None, so its line leaves these metrics out."""
from __future__ import annotations

from typing import Iterable, Optional

import trace_reduce


def recorded(run: dict) -> bool:
    """Does the program record phase spans? (``device_wait`` is in every
    decode step.)"""
    return any(s[0] == "device_wait" for s in run["spans"])


def window_share(run: dict, names: Iterable[str]) -> Optional[float]:
    """The union of the named spans, clipped to the window, over the
    window, in percent; None where the program records no phase spans."""
    if not recorded(run):
        return None
    names = set(names)
    covered = trace_reduce.union(trace_reduce.clip(
        [(s[1], s[2]) for s in run["spans"] if s[0] in names],
        run["window"]))
    return 100.0 * trace_reduce.length(covered) / run["window_s"]
