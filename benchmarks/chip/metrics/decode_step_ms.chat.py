"""Median wall time of the engine's ``decode_step`` spans in the window,
in milliseconds."""
import runlib


def read(run):
    d = [s[2] - s[1] for s in runlib.spans_in_window(run, "decode_step")]
    v = runlib.pct(d, 50)
    return None if v is None else 1e3 * v
