"""95th percentile of every gap between consecutive output tokens of every
request due in the window, prefill stalls included, in milliseconds."""
import runlib


def read(run):
    gaps = []
    for q in run["requests"]:
        t = [x[0] for x in q["tokens"]]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    v = runlib.pct(gaps, 95)
    return None if v is None else 1e3 * v
