"""Share of the window spent in prefills: the engine's per-request
``prefill`` spans, clipped to the window, over its length (percent)."""


def read(run):
    a, b = run["window"]
    t = sum(max(0.0, min(s[2], b) - max(s[1], a)) for s in run["spans"]
            if s[0] == "prefill")
    return 100.0 * t / run["window_s"]
