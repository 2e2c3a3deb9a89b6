"""Output tokens emitted inside the window over the window's seconds."""
import runlib


def read(run):
    n = sum(1 for toks in run["all_tokens"].values() for t, _ in toks
            if runlib.in_window(run, t))
    return n / run["window_s"] if n else None
