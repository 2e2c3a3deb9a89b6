"""Decode's share of the chip's peak: the FLOPs that the window's decode
steps need, over their wall time (the engine's ``decode_step`` spans) times
the peak, in percent."""
import peaks
import runlib


def read(run):
    ctx = runlib.decode_contexts(run)
    spans = runlib.spans_in_window(run, "decode_step")
    steps = [s for s in runlib.window_decode_steps(run) if s in ctx]
    if not spans or not steps:
        return None
    flops = sum(runlib.step_work(run, ctx[s])[0] for s in steps)
    wall = sum(s[2] - s[1] for s in spans)
    pk = peaks.peaks_for(run["device_kind"])["flops_bf16"]
    return 100.0 * flops / (wall * pk)
