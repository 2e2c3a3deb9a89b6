"""Whole step's share of the chip's peak over the window: the FLOPs that
the window's prefills (tokens computed, cache hits excluded) and decode
steps need, over the window times the peak, in percent."""
import peaks
import runlib


def read(run):
    ctx = runlib.decode_contexts(run)
    flops = sum(runlib.step_work(run, ctx[s])[0]
                for s in runlib.window_decode_steps(run) if s in ctx)
    flops += sum(runlib.prefill_flops(run, s)
                 for s in runlib.spans_in_window(run, "prefill"))
    if not flops:
        return None
    pk = peaks.peaks_for(run["device_kind"])["flops_bf16"]
    return 100.0 * flops / (run["window_s"] * pk)
