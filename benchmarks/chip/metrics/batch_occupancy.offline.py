"""Mean share of the batch's slots that the window's decode steps advanced
(the engine's ``decode_step`` span attribute ``active``), in percent."""
import runlib


def read(run):
    sp = runlib.spans_in_window(run, "decode_step")
    if not sp:
        return None
    return 100.0 * sum(s[3]["active"] for s in sp) / (len(sp)
                                                      * run["batch_size"])
