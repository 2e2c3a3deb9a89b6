"""Share of the window in which the host waited on the device for nothing:
the window less the union of the engine's ``device_wait`` (decode token
fetch) and ``prefill_wait`` (first-token fetch) spans, clipped to it, over
the window, in percent. The program's own account of the device's idle
share; device work that no fetch waits for (pool scrubs, block copies)
counts as a gap here."""
import phases


def read(run):
    waited = phases.window_share(run, ("device_wait", "prefill_wait"))
    return None if waited is None else 100.0 - waited
