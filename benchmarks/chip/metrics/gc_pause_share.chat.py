"""Share of the window in which the engine's thread was paused for garbage
collection: its ``gc`` spans, clipped to the window, over the window, in
percent."""
import phases


def read(run):
    return phases.window_share(run, ("gc",))
