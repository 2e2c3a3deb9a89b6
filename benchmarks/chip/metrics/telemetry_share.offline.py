"""Share of the window spent in the engine's modelled-power bookkeeping:
its ``telemetry`` spans, clipped to the window, over the window, in
percent."""
import phases


def read(run):
    return phases.window_share(run, ("telemetry",))
