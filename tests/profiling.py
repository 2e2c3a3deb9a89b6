"""Test helper: the host side of a CPU ``jax.profiler`` trace."""
import glob

import jax
from jax.profiler import ProfileData


def profiled_host_events(fn, log_dir):
    """Run ``fn()`` under a ``jax.profiler`` trace written to ``log_dir``;
    returns the host planes' events as ``(name, start_s, end_s, stats)``."""
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            {k: v for k, v in e.stats})
                           for e in line.events)
    return out
