"""90th percentile, over every request due in the window, of the time from
its scheduled arrival to its first token on the host. A request that never
emitted is charged up to the end of the run."""
import runlib


def read(run):
    if run["kind"] == "backlog":
        return None
    end = runlib.end_of_run(run)
    lat = [(q["tokens"][0][0] if q["tokens"] else end) - q["arrival"]
           for q in run["requests"]]
    return runlib.pct(lat, 90)
