"""90th percentile of queue wait: from a request's scheduled arrival to the
end of the engine's ``queued`` span (its admission), over the window's
requests."""
import runlib


def read(run):
    arrival = {q["id"]: q["arrival"] for q in run["requests"]}
    waits = [s[2] - arrival[s[3]["req_id"]] for s in run["spans"]
             if s[0] == "queued" and s[3].get("req_id") in arrival]
    return runlib.pct(waits, 90)
