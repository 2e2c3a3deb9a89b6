"""Host time of a decode step, in milliseconds: the median, over the
window's ``decode_step`` spans, of the step's ``step_prepare`` plus its
``decode_step`` less the ``device_wait`` inside it plus its ``emit``,
joined by the ``step`` they carry. The rest of the step is the blocking
token fetch, when the host waits for the device."""
import runlib

PARTS = ("step_prepare", "device_wait", "emit")


def read(run):
    parts = {}
    for s in run["spans"]:
        if s[0] in PARTS and "step" in s[3]:
            per = parts.setdefault(s[3]["step"], dict.fromkeys(PARTS, None))
            per[s[0]] = (per[s[0]] or 0.0) + s[2] - s[1]
    host = []
    for s in runlib.spans_in_window(run, "decode_step"):
        per = parts.get(s[3].get("step"))
        if per is None or None in per.values():
            continue
        host.append(per["step_prepare"] + (s[2] - s[1])
                    - per["device_wait"] + per["emit"])
    v = runlib.pct(host, 50)
    return None if v is None else 1e3 * v
