"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The harness wraps the traced stretch in a host ``TraceAnnotation`` named
``WINDOW`` and each call it makes into the program in one named after the
call (``admit``, ``decode``, ``wait``, ...). From the trace this module
takes, per device:

- the busy union: the union of the intervals in which an operation ran,
  clipped to the window, and the idle share ``1 - busy / window``;
- the device time of each jitted step, by its function's name, from the
  device's module events;
- the time in collective operations during which no other operation ran
  on that device;
- the idle gaps, each tagged with the harness annotation that overlaps it
  most (what the host was doing meanwhile).

``load`` reads a file into plain tuples; everything else is arithmetic on
those tuples, which ``selftest`` checks without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench_window"
HOST_TAGS = ("admit", "decode", "wait")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|allreduce|allgather",
                        re.IGNORECASE)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]              # seconds, start < end
Event = Tuple[str, float, float]            # (name, start_s, end_s)


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> dict:
    """``{"devices": {id: {"ops": [Event], "modules": [Event]}},
    "host": [Event]}`` from one trace file. Device events come from the
    TPU planes' "XLA Ops" and "XLA Modules" lines; host events are the
    harness's annotations on any host thread."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[0] == WINDOW or e[0] in HOST_TAGS)
    return {"devices": devices, "host": host}


def _events(line) -> List[Event]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


# -- interval arithmetic -----------------------------------------------------


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals
            if min(b, w[1]) > max(a, w[0])]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_set: Sequence[Interval],
             b_set: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) ``a_set`` not covered by ``b_set``."""
    b_u = union(b_set)
    out: List[Interval] = []
    for a, b in a_set:
        cur = a
        for c, d in b_u:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy: Sequence[Interval], w: Interval) -> List[Interval]:
    return subtract([w], busy)


# -- reductions --------------------------------------------------------------


def window_of(trace: dict) -> Interval:
    ws = [(a, b) for n, a, b in trace["host"] if n == WINDOW]
    if not ws:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return ws[0]


def reduce(trace: dict, step_names: Sequence[str] = ()) -> dict:
    """Per-device busy time, step device times, unoverlapped collective
    time and idle gaps within the harness's window, plus the means over
    devices. ``step_names`` are substrings of jitted functions' names."""
    w = window_of(trace)
    win = w[1] - w[0]
    per_dev = {}
    for dev, ev in sorted(trace["devices"].items()):
        ops = [(a, b) for _, a, b in ev["ops"]] or \
              [(a, b) for _, a, b in ev["modules"]]
        busy = union(clip(ops, w))
        coll = union(clip([(a, b) for n, a, b in ev["ops"]
                           if COLLECTIVE.search(n)], w))
        comp = clip([(a, b) for n, a, b in ev["ops"]
                     if not COLLECTIVE.search(n)], w)
        steps = {}
        for s in step_names:
            mods = [(a, b) for n, a, b in ev["modules"] if s in n
                    and a >= w[0] and b <= w[1]]
            steps[s] = {"count": len(mods), "device_s": length(mods)}
        per_dev[dev] = {
            "busy_s": length(busy),
            "collective_exposed_s": length(subtract(coll, comp)),
            "steps": steps,
            "gaps": gaps(busy, w),
            "op_time": _op_time(ev["ops"], w),
        }
    n = max(len(per_dev), 1)
    return {
        "window_s": win,
        "devices": per_dev,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"]
                                    for d in per_dev.values()) / n,
        "n_devices": len(per_dev),
    }


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[32,4096]{...} fusion(...)`` -> ``fusion.12
    bf16[32,4096]``: the instruction and its result type, without layouts
    or operands."""
    head, _, rest = op.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    ty = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head} {ty}"[:120]


def _op_time(ops: Sequence[Event], w: Interval) -> Dict[str, float]:
    """Self time of each operation within the window: an operation's
    interval less the operations nested in it (a ``while`` holds its
    body's operations on the same trace line)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self_s]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2]

    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] < b:     # not nested in the top
            close(stack.pop())
        span = clip([(a, b)], w)
        dur = span[0][1] - span[0][0] if span else 0.0
        if stack:
            stack[-1][2] -= dur
        stack.append([short_name(name), b, dur])
    while stack:
        close(stack.pop())
    return out


def breakdown(trace: dict, red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device operations that
    took most time (summed over devices, averaged), and the longest idle
    gaps on device 0 named by the host annotation that overlaps each most."""
    ops: Dict[str, float] = {}
    n = max(red["n_devices"], 1)
    for d in red["devices"].values():
        for name, s in d["op_time"].items():
            ops[name] = ops.get(name, 0.0) + s / n
    dev_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    first = red["devices"][min(red["devices"])] if red["devices"] else None
    tagged = []
    host = [(nm, a, b) for nm, a, b in trace["host"] if nm != WINDOW]
    for a, b in (first["gaps"] if first else []):
        best, over = "none", 0.0
        for nm, c, d in host:
            o = min(b, d) - max(a, c)
            if o > over:
                best, over = nm, o
        tagged.append([best, b - a])
    tagged.sort(key=lambda t: -t[1])
    return {"device_ops": [[k, v] for k, v in dev_ops],
            "idle_gaps": tagged[:top]}


def selftest() -> None:
    """Check the arithmetic on a hand-made trace; raises AssertionError."""
    t = {"host": [(WINDOW, 0.0, 10.0), ("decode", 0.0, 4.0),
                  ("admit", 4.0, 6.0), ("wait", 6.0, 10.0)],
         "devices": {0: {
             "ops": [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 3.5),
                     ("all-reduce.1", 3.0, 5.0), ("fusion.3", 8.0, 12.0),
                     ("fusion.0", -2.0, 0.5)],
             "modules": [("jit_paged_decode_step(1)", 1.0, 3.5),
                         ("jit_paged_decode_step(2)", 8.0, 12.0),
                         ("jit_other", 3.0, 5.0)]}}}
    r = reduce(t, ["paged_decode_step"])
    d = r["devices"][0]
    # busy: [0,0.5] + [1,5] + [8,10] = 0.5 + 4 + 2
    assert abs(d["busy_s"] - 6.5) < 1e-12, d["busy_s"]
    # the all-reduce [3,5] overlaps compute [2,3.5]: 1.5 s exposed
    assert abs(d["collective_exposed_s"] - 1.5) < 1e-12, d
    # only the module wholly inside the window counts
    st = d["steps"]["paged_decode_step"]
    assert st == {"count": 1, "device_s": 2.5}, st
    assert [tuple(g) for g in d["gaps"]] == [(0.5, 1.0), (5.0, 8.0)], d
    b = breakdown(t, r)
    assert b["idle_gaps"][0] == ["wait", 3.0], b
    assert b["idle_gaps"][1] == ["decode", 0.5], b
    # fusion.2 [2,3.5] overlaps fusion.1 [1,3] without nesting: each
    # keeps its own time
    assert dict(b["device_ops"]) == {"fusion.1": 2.0, "all-reduce.1": 2.0,
                                     "fusion.3": 2.0, "fusion.2": 1.5,
                                     "fusion.0": 0.5}, b
    nested = [("%while.1 = (s32[]) while(...)", 0.0, 4.0),
              ("%fusion.7 = bf16[32,4096]{1,0:T(8,128)} fusion(x)", 1.0, 2.0),
              ("%fusion.8 = f32[8]{0} fusion(y)", 2.5, 3.0)]
    assert _op_time(nested, (0.0, 10.0)) == {
        "while.1 (s32[])": 2.5, "fusion.7 bf16[32,4096]": 1.0,
        "fusion.8 f32[8]": 0.5}, _op_time(nested, (0.0, 10.0))
    assert subtract([(0, 10)], [(2, 3), (2.5, 4), (9, 12)]) == [
        (0, 2), (4, 9)]
