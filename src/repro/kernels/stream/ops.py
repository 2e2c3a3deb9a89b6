"""Jit'd wrappers for the STREAM kernels; bytes-moved accounting included
(the benchmark derives GB/s exactly like the paper's `bandwidth` tool)."""
from repro.core.tracing import TraceStats, counting_jit
from repro.kernels.stream import stream as k

#: module-level compile accounting — bench_bandwidth reports these counts
stats = TraceStats()


def _copy(a, interpret=False):
    return k.stream_copy(a, interpret=interpret)


def _scale(a, x, interpret=False):
    return k.stream_scale(a, x, interpret=interpret)


def _add(a, b, interpret=False):
    return k.stream_add(a, b, interpret=interpret)


def _triad(a, b, x, interpret=False):
    return k.stream_triad(a, b, x, interpret=interpret)


copy = counting_jit(_copy, "stream/copy", stats,
                    static_argnames=("interpret",))
scale = counting_jit(_scale, "stream/scale", stats,
                     static_argnames=("interpret",))
add = counting_jit(_add, "stream/add", stats,
                   static_argnames=("interpret",))
triad = counting_jit(_triad, "stream/triad", stats,
                     static_argnames=("interpret",))


def bytes_moved(op: str, a) -> int:
    n = a.size * a.dtype.itemsize
    if op == "read":
        # plus one (8, cols) slab of partial sums written per tile of
        # stream_read's default 256 rows, read back once by the final sum
        rows, cols = a.shape
        return n + 2 * (rows // min(256, rows)) * 8 * cols * a.dtype.itemsize
    return {"write": n, "copy": 2 * n, "scale": 2 * n,
            "add": 3 * n, "triad": 3 * n}[op]
