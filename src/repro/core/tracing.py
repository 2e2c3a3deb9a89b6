"""Compile accounting: ``TraceStats`` + ``counting_jit``.

Bounded compile counts are a serving invariant (PR 4): every jitted
executable the repo runs must be visible to a ``TraceStats`` so the CI
cross-run gate can fail any change that reintroduces a retrace. This
module is the single place ``jax.jit`` is allowed to appear — everything
else goes through :func:`counting_jit`, and the ``repro.analysis`` static
analyzer (rule DLK001 *bare-jit*) enforces exactly that.

Lives in ``repro.core`` (not ``repro.serve``) because the training and
launch layers meter their compiles too; ``repro.serve.step`` re-exports
both names for compatibility.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax


class TraceStats:
    """Per-step-family jit trace/compile counters.

    One counter per step name ("prefill", "decode", ...): ``counting_jit``
    bumps it whenever a call presents an abstract input signature (pytree
    structure + leaf shapes/dtypes + static values) the wrapper has not seen
    before — exactly the condition under which ``jax.jit`` traces and XLA
    compiles a new executable. Bounded compile counts are a serving
    invariant: with length bucketing, ``compiles("prefill")`` can never
    exceed the bucket count no matter the traffic shape, and the CI
    regression gate fails any PR that reintroduces a retrace.
    """

    def __init__(self):
        self.compile_counts: Dict[str, int] = {}
        self.call_counts: Dict[str, int] = {}

    def record(self, name: str, new_trace: bool):
        self.call_counts[name] = self.call_counts.get(name, 0) + 1
        if new_trace:
            self.compile_counts[name] = self.compile_counts.get(name, 0) + 1

    def compiles(self, name: Optional[str] = None) -> int:
        if name is not None:
            return self.compile_counts.get(name, 0)
        return sum(self.compile_counts.values())

    def calls(self, name: str) -> int:
        return self.call_counts.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.compile_counts)


def counting_jit(fn, name: str, stats: Optional[TraceStats] = None,
                 on_compile=None, **jit_kwargs):
    """``jax.jit(fn)`` wrapped with trace accounting.

    A call that grows the jit executable cache counts as one compile on
    ``stats`` (and fires ``on_compile(name)`` — the hook engines use to
    surface compile activity through telemetry counters). The detector is
    the executable-cache size around the call (exact and O(1)). The
    wrapped jitted function is exposed as ``wrapper.jitted``; AOT users
    call ``wrapper.lower(...)`` — a lower is a trace, so it records one
    compile on ``stats`` (the dryrun driver's explicit-compile path).
    """
    jitted = jax.jit(fn, **jit_kwargs)  # dalek: allow[bare-jit] counting_jit IS the tracked wrapper

    def wrapper(*args, **kwargs):
        before = jitted._cache_size()
        out = jitted(*args, **kwargs)
        new = jitted._cache_size() > before
        if stats is not None:
            stats.record(name, new)
        if new and on_compile is not None:
            on_compile(name)
        return out

    def lower(*args, **kwargs):
        if stats is not None:
            stats.record(name, True)
        if on_compile is not None:
            on_compile(name)
        return jitted.lower(*args, **kwargs)

    wrapper.jitted = jitted
    wrapper.lower = lower
    wrapper.step_name = name
    wrapper.stats = stats
    return wrapper
