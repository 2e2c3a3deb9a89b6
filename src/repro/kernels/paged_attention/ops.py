"""Jit'd wrapper for the paged decode attention kernel."""
from repro.core.tracing import TraceStats, counting_jit
from repro.kernels.paged_attention.paged_attention import paged_attention

#: module-level compile accounting for the jitted entry point
stats = TraceStats()


def _attention(q, k_pool, v_pool, layer, tables, lengths,
               blocks_per_chunk=8, interpret=False):
    return paged_attention(q, k_pool, v_pool, layer, tables, lengths,
                           blocks_per_chunk=blocks_per_chunk,
                           interpret=interpret)


attention = counting_jit(_attention, "paged/attention", stats,
                         static_argnames=("blocks_per_chunk", "interpret"))
