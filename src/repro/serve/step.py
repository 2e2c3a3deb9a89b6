"""Serving steps: prefill (builds KV caches / recurrent state) and decode
(one new token against a cache of ``seq_len``). Cache sharding comes from the
model's ``cache_axes()`` logical axes; for batch=1 long-context decode the
``kv_seq`` rule is overridden to sequence-shard the cache (context/SP).

``make_decode_step`` fuses sampling into the jitted step so the host loop
syncs once per step for the whole batch (one [B,1] token fetch) instead of
once per slot; ``pos`` may be a [B] vector for continuous batching.
``make_slot_prefill`` prefills a single request into one batch row of the
shared cache while the other rows keep their in-flight state. Sampling runs
under the ``sample`` named scope (see ``models.common`` for the others).

Prompt-length bucketing: an exact-length prefill retraces one executable
per distinct prompt length, so production-shaped traffic (every prompt a
different length) turns the engine into a compile loop. ``prefill_buckets``
computes power-of-two bucket edges, ``bucket_for``/``pad_to_bucket``
right-pad a prompt to its bucket edge, and the bucketed step variants take
the *true* length as a traced scalar: logits are gathered at the true last
token and only the real ``[0, len)`` cache positions survive the scatter
(``mask_cache_tail``), so stale pad KV never leaks into later decode.
Compile activity itself is first-class: every engine step goes through
``counting_jit``, whose ``TraceStats`` counts one compile per distinct
abstract input signature — the metric the CI cross-run gate regresses on.
(Signature accounting is wrapper-level and deterministic; ``jax.monitoring``
events would need process-global listeners and are backend-dependent.)
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.tracing import TraceStats, counting_jit
from repro.models.common import (copy_cache_block, gather_cache_slot,
                                 mask_cache_tail, paged_gather,
                                 paged_scatter_block, paged_scatter_slot,
                                 reset_cache_blocks, scatter_cache_slot)
from repro.parallel.sharding import spec_for

# compile accounting (``TraceStats``/``counting_jit``) lives in
# ``repro.core.tracing`` — training and launch meter compiles too — and is
# re-exported here for the serving call sites and existing imports.


# ---------------------------------------------------------------------------
# prompt-length bucketing


def prefill_buckets(max_len: int, min_bucket: int = 8) -> Tuple[int, ...]:
    """Power-of-two bucket edges covering prompt lengths in [1, max_len].

    Edges double from ``min_bucket`` and the last edge is clamped to
    ``max_len`` (a prompt can never exceed the cache), so the number of
    distinct prefill shapes — and therefore compiled executables — is
    O(log2(max_len / min_bucket)) regardless of traffic.
    """
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    edges: List[int] = []
    b = min(min_bucket, max_len)
    while b < max_len:
        edges.append(b)
        b *= 2
    edges.append(min(b, max_len))
    return tuple(edges)


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    """Smallest bucket edge >= length (exact length past the last edge)."""
    for edge in buckets:
        if length <= edge:
            return edge
    return length


def pad_to_bucket(prompt: np.ndarray, buckets: Sequence[int],
                  pad_id: int = 0) -> Tuple[np.ndarray, int]:
    """Right-pad a [S] prompt to its bucket edge; returns (padded, true_len).

    Right-padding (not left) keeps every real token at its true position:
    under causal masking the pad tail cannot influence real positions, so
    bucketed logits at ``true_len - 1`` match the exact-length prefill.
    """
    prompt = np.asarray(prompt, np.int32)
    n = len(prompt)
    edge = bucket_for(n, buckets)
    if edge == n:
        return prompt, n
    padded = np.full(edge, pad_id, np.int32)
    padded[:n] = prompt
    return padded, n


# ---------------------------------------------------------------------------
# step builders


@jax.named_scope("sample")
def _sample(logits, greedy=True, key=None):
    """The next token from ``logits``: argmax, or a draw with ``key``."""
    if greedy or key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def make_prefill_step(model, bucketed: bool = False):
    """Whole-batch prefill. ``bucketed=True`` adds a traced ``true_len``
    argument: the batch is right-padded to a bucket edge, logits come from
    the true last token, and cache positions >= true_len are zeroed so pad
    KV never reaches decode."""
    if not bucketed:
        def prefill_step(params, batch, caches):
            logits, caches = model.prefill(params, batch, caches)
            return logits, caches
        return prefill_step

    def bucketed_prefill_step(params, batch, true_len, caches):
        logits, caches = model.prefill(params, batch, caches,
                                       true_len=true_len)
        return logits, mask_cache_tail(caches, true_len)
    return bucketed_prefill_step


def make_decode_step(model, greedy=True):
    """Fused decode + in-jit sampling. ``pos``: scalar or [B] int32."""
    def decode_step(params, tokens, pos, caches, key=None):
        logits, caches = model.decode_step(params, tokens, pos, caches)
        return _sample(logits, greedy, key), logits, caches
    return decode_step


def make_slot_prefill(model, bucketed: bool = False):
    """Prefill one request ([1, S] tokens) into batch row ``slot`` of a
    shared cache pytree; every other row is untouched.

    Exact mode retraces per distinct prompt length (jit caches one
    executable per S). Bucketed mode takes right-padded tokens plus the
    traced true length: executables are bounded by the bucket count, the
    next token comes from the logits at ``true_len - 1``, and only the real
    ``[0, true_len)`` cache positions are scattered back."""
    if not bucketed:
        def slot_prefill(params, tokens, slot, caches):
            sub = gather_cache_slot(caches, slot)
            logits, sub = model.prefill(params, {"tokens": tokens}, sub)
            next_tok = _sample(logits)
            return next_tok, logits, scatter_cache_slot(caches, sub, slot)
        return slot_prefill

    def bucketed_slot_prefill(params, tokens, true_len, slot, caches):
        sub = gather_cache_slot(caches, slot)
        logits, sub = model.prefill(params, {"tokens": tokens}, sub,
                                    true_len=true_len)
        sub = mask_cache_tail(sub, true_len)
        next_tok = _sample(logits)
        return next_tok, logits, scatter_cache_slot(caches, sub, slot)
    return bucketed_slot_prefill


def make_paged_decode_step(model, greedy=True):
    """Fused decode through block-table indirection.

    The pool ([L, P, block, kvh, dh] leaves) is gathered into per-slot
    contiguous views via ``tables`` ([B, NB] block ids), the unmodified
    model decode runs on the view, and only each slot's touched block is
    scattered back. Table *values* are traced, so remaps (prefix sharing,
    COW, lazy growth) never retrace — the decode executable count stays 1.
    """
    def paged_decode_step(params, tokens, pos, tables, pool, key=None):
        view = paged_gather(pool, tables)
        logits, view = model.decode_step(params, tokens, pos, view)
        next_tok = _sample(logits, greedy, key)
        pool = paged_scatter_block(pool, view, tables, pos)
        return next_tok, logits, pool
    return paged_decode_step


def make_paged_slot_prefill(model, bucketed: bool = False):
    """Prefill one request's *uncached tail* through its block table.

    ``start_pos`` (traced) is the first uncached position: the matched
    prefix blocks already mapped into ``table_row`` supply KV for
    [0, start_pos) with zero compute, the chunk attends causally over
    prefix + itself, and logits come from the chunk's (true) last token.
    Bucketed mode right-pads the tail to its bucket edge; everything at or
    past ``start_pos + true_len`` is zeroed before the scatter so pad KV
    and stale block contents never reach decode. Executables stay bounded
    by the bucket count — the same compile budget as unpaged prefill.
    """
    if not bucketed:
        def paged_slot_prefill(params, tokens, start_pos, table_row, pool):
            sub = paged_gather(pool, table_row[None, :])
            logits, sub = model.prefill(params, {"tokens": tokens}, sub,
                                        start_pos=start_pos)
            sub = mask_cache_tail(sub, start_pos + tokens.shape[1])
            next_tok = _sample(logits)
            return next_tok, logits, paged_scatter_slot(pool, sub, table_row)
        return paged_slot_prefill

    def paged_bucketed_slot_prefill(params, tokens, true_len, start_pos,
                                    table_row, pool):
        sub = paged_gather(pool, table_row[None, :])
        logits, sub = model.prefill(params, {"tokens": tokens}, sub,
                                    true_len=true_len, start_pos=start_pos)
        sub = mask_cache_tail(sub, start_pos + true_len)
        next_tok = _sample(logits)
        return next_tok, logits, paged_scatter_slot(pool, sub, table_row)
    return paged_bucketed_slot_prefill


def pow2_chunks(n: int) -> List[int]:
    """Decompose a prompt length into power-of-two chunk sizes, largest
    first (its binary representation).

    Chunked left-to-right prefill for the recurrent families feeds these
    through ``model.prefill`` carrying state between chunks: positions stay
    monotone, every chunk size is a power of two (the chunkwise SSM kernels
    require ``t % min(chunk, t) == 0``), and the number of distinct chunk
    shapes over any traffic is <= log2(max_seq) — so the compile count
    stays bounded without ever right-padding carried state.
    """
    if n < 1:
        raise ValueError(f"prompt length must be >= 1, got {n}")
    return [1 << b for b in range(n.bit_length() - 1, -1, -1) if n & (1 << b)]


def make_recurrent_chunk_prefill(model):
    """One chunk of a left-to-right recurrent prefill.

    ``state`` is the batch-1 carried state tree (fresh on the first chunk);
    ``start_pos`` (traced) is the chunk's absolute offset — position-free
    families ignore it, attention-bearing recurrent families (zamba2 shared
    attention, whisper decoder self-attention) offset their KV writes and
    masks with it. ``frames`` is None except on an audio request's first
    chunk, where it feeds the encoder and fills the cross cache that later
    chunks (and decode) reuse; the None/array pytree difference gives the
    frames variant its own executable, counted like any other.

    Returns ``(next_token, logits, state)`` with the next token sampled
    from the chunk's last position — after the final chunk that is the
    request's first generated token.
    """
    def chunk_prefill(params, tokens, frames, start_pos, state):
        batch = {"tokens": tokens}
        if frames is not None:
            batch["frames"] = frames
        logits, state = model.prefill(params, batch, state,
                                      start_pos=start_pos)
        next_tok = _sample(logits)
        return next_tok, logits, state
    return chunk_prefill


def make_block_ops(stats: Optional[TraceStats] = None, on_compile=None):
    """Jitted pool maintenance ops: (zero_blocks, copy_block).

    ``zero_blocks(pool, blocks)`` scrubs freed blocks (fixed-width padded
    id vector -> one executable); ``copy_block(pool, src, dst)`` is the
    copy-on-write arm (traced scalars -> one executable). Both run under
    ``counting_jit`` so the engine's ``TraceStats`` — and the CI compile
    gate — see the pool-maintenance executables, not just prefill/decode."""
    return (counting_jit(reset_cache_blocks, "zero_blocks", stats,
                         on_compile=on_compile),
            counting_jit(copy_cache_block, "copy_block", stats,
                         on_compile=on_compile))


def serve_rules(shape):
    """Sharding-rule overrides per shape cell.

    batch=1 (long_500k): nothing to shard on batch -> sequence-shard KV
    caches over ("pod","data") and keep TP on heads.
    """
    if shape.global_batch == 1:
        return {"batch": None, "kv_seq": ("pod", "data")}
    return {}


def cache_specs(mesh, model, cache_sds, rules=None):
    axes = model.cache_axes()
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    return jax.tree.map(
        lambda a, c: spec_for(mesh, a, c.shape, rules),
        axes, cache_sds, is_leaf=is_axes)


def abstract_cache(model, batch_size, max_seq, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model.init_cache(batch_size, max_seq, dtype))
