"""Per-slot cache/state adapters: one continuous engine, every family.

``ContinuousEngine`` used to speak three dialects — contiguous per-slot KV
tensors, a paged block pool, and (for the recurrent families) nothing at
all: SSM/hybrid and whisper could not share the batcher because right-pad
bucketing and slot recycling would corrupt carried state. This module
factors all per-slot state handling behind one protocol:

``CacheAdapter``        the interface the engine speaks: per-slot
                        alloc/free/reset, chunked prefill into one slot,
                        fused whole-batch decode, admission queries, and
                        declared capability flags (``ServingCaps`` from the
                        model registry — no more ``inspect.signature``
                        sniffing on model methods).
``PagedKVAdapter``      flat (k, v) caches behind a refcounted ``PagePool``
                        + radix prefix trie (dense/MoE/VLM transformers).
``WindowRingAdapter``   contiguous per-slot rows — the gemma3 local:global
                        window *ring* backend, doubling as the contiguous
                        fallback when paging is explicitly off.
``RecurrentStateAdapter`` per-slot recurrent-state gather/scatter/reset and
                        chunked left-to-right prefill (xlstm/zamba2/
                        mamba2/whisper): a prompt is fed through the model
                        in power-of-two chunks carrying state between them
                        (no right-pad ever touches the state), and the
                        finished batch-1 state is scattered into the slot's
                        row of the shared batch tree. Recurrent leaves put
                        the batch on *different* axes per leaf (xlstm sLSTM
                        tuples are [B, ...] while its mLSTM leaves are
                        [L, B, ...]); the adapter infers a per-leaf axes
                        tree once from two ``jax.eval_shape`` calls and
                        uses the axis-aware tree ops in ``models.common``.

Every jitted step runs through ``counting_jit`` against the engine's shared
``TraceStats`` so compile counts stay bounded and regression-gated: paged
and contiguous prefill by the bucket count, recurrent chunked prefill by
the number of distinct power-of-two chunk sizes (<= log2(max_seq), plus the
with-frames variants for audio) — never per request.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import (reset_cache_slot, scatter_state_slot)
from repro.models.registry import ServingCaps, serving_caps
from repro.obs import Tracer, span_or_null
from repro.serve.paging import (PagePool, RadixPrefixCache,
                                resolve_kv_block_size)
from repro.serve.queue import Request
from repro.serve.step import (TraceStats, counting_jit, make_block_ops,
                              make_decode_step, make_paged_decode_step,
                              make_paged_slot_prefill,
                              make_recurrent_chunk_prefill, make_slot_prefill,
                              pad_to_bucket, pow2_chunks)
from repro.serve.step import prefill_buckets as auto_prefill_buckets

__all__ = ["PrefillOutcome", "CacheAdapter", "PagedKVAdapter",
           "WindowRingAdapter", "RecurrentStateAdapter", "make_adapter",
           "resolve_buckets"]


def resolve_buckets(spec, max_seq: int, model=None):
    """Normalize a ``prefill_buckets`` argument.

    ``"auto"``/True -> power-of-two edges up to ``max_seq``; ``None``/
    ``"off"``/False -> bucketing disabled (exact-length prefill, one
    executable per distinct length); an iterable -> explicit edges (sorted,
    deduped, capped at ``max_seq``). With a ``model``, ``"auto"`` silently
    degrades to off when the family declares ``bucketed_prefill=False``
    (``serving_caps``: right-pad would corrupt carried recurrent state);
    explicitly requested edges raise."""
    if spec in (None, False, "off", "none"):
        return None
    supported = model is None or serving_caps(model.cfg).bucketed_prefill
    if spec in (True, "auto"):
        return auto_prefill_buckets(max_seq) if supported else None
    if not supported:
        raise ValueError(
            f"family '{model.cfg.family}' declares bucketed_prefill=False: "
            "right-pad would corrupt carried recurrent state — its chunked "
            "prefill is already compile-bounded (pass prefill_buckets='off')")
    edges = sorted({min(int(b), max_seq) for b in spec if int(b) >= 1})
    if not edges:
        raise ValueError(f"no usable prefill buckets in {spec!r}")
    if edges[-1] < max_seq:
        edges.append(max_seq)     # every admissible prompt must fit a bucket
    return tuple(edges)


@dataclasses.dataclass
class PrefillOutcome:
    """What one slot prefill did. ``first_token is None`` means the backend
    could not back the prompt (paged pool dry): the adapter has already
    dropped its slot resources and the engine finishes the request with
    reason "pages"."""

    first_token: Optional[int]
    cached_tokens: int = 0     # prompt span served from the prefix cache
    computed_tokens: int = 0   # prompt tokens that actually ran


class CacheAdapter:
    """Base adapter: owns the model's per-slot serving state and every
    jitted step that touches it. The engine never inspects model methods or
    cache layouts — it calls this interface and trusts ``self.caps``.

    Lifecycle per slot: ``prefill(slot, req)`` claims the row (fresh state,
    prompt fed in), ``decode_step`` advances every row in one fused call,
    ``free_slot`` resets/releases the row the moment its request finishes —
    slot reuse without that reset is exactly what dalek-lint DLK008 flags.
    """

    kind: str = "base"

    def __init__(self, model, params, *, batch_size: int, max_seq: int,
                 buckets, caps: ServingCaps, trace_stats: TraceStats,
                 on_compile=None, greedy: bool = True,
                 tracer: Optional[Tracer] = None):
        self.model = model
        self.params = params
        self.batch_size = batch_size
        self.max_seq = max_seq
        self.buckets = buckets
        self.caps = caps
        self.trace_stats = trace_stats
        self.on_compile = on_compile
        self.greedy = greedy
        # the engine's tracer: each prefill's blocking first-token fetch is
        # a ``prefill_wait`` span on it
        self.tracer = tracer
        # what the last ``begin_step`` did to the pool (the engine's
        # ``step_prepare`` span carries these); zero without a pool
        self.step_counts = {"blocks_allocated": 0, "blocks_scrubbed": 0,
                            "cow": 0}
        self.caches = None
        # non-paged backends expose inert handles so engine property
        # aliases (`engine.pages` / `engine.prefix` / `engine.block_size`)
        # stay stable for benches and tests
        self.pages: Optional[PagePool] = None
        self.prefix: Optional[RadixPrefixCache] = None
        self.block_size: Optional[int] = None

    # -- lifecycle ----------------------------------------------------------

    def ensure_ready(self):
        """Lazy state allocation (first ``run``)."""
        raise NotImplementedError

    def prefill(self, slot_index: int, req: Request) -> PrefillOutcome:
        """Feed one request's prompt into ``slot_index`` (fresh per-slot
        state; other rows untouched) and sample its first token."""
        raise NotImplementedError

    def begin_step(self, active_slots) -> List:
        """Pre-decode bookkeeping; returns slots the backend can no longer
        back (engine finishes them with reason "pages")."""
        return []

    def _first_token(self, next_tok) -> int:
        """Block on a prefill's sampled token (a ``prefill_wait`` span)."""
        with span_or_null(self.tracer, "prefill_wait"):
            # dalek: allow[host-sync] first sampled token must reach the host to emit/EOS-check
            return int(np.asarray(next_tok)[0, 0])

    def decode_step(self, tokens, pos):
        """One fused decode for the whole batch; returns the [B, 1] device
        token array (the engine owns the single host sync)."""
        raise NotImplementedError

    def free_slot(self, slot_index: int):
        """Release/reset one slot's state so the next occupant starts
        clean. Must be called before ``SlotManager.release`` (DLK008)."""
        raise NotImplementedError

    # -- admission ----------------------------------------------------------

    def can_admit(self, req: Request) -> bool:
        """Head-of-line resource check (paged: worst-case pool coverage)."""
        return True

    def expected_cached(self, req: Request) -> int:
        """Prompt span a prefix cache would serve right now (probe only)."""
        return 0

    # -- observability ------------------------------------------------------

    def pool_gauges(self):
        """(free_blocks, evictable_blocks) for step gauges; (-1, -1) when
        the backend has no pool."""
        return -1, -1

    def run_stats(self) -> Dict:
        return {"kv_block_size": self.block_size}

    def reset_metrics(self):
        """Benchmark warmup reset: drop cached/shared state *statistics*
        (jit caches and buffers survive — freed slots are always
        re-prefilled before reuse)."""


class PagedKVAdapter(CacheAdapter):
    """Flat (k, v) layer caches behind a refcounted block pool with radix
    prefix sharing — today's paged path, unchanged semantics: COW on
    defensively-shared write positions, zero-on-free scrubbing, lazy block
    growth in decode, trie eviction under pool pressure."""

    kind = "paged-kv"

    def __init__(self, model, params, *, block_size: int,
                 prefix_cache: bool = True,
                 kv_pool_blocks: Optional[int] = None, **kw):
        super().__init__(model, params, **kw)
        self.block_size = block_size
        self.n_slot_blocks = self.max_seq // block_size
        n_blocks = (kv_pool_blocks if kv_pool_blocks is not None
                    else self.batch_size * self.n_slot_blocks + 1)
        self.pages = PagePool(self.batch_size, self.n_slot_blocks, n_blocks,
                              block_size)
        self.prefix = (RadixPrefixCache(block_size, self.pages)
                       if prefix_cache else None)
        self._decode = counting_jit(
            make_paged_decode_step(model, self.greedy), "decode",
            self.trace_stats, on_compile=self.on_compile)
        self._prefill_slot = counting_jit(
            make_paged_slot_prefill(model, bucketed=bool(self.buckets)),
            "prefill", self.trace_stats, on_compile=self.on_compile)
        self._zero_blocks, self._copy_block = make_block_ops(
            self.trace_stats, self.on_compile)

    def ensure_ready(self):
        if self.caches is None:
            # the "batch" axis of the cache is the POOL of blocks, each
            # block_size positions long; slots see contiguous views
            # through their block tables
            self.caches = self.model.init_cache(self.pages.n_blocks,
                                                self.block_size)

    # -- pool bookkeeping ---------------------------------------------------

    def _flush_freed(self):
        """Scrub freed blocks before any realloc. Fixed-width chunks (padded
        with the null block) keep the jitted zero-kernel at one executable."""
        pending = self.pages.drain_pending_zero()
        if not pending:
            return
        self.step_counts["blocks_scrubbed"] += len(pending)
        width = self.n_slot_blocks
        for i in range(0, len(pending), width):
            chunk = pending[i:i + width]
            chunk = chunk + [PagePool.NULL] * (width - len(chunk))
            self.caches = self._zero_blocks(self.caches,
                                            jnp.asarray(chunk, jnp.int32))

    def _alloc_block(self) -> Optional[int]:
        """One zeroed block, evicting cold prefix-cache entries if the free
        list is dry. Returns None only when every block is live."""
        self._flush_freed()
        blk = self.pages.alloc()
        if blk is None and self.prefix is not None:
            if self.prefix.evict(1):
                self._flush_freed()
                blk = self.pages.alloc()
        if blk is not None:
            self.step_counts["blocks_allocated"] += 1
        return blk

    # -- admission ----------------------------------------------------------

    def expected_cached(self, req: Request) -> int:
        if self.prefix is None:
            return 0
        return self.prefix.probe(np.asarray(req.prompt, np.int32))

    def can_admit(self, req: Request) -> bool:
        """Admit only when the pool can cover the request's worst-case
        footprint (prompt + budget, capped at max_seq) net of the blocks a
        prefix-cache hit would share. Evictable trie blocks count as
        available — ``_alloc_block`` reclaims them on demand."""
        span = min(len(req.prompt) + req.max_new_tokens, self.max_seq)
        needed = self.pages.blocks_for(span) \
            - self.expected_cached(req) // self.block_size
        available = self.pages.free_blocks()
        if self.prefix is not None:
            available += self.prefix.evictable_blocks()
        return needed <= available

    # -- lifecycle ----------------------------------------------------------

    def prefill(self, slot_index: int, req: Request) -> PrefillOutcome:
        """Map the matched prefix (zero compute), allocate blocks for the
        unmatched span, chunk-prefill the tail only, offer the full prompt
        blocks to the trie. ``first_token=None`` when the pool is dry (only
        possible with an explicitly undersized pool — ``can_admit`` covers
        the default sizing)."""
        prompt = np.asarray(req.prompt, np.int32)
        matched = (self.prefix.match(prompt)
                   if self.prefix is not None else [])
        if matched:
            self.pages.map_shared(slot_index, matched)
        start = len(matched) * self.block_size
        # back only the prompt here; decode grows the table block-by-block
        # (``ensure_writable``) so a request that stops early never claims
        # its worst-case footprint
        if not self.pages.ensure_capacity(slot_index, len(prompt),
                                          self._alloc_block):
            self.pages.release_slot(slot_index)
            return PrefillOutcome(None)
        tail = prompt[start:]
        table_row = jnp.asarray(self.pages.table_row(slot_index))
        if self.buckets:
            padded, n = pad_to_bucket(tail, self.buckets)
            next_tok, _, self.caches = self._prefill_slot(
                self.params, jnp.asarray(padded[None, :]), jnp.int32(n),
                jnp.int32(start), table_row, self.caches)
        else:
            next_tok, _, self.caches = self._prefill_slot(
                self.params, jnp.asarray(tail[None, :]), jnp.int32(start),
                table_row, self.caches)
        first = self._first_token(next_tok)
        if self.prefix is not None:
            self.prefix.insert(prompt, self.pages.table_row(slot_index))
        return PrefillOutcome(first, cached_tokens=start,
                              computed_tokens=len(tail))

    def begin_step(self, active_slots) -> List:
        """Back every active slot's write position before the fused step:
        fresh block on a boundary, COW if (defensively) shared, report the
        slot for a "pages" finish when the pool is dry."""
        self.step_counts = dict.fromkeys(self.step_counts, 0)
        doomed = []
        for s in active_slots:
            state, src, dst = self.pages.ensure_writable(
                s.index, s.pos, self._alloc_block)
            if state == "cow":
                self.step_counts["cow"] += 1
                self.caches = self._copy_block(
                    self.caches, jnp.int32(src), jnp.int32(dst))
            elif state == "oom":
                doomed.append(s)
        return doomed

    def decode_step(self, tokens, pos):
        tables = jnp.asarray(self.pages.tables)
        next_tok, _, self.caches = self._decode(
            self.params, tokens, pos, tables, self.caches)
        return next_tok

    def free_slot(self, slot_index: int):
        # drop the slot's block refs; blocks whose refcount hits zero queue
        # for scrubbing and are re-zeroed before any realloc, so the pool
        # stays bit-identical to a contiguous cache whose rows reset on
        # release
        self.pages.release_slot(slot_index)

    # -- observability ------------------------------------------------------

    def pool_gauges(self):
        free = self.pages.free_blocks()
        evictable = (self.prefix.evictable_blocks()
                     if self.prefix is not None else -1)
        return free, evictable

    def run_stats(self) -> Dict:
        pg = self.pages.stats.as_dict()
        pg["free_blocks"] = self.pages.free_blocks()
        out = {"kv_block_size": self.block_size, "kv_pages": pg}
        if self.prefix is not None:
            out["prefix_cache"] = self.prefix.stats.as_dict()
        return out

    def reset_metrics(self):
        if self.prefix is not None:
            # cold prefix cache: a benchmark's measured phase must not reap
            # hits the warmup planted (the warmup's *compiles* are exactly
            # what reset keeps)
            self.prefix.clear()
        self.pages.stats = type(self.pages.stats)(
            total_blocks=self.pages.stats.total_blocks)


class WindowRingAdapter(CacheAdapter):
    """Contiguous per-slot cache rows — the gemma3 local:global window
    *ring* backend (rings can't resume mid-stream, so no paging and no
    chunked prefill), doubling as the flat-cache contiguous fallback when
    paging is explicitly disabled. Slot reset zeroes the row."""

    kind = "window-ring"

    def __init__(self, model, params, **kw):
        super().__init__(model, params, **kw)
        if self.caps.kind != "window-ring":
            self.kind = "contiguous"       # flat family with paging off
        self._decode = counting_jit(make_decode_step(model, self.greedy),
                                    "decode", self.trace_stats,
                                    on_compile=self.on_compile)
        self._prefill_slot = counting_jit(
            make_slot_prefill(model, bucketed=bool(self.buckets)),
            "prefill", self.trace_stats, on_compile=self.on_compile)
        self._reset_slot = counting_jit(reset_cache_slot, "reset_slot",
                                        self.trace_stats,
                                        on_compile=self.on_compile)

    def ensure_ready(self):
        if self.caches is None:
            self.caches = self.model.init_cache(self.batch_size,
                                                self.max_seq)

    def prefill(self, slot_index: int, req: Request) -> PrefillOutcome:
        prompt = np.asarray(req.prompt, np.int32)
        if self.buckets:
            padded, n = pad_to_bucket(prompt, self.buckets)
            next_tok, _, self.caches = self._prefill_slot(
                self.params, jnp.asarray(padded[None, :]), jnp.int32(n),
                jnp.int32(slot_index), self.caches)
        else:
            next_tok, _, self.caches = self._prefill_slot(
                self.params, jnp.asarray(prompt[None, :]),
                jnp.int32(slot_index), self.caches)
        first = self._first_token(next_tok)
        return PrefillOutcome(first, computed_tokens=len(prompt))

    def decode_step(self, tokens, pos):
        next_tok, _, self.caches = self._decode(
            self.params, tokens, pos, self.caches)
        return next_tok

    def free_slot(self, slot_index: int):
        # recycle: zero the slot's cache rows so the next occupant starts
        # clean
        self.caches = self._reset_slot(self.caches, jnp.int32(slot_index))


class RecurrentStateAdapter(CacheAdapter):
    """Carried-state families (SSM/hybrid/encoder-decoder) in the
    continuous batcher.

    Prefill never right-pads: the prompt is decomposed into power-of-two
    chunks (largest first — its binary representation) and fed left-to-
    right through ``model.prefill`` with the state carried between chunks,
    starting from a *freshly initialized* batch-1 state template. The
    finished state is scattered wholesale into the slot's row of the
    shared batch tree — which doubles as the reset: no stale state from a
    prior occupant can survive, because every leaf row is overwritten.
    Executable count is bounded by the distinct chunk sizes
    (<= log2(max_seq), plus the frames variant for audio's first chunk),
    never by request count.

    Decode reuses the ordinary fused step: recurrent models take the whole
    state tree and a [B] position vector (position-free families ignore
    it), and every update is per-row, so batched decode is bit-exact
    against one-request-at-a-time serving (property-tested).

    Free rows keep whatever state their garbage decode writes produce; the
    next occupant's prefill overwrites every leaf row before any read, so
    that garbage is never observable.
    """

    kind = "recurrent"

    def __init__(self, model, params, **kw):
        super().__init__(model, params, **kw)
        assert not self.buckets, "recurrent prefill cannot right-pad"
        # per-leaf batch axis: recurrent trees mix [L, B, ...] and [B, ...]
        # leaves — diff two abstract shapes to find which axis is batch
        s2 = jax.eval_shape(lambda: model.init_cache(2, self.max_seq))
        s3 = jax.eval_shape(lambda: model.init_cache(3, self.max_seq))
        self._axes = jax.tree.map(
            lambda a, b: next(i for i, (x, y) in
                              enumerate(zip(a.shape, b.shape)) if x != y),
            s2, s3)
        self._fresh = None    # batch-1 freshly-initialized state template
        self._decode = counting_jit(make_decode_step(model, self.greedy),
                                    "decode", self.trace_stats,
                                    on_compile=self.on_compile)
        self._chunk = counting_jit(
            make_recurrent_chunk_prefill(model), "prefill",
            self.trace_stats, on_compile=self.on_compile)
        self._scatter = counting_jit(
            lambda caches, sub, slot: scatter_state_slot(
                caches, sub, slot, self._axes),
            "state_scatter", self.trace_stats, on_compile=self.on_compile)

    def ensure_ready(self):
        if self.caches is None:
            self.caches = self.model.init_cache(self.batch_size,
                                                self.max_seq)
            self._fresh = self.model.init_cache(1, self.max_seq)

    def prefill(self, slot_index: int, req: Request) -> PrefillOutcome:
        prompt = np.asarray(req.prompt, np.int32)
        frames = req.frames
        state = self._fresh
        offset = 0
        next_tok = None
        for size in pow2_chunks(len(prompt)):
            tokens = jnp.asarray(prompt[None, offset:offset + size])
            fr = (jnp.asarray(frames)[None] if
                  (frames is not None and offset == 0) else None)
            next_tok, _, state = self._chunk(
                self.params, tokens, fr, jnp.int32(offset), state)
            offset += size
        # scatter the finished batch-1 state into the slot's row: claims
        # AND resets the row in one write (every leaf row is overwritten)
        self.caches = self._scatter(self.caches, state,
                                    jnp.int32(slot_index))
        first = self._first_token(next_tok)
        return PrefillOutcome(first, computed_tokens=len(prompt))

    def decode_step(self, tokens, pos):
        next_tok, _, self.caches = self._decode(
            self.params, tokens, pos, self.caches)
        return next_tok

    def free_slot(self, slot_index: int):
        # belt-and-braces reset: scatter the fresh template into the freed
        # row (same executable as the prefill scatter). The next prefill
        # overwrites the row anyway, but a zeroed row keeps state dumps and
        # replay bit-reproducible regardless of traffic order.
        if self.caches is not None:
            self.caches = self._scatter(self.caches, self._fresh,
                                        jnp.int32(slot_index))


def make_adapter(model, params, *, batch_size: int, max_seq: int,
                 prefill_buckets="auto", kv_block_size="auto",
                 prefix_cache: bool = True,
                 kv_pool_blocks: Optional[int] = None, greedy: bool = True,
                 trace_stats: Optional[TraceStats] = None, on_compile=None,
                 tracer: Optional[Tracer] = None):
    """Select and build the backend for ``model``'s declared capabilities.

    ``"auto"`` arguments degrade silently where the family can't honor them
    (paging/bucketing off for recurrent, paging off for window rings);
    explicit requests on an incapable family raise with the actionable
    alternative — the early error ``launch/serve.py`` surfaces."""
    caps = serving_caps(model.cfg)
    buckets = resolve_buckets(prefill_buckets, max_seq, model)
    trace_stats = trace_stats if trace_stats is not None else TraceStats()
    common = dict(batch_size=batch_size, max_seq=max_seq, buckets=buckets,
                  caps=caps, trace_stats=trace_stats, on_compile=on_compile,
                  greedy=greedy, tracer=tracer)
    block_size = resolve_kv_block_size(kv_block_size, max_seq, caps.paged_kv)
    if caps.kind == "recurrent":
        return RecurrentStateAdapter(model, params, **common)
    if block_size:
        return PagedKVAdapter(model, params, block_size=block_size,
                              prefix_cache=prefix_cache,
                              kv_pool_blocks=kv_pool_blocks, **common)
    return WindowRingAdapter(model, params, **common)
