"""Paged decode attention: one query token per slot against that slot's live
KV blocks, read from the stacked pool in place through its block table.

The pool leaves are ``[L, P, block, kvh, dh]`` (layer, pool block, offset,
KV head, head dim) and stay in HBM (``memory_space=ANY``); the layer index,
the flattened block tables and the per-slot lengths are scalar prefetch, so
no per-layer slice of the pool is ever materialised.

Grid: one step per slot, in order. A slot's blocks are read in chunks of
``blocks_per_chunk`` blocks: each live block (``[block, kvh, dh]``, every KV
head at once) is one DMA into a double-buffered VMEM chunk, and blocks at or
past the slot's length are not fetched at all. The next chunk, or the next
slot's first chunk, is in flight while the current one is computed.

Grouped-query attention without a repeat: a block is viewed as
``[block * kvh, dh]`` rows (row ``t * kvh + g`` is position ``t`` of KV head
``g``), every query head is scored against every row in one MXU product, and
a mask keeps query head ``h`` on KV head ``h // (H // kvh)`` (the mapping of
``models.common._repeat_kv``, padded heads included) and on positions below
the length. Scores, running max and sum and the accumulator are float32
(online softmax); the MXU inputs are the cache's dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK = -1e30


def _kernel(layer_ref, tables_ref, lengths_ref,        # scalar prefetch
            q_ref, k_hbm, v_hbm,                        # inputs
            o_ref,                                      # output
            k_buf, v_buf, sems, cur_buf,                # scratch
            *, n_slots, n_table, block, kvh, bpc, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]
    chunk_len = bpc * block

    def copies(slot, chunk, buf, j):
        blk = tables_ref[slot * n_table + chunk * bpc + j]
        return (pltpu.make_async_copy(k_hbm.at[layer, blk], k_buf.at[buf, j],
                                      sems.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], v_buf.at[buf, j],
                                      sems.at[buf, 1]))

    def for_live_blocks(slot, chunk, buf, act):
        for j in range(bpc):
            @pl.when((chunk * bpc + j) * block < lengths_ref[slot])
            def _():
                for c in copies(slot, chunk, buf, j):
                    act(c)

    @pl.when(b == 0)
    def _first():
        # blocks past a slot's length are never fetched: their rows keep
        # what the buffer held, so it starts as zeros, never as garbage
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        cur_buf[0] = 0
        for_live_blocks(0, 0, 0, lambda c: c.start())

    length = lengths_ref[b]
    q = q_ref[...]
    h, dh = q.shape
    rows = bpc * block * kvh
    row = lax.broadcasted_iota(jnp.int32, (h, rows), 0)
    col = lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    own_head = row // (h // kvh) == col % kvh
    col_pos = col // kvh

    def body(chunk, carry):
        m, l, acc = carry
        buf = cur_buf[0]
        last = chunk + 1 == pl.cdiv(length, chunk_len)
        nxt_slot = jnp.where(last, b + 1, b)
        nxt_chunk = jnp.where(last, 0, chunk + 1)

        @pl.when(nxt_slot < n_slots)
        def _prefetch():
            for_live_blocks(nxt_slot, nxt_chunk, 1 - buf, lambda c: c.start())

        for_live_blocks(b, chunk, buf, lambda c: c.wait())
        k = k_buf[buf].reshape(rows, dh)
        v = v_buf[buf].reshape(rows, dh)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        ok = own_head & (chunk * chunk_len + col_pos < length)
        s = jnp.where(ok, s, MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        cur_buf[0] = 1 - buf
        return m_new, l, acc

    init = (jnp.full((h, 1), MASK, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, pl.cdiv(length, chunk_len), body, init)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                    blocks_per_chunk=8, interpret=False):
    """q: [B, H, dh]; k_pool, v_pool: [L, P, block, kvh, dh]; layer: int32
    scalar; tables: [B, NB] int32 block ids; lengths: [B] int32 in
    [1, NB * block]. Returns [B, H, dh] in q's dtype: each slot's query
    heads attended over positions [0, length) of its logical cache."""
    n_slots, h, dh = q.shape
    n_layers, n_pool, block, kvh, _ = k_pool.shape
    n_table = tables.shape[1]
    assert h % kvh == 0, (h, kvh)
    bpc = min(blocks_per_chunk, n_table)
    # [block, kvh, dh] -> [block * kvh, dh]: the pool's tiled layout keeps
    # this a bitcast for the head counts the paged families use
    k_rows = k_pool.reshape(n_layers, n_pool, block * kvh, dh)
    v_rows = v_pool.reshape(n_layers, n_pool, block * kvh, dh)
    kernel = functools.partial(
        _kernel, n_slots=n_slots, n_table=n_table, block=block, kvh=kvh,
        bpc=bpc, scale=1.0 / dh ** 0.5)
    slot_spec = pl.BlockSpec((None, h, dh), lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[slot_spec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((2, bpc, block * kvh, dh), k_pool.dtype),
                pltpu.VMEM((2, bpc, block * kvh, dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # the next slot's first chunk is fetched during this slot's last
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_rows, v_rows)
