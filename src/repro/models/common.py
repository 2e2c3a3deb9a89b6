"""Shared model components: params, norms, RoPE, GQA attention, MLPs, loss.

All modules are pure functions over explicit parameter pytrees. Every init
function returns ``(params, axes)`` where ``axes`` mirrors the params tree
with logical-axis tuples consumed by ``repro.parallel.sharding``.

The layers a step's device time splits into run under ``jax.named_scope``s
(``attention`` with ``kv_repeat`` inside it, ``mlp``, ``lm_head``,
``kv_gather``, ``kv_scatter``): metadata only, so the XLA ops of a device
trace name their layer in ``op_name``.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.parallel.sharding import Sharder

# ---------------------------------------------------------------------------
# parameter helpers


def _init(key, shape, dtype, scale):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


class ParamBuilder:
    """Accumulates (params, logical-axes) trees with auto key splitting.

    Pass ``key=None`` for *abstract* mode: parameters become
    ShapeDtypeStructs (no allocation, no RNG) — used by the dry-run.
    """

    def __init__(self, key, dtype=jnp.float32):
        self.key = key
        self.dtype = dtype
        self.params = {}
        self.axes = {}

    @property
    def abstract(self):
        return self.key is None

    def dense(self, name, shape, axes, fan_in=None, zero=False, one=False):
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, self.dtype)
        elif one:
            arr = jnp.ones(shape, self.dtype)
        elif zero:
            arr = jnp.zeros(shape, self.dtype)
        else:
            self.key, sub = jax.random.split(self.key)
            fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
            arr = _init(sub, shape, self.dtype, 1.0 / np.sqrt(max(fan, 1)))
        self.params[name] = arr
        self.axes[name] = tuple(axes)
        return arr

    def child(self, name):
        key = None
        if not self.abstract:
            # a stable digest, not hash(): str hashes are salted per process,
            # which would give every process different seeded weights
            key = jax.random.fold_in(self.key, zlib.crc32(name.encode()))
        sub = ParamBuilder(key, self.dtype)
        self.params[name] = sub.params
        self.axes[name] = sub.axes
        return sub

    def build(self):
        return self.params, self.axes


# ---------------------------------------------------------------------------
# norms


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings


def rope(x, positions, theta=10_000.0):
    """x: [..., S, H, Dh]; positions: [..., S] int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA; full / sliding window / blocked-lazy-softmax / decode)


def attn_init(pb: ParamBuilder, cfg: ModelConfig, L: Optional[int] = None):
    """Stacked ([L] leading) or single-layer attention params.

    When ``cfg.pad_q_heads`` > num_heads (TP-axis adaptation), the padded
    head rows of wq and columns of wo are zero-initialized: padded heads
    compute softmax(0·k)·v through a zero wo column — exact no-ops.
    """
    pre = (L,) if L is not None else ()
    pax = ("layers",) if L is not None else ()
    d, h, kvh, dh = cfg.d_model, cfg.q_heads, cfg.num_kv_heads, cfg.head_dim
    wq = pb.dense("wq", pre + (d, h, dh), pax + ("embed", "heads", "head_dim"), fan_in=d)
    pb.dense("wk", pre + (d, kvh, dh), pax + ("embed", "kv_heads", "head_dim"), fan_in=d)
    pb.dense("wv", pre + (d, kvh, dh), pax + ("embed", "kv_heads", "head_dim"), fan_in=d)
    wo = pb.dense("wo", pre + (h, dh, d), pax + ("heads", "head_dim", "embed"),
                  fan_in=h * dh)
    if h != cfg.num_heads and not pb.abstract:
        # per-KV-group padding: group g holds G real heads then G_pad-G
        # zeroed pads, so _repeat_kv's h -> h // G_pad mapping is preserved
        g_pad = h // kvh
        g_real = cfg.num_heads // kvh
        mask = (jnp.arange(h) % g_pad) < g_real
        pb.params["wq"] = wq * mask[:, None].astype(wq.dtype)
        pb.params["wo"] = wo * mask[:, None, None].astype(wo.dtype)
    if cfg.qk_norm:
        pb.dense("q_norm", pre + (dh,), pax + ("norm",), zero=True)
        pb.dense("k_norm", pre + (dh,), pax + ("norm",), zero=True)


def _qkv(x, p, cfg: ModelConfig, positions, shd: Sharder, use_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shd(q, "batch", "seq", "act_heads", None)
    k = shd(k, "batch", "seq", "act_kv_heads", None)
    v = shd(v, "batch", "seq", "act_kv_heads", None)
    return q, k, v


@jax.named_scope("kv_repeat")
def _repeat_kv(k, n_q_heads):
    """GQA: repeat KV heads to the query-head count.

    Keeps the attention einsums in [B,*,H,dh] form with H sharded over the
    TP axis — shardable for ANY kv-head count (kvh that doesn't divide the
    mesh would otherwise force replicated attention).
    """
    kvh = k.shape[2]
    if kvh == n_q_heads:
        return k
    idx = jnp.arange(n_q_heads) // (n_q_heads // kvh)
    return jnp.take(k, idx, axis=2)


def _mask(q_pos, k_pos, *, causal, window, is_global):
    """Attention mask. window/is_global may be traced.

    Unbatched: q_pos [S], k_pos [T] -> bool [S, T].
    Batched (continuous batching: per-slot positions): q_pos [B, S] and/or
    k_pos [B, T] -> bool [B, S, T].
    """
    if q_pos.ndim > 1 or k_pos.ndim > 1:
        qp = (q_pos if q_pos.ndim > 1 else q_pos[None])[:, :, None]
        kp = (k_pos if k_pos.ndim > 1 else k_pos[None])[:, None, :]
    else:
        qp, kp = q_pos[:, None], k_pos[None, :]
    shape = jnp.broadcast_shapes(qp.shape, kp.shape)
    m = jnp.ones(shape, bool)
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        in_win = (qp - kp) < window
        m = m & jnp.where(is_global, True, in_win)
    return m


def attention_scores(q, k, v, mask, scores_f32=True):
    """Naive full attention. q:[B,S,H,Dh] k,v:[B,T,H,Dh] mask:[S,T] or [B,S,T].

    scores_f32=False keeps the score/probability buffers in bf16 (flash-
    style numerics: max-subtracted exp in bf16, f32 denominator) — halves
    the attention HBM traffic on the XLA fallback path; the Pallas kernel
    keeps everything in VMEM regardless.
    """
    dh = q.shape[-1]
    mb = mask[None, None] if mask.ndim == 2 else mask[:, None]  # -> [B|1,1,S,T]
    if scores_f32:
        s = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(dh)
        s = jnp.where(mb, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bthd->bshd", p, v)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.asarray(np.sqrt(dh), q.dtype)
    s = jnp.where(mb, s, jnp.asarray(-jnp.inf, s.dtype))
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    m = jnp.maximum(m, jnp.asarray(-1e30, s.dtype))  # all-masked rows
    p = jnp.exp(s - m)                                # bf16, in [0,1]
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)  # [B,H,S,1]
    o = jnp.einsum("bhst,bthd->bshd", p, v)
    return o / jnp.maximum(denom, 1e-30).swapaxes(1, 2).astype(o.dtype)


def blocked_attention(q, k, v, q_positions, k_positions, *, causal, window,
                      is_global, q_block=512, scores_f32=True):
    """Memory-bounded attention: scan over query blocks.

    Keeps the live score buffer at [B, H, qb, T] instead of [.., S, T].
    This is the pure-JAX analogue of the flash_attention Pallas kernel; the
    kernel is used on real TPUs, this path is used for lowering/dry-run and
    CPU validation.
    """
    b, s, h, dh = q.shape
    qb = min(q_block, s)
    n_blocks = (s + qb - 1) // qb
    pad = n_blocks * qb - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad), constant_values=-1)
    qs = q.reshape(b, n_blocks, qb, h, dh).transpose(1, 0, 2, 3, 4)
    qpos = q_positions.reshape(n_blocks, qb)

    def body(carry, inp):
        qblk, qp = inp
        m = _mask(qp, k_positions, causal=causal, window=window,
                  is_global=is_global)
        o = attention_scores(qblk, k, v, m, scores_f32)
        return carry, o

    # recompute scores/probs in backward: without this the inner scan stacks
    # per-block probability+mask buffers for the whole sequence (O(S*T))
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = lax.scan(body, None, (qs, qpos))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * qb, h, dh)
    if pad:
        out = out[:, :s]
    return out


@jax.named_scope("attention")
def attention(x, p, cfg: ModelConfig, shd: Sharder, *, positions,
              is_global=True, causal=True, impl="blocked", q_block=512,
              kv_cache=None, cache_pos=None, use_rope=True,
              k_positions=None, k_valid=None, cache_slot=None,
              return_kv=False, scores_f32=True):
    """Full attention module. Returns (out, new_kv_cache_entry).

    kv_cache: optional (k_cache, v_cache) with shape [B, T_max, kvh, Dh];
    when given, behaves as a decode/prefill step writing at ``cache_pos``
    (or ``cache_slot`` when the cache is a ring buffer — then pass explicit
    ``k_positions``/``k_valid`` for the slot->token-position mapping).
    ``cache_pos``/``cache_slot`` may be a [B] vector during single-token
    decode (continuous batching: every batch row sits at its own position;
    pass ``positions`` as [B, 1] to match).
    return_kv: also return the freshly projected (k, v) (used to build
    window ring buffers after a cache-less prefill).
    """
    b, s, d = x.shape
    kvh = cfg.num_kv_heads
    window = cfg.sliding_window if cfg.sliding_window > 0 else None
    q, k, v = _qkv(x, p, cfg, positions, shd, use_rope=use_rope)
    fresh_kv = (k, v)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        write_at = cache_pos if cache_slot is None else cache_slot
        if jnp.ndim(write_at) == 0:
            ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, write_at, 0, 0))
            cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, write_at, 0, 0))
        else:
            assert s == 1, "per-row cache positions require single-token decode"
            bidx = jnp.arange(b)
            ck = ck.at[bidx, write_at].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[bidx, write_at].set(v[:, 0].astype(cv.dtype))
        new_cache = (ck, cv)
        k, v = ck, cv
        t_max = ck.shape[1]
        if k_positions is None:
            k_positions = jnp.arange(t_max)
            if jnp.ndim(cache_pos) == 0:
                valid = k_positions < (cache_pos + s)
            else:
                valid = k_positions[None, :] < (cache_pos[:, None] + s)
        else:
            valid = k_valid
    else:
        if k_positions is None:
            k_positions = positions
        valid = k_valid

    # GQA: repeat KV to query-head count; H stays TP-shardable
    k = _repeat_kv(k.astype(q.dtype), cfg.q_heads)
    v = _repeat_kv(v.astype(q.dtype), cfg.q_heads)
    k = shd(k, "batch", None, "act_heads", None)
    v = shd(v, "batch", None, "act_heads", None)
    qg = q

    if s == 1 and kv_cache is not None:
        # decode: single query, direct masked attention over the cache
        m = _mask(positions, k_positions, causal=causal, window=window,
                  is_global=is_global)
        if valid is not None:
            m = m & (valid[None, :] if valid.ndim == 1 else valid[:, None, :])
        o = attention_scores(qg, k, v, m, scores_f32)
    elif impl == "naive":
        m = _mask(positions, k_positions, causal=causal, window=window,
                  is_global=is_global)
        if valid is not None:
            m = m & (valid[None, :] if valid.ndim == 1 else valid[:, None, :])
        o = attention_scores(qg, k, v, m, scores_f32)
    else:
        if valid is not None:
            # prefill into cache: mask invalid tail via positions trick
            o = blocked_attention(qg, k, v, positions, jnp.where(valid, k_positions, 2**30),
                                  causal=causal, window=window,
                                  is_global=is_global, q_block=q_block,
                                  scores_f32=scores_f32)
        else:
            o = blocked_attention(qg, k, v, positions, k_positions,
                                  causal=causal, window=window,
                                  is_global=is_global, q_block=q_block,
                                  scores_f32=scores_f32)

    o = o.reshape(b, s, cfg.q_heads, cfg.head_dim)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    out = shd(out, "batch", "seq", "act_embed")
    if return_kv:
        return out, fresh_kv
    return out, new_cache


# ---------------------------------------------------------------------------
# KV-cache slot management (continuous batching)


def reset_cache_slot(caches, slot, batch_axis=1):
    """Zero one batch row across a KV-cache pytree (slot recycling).

    Caches are stacked [L, B, T, kvh, dh] arrays (or dicts of them for
    local:global window caches); ``batch_axis`` selects the B axis. ``slot``
    may be a traced scalar, so the helper is jit-friendly.
    """
    def _zero(c):
        row = lax.dynamic_slice_in_dim(c, slot, 1, batch_axis)
        return lax.dynamic_update_slice_in_dim(
            c, jnp.zeros_like(row), slot, batch_axis)
    return jax.tree.map(_zero, caches)


def mask_cache_tail(caches, true_len, batch_axis=1):
    """Zero cache entries at positions >= ``true_len`` along the seq axis.

    Right-pad hygiene for bucketed prefill: a prompt padded to its bucket
    edge writes pad-token KV at [true_len, bucket); zeroing that tail keeps
    the invariant that a slot's cache holds exactly its real prefix (decode
    validity masks would hide the pad entries anyway, but a clean cache
    makes bucketed and exact-length prefill states bit-identical).

    Works for flat stacked caches ([L, B, T, kvh, dh]) and the gemma3
    local:global dict: global leaves index the seq axis by absolute
    position; local ring leaves index by ring slot, where ``_ring_gather``
    already zeroed slots beyond the true length (for rings shorter than
    ``true_len`` every slot holds a real position and the mask is a no-op).
    ``true_len`` may be a traced scalar.
    """
    def _mask(c):
        seq_axis = batch_axis + 1
        idx = lax.broadcasted_iota(jnp.int32, c.shape, seq_axis)
        return jnp.where(idx < true_len, c, jnp.zeros((), c.dtype))
    return jax.tree.map(_mask, caches)


def gather_cache_slot(caches, slot, batch_axis=1):
    """Extract one batch row of a cache pytree as a batch-1 cache."""
    return jax.tree.map(
        lambda c: lax.dynamic_slice_in_dim(c, slot, 1, batch_axis), caches)


# ---------------------------------------------------------------------------
# paged KV cache indirection (serve.paging owns the block bookkeeping)
#
# The physical pool stores fixed-size KV blocks: each leaf is
# [L, P, block, kvh, dh] — a contiguous cache whose "batch" axis is the
# block id and whose seq axis is block_size positions. A slot's logical
# cache is defined by its block table (an [NB] row of block ids): logical
# position t lives in pool block ``table[t // block]`` at offset
# ``t % block``. Gathering a table therefore reconstructs a contiguous
# [L, B, NB*block, kvh, dh] view bit-identical to the per-slot cache the
# unpaged steps use — which is exactly the bit-exactness contract the
# paged serving steps are property-tested against.


@jax.named_scope("kv_gather")
def paged_gather(pool, tables):
    """Materialize logical cache views through block tables.

    pool leaves: [L, P, block, kvh, dh]; tables: [B, NB] int32 block ids.
    Returns leaves [L, B, NB*block, kvh, dh] — the per-slot contiguous view
    the unmodified model decode/prefill runs on.
    """
    def g(c):
        v = jnp.take(c, tables, axis=1)          # [L, B, NB, block, ...]
        return v.reshape(v.shape[0], tables.shape[0], -1, *v.shape[4:])
    return jax.tree.map(g, pool)


@jax.named_scope("kv_scatter")
def paged_scatter_block(pool, view, tables, pos):
    """Write back, per batch row, the single block containing ``pos``.

    Decode mutates exactly one position per slot, so only the touched block
    needs to return to the pool. ``pos``: [B] int32 per-slot positions.
    Free slots point at the reserved null block; their duplicate scatter
    indices collide there harmlessly (the null block is never read).
    """
    b = tables.shape[0]
    bidx = jnp.arange(b)

    def s(c, v):
        blk_size = c.shape[2]
        blk = pos // blk_size
        vr = v.reshape(v.shape[0], b, -1, blk_size, *v.shape[3:])
        touched = vr[:, bidx, blk]               # [L, B, block, ...]
        return c.at[:, tables[bidx, blk]].set(touched)
    return jax.tree.map(s, pool, view)


@jax.named_scope("kv_scatter")
def paged_scatter_slot(pool, view, table_row):
    """Write a batch-1 logical view back through one slot's block table.

    Used after a slot prefill: every view block returns to its pool block.
    Shared prefix blocks are rewritten with the identical bytes the gather
    read (prefill only mutates positions >= its start offset), so other
    owners observe no change; unallocated tail entries scatter into the
    null block.
    """
    def s(c, v):
        blk_size = c.shape[2]
        vr = v.reshape(v.shape[0], -1, blk_size, *v.shape[3:])
        return c.at[:, table_row].set(vr)
    return jax.tree.map(s, pool, view)


def reset_cache_blocks(pool, blocks):
    """Zero a batch of pool blocks (freed-block scrubbing).

    ``blocks``: [K] int32 block ids, padded with the null block id (0) —
    duplicate indices are fine, the scatter just re-zeroes. Keeping freed
    blocks zeroed preserves the invariant that a paged pool is bit-identical
    to a contiguous cache whose slot rows reset on release.
    """
    def z(c):
        shape = (c.shape[0], blocks.shape[0]) + c.shape[2:]
        return c.at[:, blocks].set(jnp.zeros(shape, c.dtype))
    return jax.tree.map(z, pool)


def copy_cache_block(pool, src, dst):
    """Copy one pool block (copy-on-write): dst <- src across every leaf.
    ``src``/``dst`` may be traced scalars."""
    def cp(c):
        blk = lax.dynamic_slice_in_dim(c, src, 1, 1)
        return lax.dynamic_update_slice_in_dim(c, blk, dst, 1)
    return jax.tree.map(cp, pool)


def scatter_cache_slot(caches, update, slot, batch_axis=1):
    """Write a batch-1 cache pytree back into one batch row."""
    return jax.tree.map(
        lambda c, u: lax.dynamic_update_slice_in_dim(
            c, u.astype(c.dtype), slot, batch_axis), caches, update)


# ---------------------------------------------------------------------------
# mixed-axis state trees (recurrent serving)
#
# Recurrent families stack per-layer state with the batch on DIFFERENT axes
# per leaf: xlstm mLSTM/conv leaves are [L, B, ...] (axis 1) while its sLSTM
# leaves are [B, ...] (axis 0); zamba2 mixes [L, B, ...] mamba state with
# [B, T, ...] attention KV. These helpers take an ``axes`` pytree (same
# structure as ``state``, int batch-axis per leaf — inferred once by
# ``serve.state`` from two ``jax.eval_shape``s of ``init_cache``) so one
# gather/scatter pair serves every family.


def gather_state_slot(state, slot, axes):
    """Extract one batch row of a mixed-axis state tree as a batch-1 tree.
    ``slot`` may be a traced scalar."""
    return jax.tree.map(
        lambda c, ax: lax.dynamic_slice_in_dim(c, slot, 1, ax), state, axes)


def scatter_state_slot(state, update, slot, axes):
    """Write a batch-1 mixed-axis state tree back into one batch row.

    Scattering a freshly-initialized batch-1 template is also how a slot is
    *reset*: every leaf row is overwritten wholesale, so no stale carried
    state (or KV) from a prior occupant survives slot reuse."""
    return jax.tree.map(
        lambda c, u, ax: lax.dynamic_update_slice_in_dim(
            c, u.astype(c.dtype), slot, ax), state, update, axes)


# ---------------------------------------------------------------------------
# MLPs


def mlp_init(pb: ParamBuilder, d_model, d_ff, L: Optional[int] = None,
             hidden_axis="mlp"):
    pre = (L,) if L is not None else ()
    pax = ("layers",) if L is not None else ()
    pb.dense("w_gate", pre + (d_model, d_ff), pax + ("embed", hidden_axis), fan_in=d_model)
    pb.dense("w_up", pre + (d_model, d_ff), pax + ("embed", hidden_axis), fan_in=d_model)
    pb.dense("w_down", pre + (d_ff, d_model), pax + (hidden_axis, "embed"), fan_in=d_ff)


@jax.named_scope("mlp")
def mlp(x, p, shd: Sharder, hidden_axis="act_mlp"):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    h = shd(h, "batch", "seq", hidden_axis)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    return shd(out, "batch", "seq", "act_embed")


# ---------------------------------------------------------------------------
# embeddings & loss


def embed_init(pb: ParamBuilder, cfg: ModelConfig):
    pb.dense("embedding", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
             fan_in=cfg.d_model)
    pb.dense("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
             fan_in=cfg.d_model)
    pb.dense("final_norm", (cfg.d_model,), ("norm",), zero=True)


def embed(tokens, p, dtype):
    return p["embedding"].astype(dtype)[tokens]


@jax.named_scope("lm_head")
def unembed(x, p, shd: Sharder):
    x = rms_norm(x, p["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(x.dtype))
    return shd(logits, "batch", "seq", "act_vocab")


def chunked_softmax_xent(h, params, labels, mask=None, n_chunks=16):
    """Cross-entropy without materializing [B,S,V] logits.

    Online logsumexp over vocab chunks; each chunk's logits are recomputed
    in the backward pass (jax.checkpoint), so peak memory is O(B*S*V/n).
    """
    hn = rms_norm(h, params["final_norm"])
    w = params["unembed"]
    v = w.shape[1]
    c = v // n_chunks
    assert v % n_chunks == 0, (v, n_chunks)
    b, s, _ = h.shape

    def body(carry, i):
        m_run, s_run, gold = carry
        wc = lax.dynamic_slice_in_dim(w, i * c, c, 1).astype(hn.dtype)
        lo = jnp.einsum("bsd,dc->bsc", hn, wc).astype(jnp.float32)
        m_new = jnp.maximum(m_run, jnp.max(lo, axis=-1))
        s_run = (s_run * jnp.exp(m_run - m_new)
                 + jnp.sum(jnp.exp(lo - m_new[..., None]), axis=-1))
        in_range = (labels >= i * c) & (labels < (i + 1) * c)
        idx = jnp.clip(labels - i * c, 0, c - 1)
        g = jnp.take_along_axis(lo, idx[..., None], axis=-1)[..., 0]
        gold = gold + jnp.where(in_range, g, 0.0)
        return (m_new, s_run, gold), None

    init = (jnp.full((b, s), -1e30, jnp.float32),
            jnp.zeros((b, s), jnp.float32),
            jnp.zeros((b, s), jnp.float32))
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m_run, s_run, gold), _ = lax.scan(body, init, jnp.arange(n_chunks))
    nll = jnp.log(jnp.maximum(s_run, 1e-30)) + m_run - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def softmax_xent(logits, labels, mask=None):
    """Mean cross-entropy in fp32; labels: int [B,S]."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
