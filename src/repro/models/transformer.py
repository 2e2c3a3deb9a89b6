"""Dense decoder-only transformer stack (scan-over-layers).

Covers granite-20b, deepseek-coder-33b, qwen3-32b, gemma3-27b (5:1
local:global via per-layer scanned flags) and the internvl2-76b backbone
(patch-embedding prefix from the stub frontend). MoE layers plug in through
``repro.models.moe``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common, moe as moe_mod
from repro.models.common import ParamBuilder
from repro.parallel.sharding import Sharder


def layer_flags(cfg: ModelConfig) -> np.ndarray:
    """Per-layer is_global flag (gemma3 local:global pattern)."""
    if cfg.local_global_period <= 0:
        return np.ones((cfg.num_layers,), np.bool_)
    idx = np.arange(cfg.num_layers)
    return (idx + 1) % cfg.local_global_period == 0


class DecoderLM:
    """Functional decoder-only LM; params are explicit pytrees."""

    def __init__(self, cfg: ModelConfig, mesh=None, *, attn_impl="blocked",
                 q_block=512, remat=True, shd_rules=None, barrier=False,
                 scores_f32=True, carry_barrier=False, moe_impl="gspmd"):
        self.cfg = cfg
        self.shd = Sharder(mesh, rules=shd_rules, barrier=barrier)
        self.attn_impl = attn_impl
        self.q_block = q_block
        self.remat = remat
        self.scores_f32 = scores_f32
        # pin the scan carry inside the (remat) body: stops XLA:CPU from
        # hoisting a whole-stash bf16->f32 convert out of the backward loop
        self.carry_barrier = carry_barrier
        self.moe_impl = moe_impl
        self.n_scan = cfg.num_layers - cfg.first_k_dense

    # -- params ------------------------------------------------------------

    def init(self, key):
        cfg = self.cfg
        pb = ParamBuilder(key, jnp.dtype(cfg.param_dtype))
        common.embed_init(pb, cfg)
        if cfg.frontend_stub and cfg.family == "vlm":
            pb.dense("patch_proj", (cfg.d_model, cfg.d_model), ("embed", None),
                     fan_in=cfg.d_model)
        lb = pb.child("layers")
        self._layer_init(lb, cfg, self.n_scan)
        if cfg.first_k_dense:
            db = pb.child("dense_prefix")
            for i in range(cfg.first_k_dense):
                sub = db.child(f"layer_{i}")
                self._dense_layer_init(sub, cfg, None)
        return pb.build()

    def _dense_layer_init(self, pb, cfg, L):
        pre_ax = ("layers",) if L is not None else ()
        pre = (L,) if L is not None else ()
        pb.dense("norm1", pre + (cfg.d_model,), pre_ax + ("norm",), zero=True)
        pb.dense("norm2", pre + (cfg.d_model,), pre_ax + ("norm",), zero=True)
        ab = pb.child("attn")
        common.attn_init(ab, cfg, L)
        mb = pb.child("mlp")
        if cfg.is_moe:
            # deepseek-moe style: dense-prefix FFN matches total activated width
            d_ff = cfg.moe_d_ff * (cfg.num_shared_experts + cfg.experts_per_token)
        else:
            d_ff = cfg.d_ff
        common.mlp_init(mb, cfg.d_model, d_ff, L)

    def _layer_init(self, pb, cfg, L):
        if cfg.is_moe:
            pre = (L,)
            pre_ax = ("layers",)
            pb.dense("norm1", pre + (cfg.d_model,), pre_ax + ("norm",), zero=True)
            pb.dense("norm2", pre + (cfg.d_model,), pre_ax + ("norm",), zero=True)
            ab = pb.child("attn")
            common.attn_init(ab, cfg, L)
            eb = pb.child("moe")
            moe_mod.moe_init(eb, cfg, L)
        else:
            self._dense_layer_init(pb, cfg, L)

    # -- forward -----------------------------------------------------------

    def _block(self, x, p, *, positions, is_global, cache=None, cache_pos=None,
               is_moe=False):
        cfg, shd = self.cfg, self.shd
        h, new_cache = common.attention(
            common.rms_norm(x, p["norm1"]), p["attn"], cfg, shd,
            positions=positions, is_global=is_global,
            impl=self.attn_impl, q_block=self.q_block,
            kv_cache=cache, cache_pos=cache_pos, scores_f32=self.scores_f32)
        x = x + h
        y = common.rms_norm(x, p["norm2"])
        if is_moe:
            ff, aux = moe_mod.moe_apply(y, p["moe"], cfg, shd,
                                        impl=self.moe_impl)
        else:
            ff, aux = common.mlp(y, p["mlp"], shd), 0.0
        return x + ff, new_cache, aux

    def _run_stack(self, x, params, *, positions, caches=None, cache_pos=None,
                   true_len=None):
        """Run the layer stack.

        caches: None (training) | (k_all, v_all) stacked [L,B,T,kvh,dh]
        | {"global": (k,v), "local": (k,v)} for local:global window caches.
        Caches ride in the scan CARRY and are updated in place
        (dynamic-update-slice on the donated buffers) — a single cache copy
        lives in HBM, not the 2x of a scan-ys formulation.

        true_len: traced true prompt length for bucketed (right-padded)
        prefill — under causal masking the pad tail cannot change real
        positions, but the window *ring* caches must be built from the true
        last token, not the pad tail.
        """
        cfg = self.cfg
        flags = jnp.asarray(layer_flags(cfg))
        li0 = 0
        # unrolled dense prefix (deepseek-moe/moonshot first-k-dense)
        if cfg.first_k_dense:
            for i in range(cfg.first_k_dense):
                p = params["dense_prefix"][f"layer_{i}"]
                c = None if caches is None else (caches[0][li0], caches[1][li0])
                x, nc, _ = self._block(
                    x, p, positions=positions, is_global=flags[li0],
                    cache=c, cache_pos=cache_pos)
                if caches is not None:
                    caches = (caches[0].at[li0].set(nc[0]),
                              caches[1].at[li0].set(nc[1]))
                li0 += 1

        scan_flags = flags[li0:]
        lp = params["layers"]

        if caches is None:
            def body(carry, inp):
                xc, aux = carry
                if self.carry_barrier:
                    xc = lax.optimization_barrier(xc)
                p, flag = inp
                xc, _, a = self._block(xc, p, positions=positions,
                                       is_global=flag, is_moe=cfg.is_moe)
                return (xc, aux + a), None

            if self.remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux_s), _ = lax.scan(body, (x, 0.0), (lp, scan_flags))
            return x, None, aux_s

        if isinstance(caches, dict):
            return self._run_stack_windowed(x, params, positions=positions,
                                            caches=caches, cache_pos=cache_pos,
                                            scan_flags=scan_flags,
                                            true_len=true_len)

        def body(carry, inp):
            xc, aux, ck_all, cv_all, li = carry
            p, flag = inp
            ck = lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
            cv = lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
            xc, nc, a = self._block(xc, p, positions=positions, is_global=flag,
                                    cache=(ck, cv), cache_pos=cache_pos,
                                    is_moe=cfg.is_moe)
            ck_all = lax.dynamic_update_slice_in_dim(ck_all, nc[0][None], li, 0)
            cv_all = lax.dynamic_update_slice_in_dim(cv_all, nc[1][None], li, 0)
            return (xc, aux + a, ck_all, cv_all, li + 1), None

        (x, aux_s, new_k, new_v, _), _ = lax.scan(
            body, (x, 0.0, caches[0], caches[1], jnp.int32(li0)),
            (lp, scan_flags))
        return x, (new_k, new_v), aux_s

    # -- gemma3-style local:global window caches -----------------------------

    def _ring_gather(self, k, v, s, w):
        """Last-`W`-tokens ring from fresh K/V of length s: slot j holds the
        most recent token p with p ≡ j (mod W)."""
        j = jnp.arange(w)
        p = (s - 1) - ((s - 1 - j) % w)          # may be negative: unwritten
        pc = jnp.clip(p, 0)
        ring_k = jnp.take(k, pc, axis=1)
        ring_v = jnp.take(v, pc, axis=1)
        zero = (p < 0)[None, :, None, None]
        ring_k = jnp.where(zero, 0, ring_k)
        ring_v = jnp.where(zero, 0, ring_v)
        return ring_k, ring_v

    def window_size(self):
        return max(self.cfg.sliding_window, 1)

    def _run_stack_windowed(self, x, params, *, positions, caches, cache_pos,
                            scan_flags, true_len=None):
        """Scan with lax.cond per layer: global layers use the full-length
        cache stack, local layers a window-sized ring. Cuts KV memory by
        ~window/seq for the 5/6 local layers (gemma3: 32x at 32k)."""
        cfg, shd = self.cfg, self.shd
        gk, gv = caches["global"]
        w = caches["local"][0].shape[2]
        lk, lv = caches["local"]
        s = x.shape[1]

        def global_branch(xc, p, gk, gv, lk, lv, lig, lil):
            ck = lax.dynamic_index_in_dim(gk, lig, 0, keepdims=False)
            cv = lax.dynamic_index_in_dim(gv, lig, 0, keepdims=False)
            h, nc = common.attention(
                common.rms_norm(xc, p["norm1"]), p["attn"], cfg, shd,
                positions=positions, is_global=True, impl=self.attn_impl,
                q_block=self.q_block, kv_cache=(ck, cv), cache_pos=cache_pos)
            gk = lax.dynamic_update_slice_in_dim(gk, nc[0][None], lig, 0)
            gv = lax.dynamic_update_slice_in_dim(gv, nc[1][None], lig, 0)
            return xc + h, gk, gv, lk, lv, lig + 1, lil

        def local_branch(xc, p, gk, gv, lk, lv, lig, lil):
            y = common.rms_norm(xc, p["norm1"])
            if s == 1:
                slot = cache_pos % w
                j = jnp.arange(w)
                if jnp.ndim(cache_pos) == 0:
                    k_pos = cache_pos - ((cache_pos - j) % w)
                else:                                    # per-row positions
                    cp = cache_pos[:, None]
                    k_pos = cp - ((cp - j[None, :]) % w)  # [B, w]
                ck = lax.dynamic_index_in_dim(lk, lil, 0, keepdims=False)
                cv = lax.dynamic_index_in_dim(lv, lil, 0, keepdims=False)
                h, nc = common.attention(
                    y, p["attn"], cfg, shd, positions=positions,
                    is_global=False, impl=self.attn_impl,
                    q_block=self.q_block, kv_cache=(ck, cv),
                    cache_slot=slot, cache_pos=cache_pos,
                    k_positions=k_pos, k_valid=(k_pos >= 0))
                nk, nv = nc
            else:
                # prefill: windowed attention over the input, then build ring
                h, (fk, fv) = common.attention(
                    y, p["attn"], cfg, shd, positions=positions,
                    is_global=False, impl=self.attn_impl,
                    q_block=self.q_block, return_kv=True)
                nk, nv = self._ring_gather(
                    fk.astype(lk.dtype), fv.astype(lv.dtype),
                    s if true_len is None else true_len, w)
            lk = lax.dynamic_update_slice_in_dim(lk, nk[None], lil, 0)
            lv = lax.dynamic_update_slice_in_dim(lv, nv[None], lil, 0)
            return xc + h, gk, gv, lk, lv, lig, lil + 1

        def body(carry, inp):
            xc, gk, gv, lk, lv, lig, lil = carry
            p, flag = inp
            xc, gk, gv, lk, lv, lig, lil = lax.cond(
                flag, global_branch, local_branch,
                xc, p, gk, gv, lk, lv, lig, lil)
            y = common.rms_norm(xc, p["norm2"])
            xc = xc + common.mlp(y, p["mlp"], shd)
            return (xc, gk, gv, lk, lv, lig, lil), None

        init = (x, gk, gv, lk, lv, jnp.int32(0), jnp.int32(0))
        (x, gk, gv, lk, lv, _, _), _ = lax.scan(
            body, init, (params["layers"], scan_flags))
        return x, {"global": (gk, gv), "local": (lk, lv)}, 0.0

    def _inputs_to_h(self, batch, params):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = common.embed(batch["tokens"], params, dtype)
        if cfg.family == "vlm" and "patch_embeddings" in batch:
            pe = jnp.einsum("bsd,de->bse",
                            batch["patch_embeddings"].astype(dtype),
                            params["patch_proj"].astype(dtype))
            x = jnp.concatenate([pe, x], axis=1)
        return self.shd(x, "batch", "seq", "act_embed")

    def forward(self, params, batch):
        """Training/scoring forward: batch = {tokens [B,S], (patch_embeddings)}.

        Returns (logits [B,S',V], aux_loss).
        """
        x = self._inputs_to_h(batch, params)
        positions = jnp.arange(x.shape[1])
        x, _, aux = self._run_stack(x, params, positions=positions)
        logits = common.unembed(x, params, self.shd)
        return logits, aux

    def hidden(self, params, batch):
        """Final hidden states (pre-unembed) — used by the chunked
        cross-entropy path that never materializes full [B,S,V] logits."""
        x = self._inputs_to_h(batch, params)
        positions = jnp.arange(x.shape[1])
        x, _, aux = self._run_stack(x, params, positions=positions)
        return x, aux

    # -- serving -----------------------------------------------------------

    def init_cache(self, batch_size, max_seq, dtype=jnp.bfloat16):
        cfg = self.cfg
        flags = layer_flags(cfg)
        if cfg.local_global_period > 0:
            n_global = int(flags.sum())
            n_local = cfg.num_layers - n_global
            w = min(self.window_size(), max_seq)
            gshape = (n_global, batch_size, max_seq, cfg.num_kv_heads,
                      cfg.head_dim)
            lshape = (n_local, batch_size, w, cfg.num_kv_heads, cfg.head_dim)
            return {
                "global": (jnp.zeros(gshape, dtype), jnp.zeros(gshape, dtype)),
                "local": (jnp.zeros(lshape, dtype), jnp.zeros(lshape, dtype)),
            }
        shape = (cfg.num_layers, batch_size, max_seq, cfg.num_kv_heads,
                 cfg.head_dim)
        k = jnp.zeros(shape, dtype)
        return (k, jnp.zeros(shape, dtype))

    def cache_axes(self):
        ax = ("layers", "batch", "kv_seq", "act_kv_heads", None)
        if self.cfg.local_global_period > 0:
            # ring (window) caches are small: never worth seq-sharding
            axl = ("layers", "batch", None, "act_kv_heads", None)
            return {"global": (ax, ax), "local": (axl, axl)}
        return (ax, ax)

    def prefill(self, params, batch, caches, true_len=None, start_pos=None):
        """Prefill: writes KV caches at [start, start+S); returns
        (logits_last, caches).

        true_len: optional traced scalar for bucketed (right-padded)
        prompts — window ring caches are built from the true last token and
        the returned logits come from position ``true_len - 1`` instead of
        the pad tail. Cache positions >= true_len still hold pad KV; the
        serving steps zero them via ``common.mask_cache_tail``.

        start_pos: optional traced scalar offsetting the chunk (paged
        serving's prefix-cache tail prefill): token i of the batch sits at
        absolute position ``start_pos + i``, attends causally over the
        cache prefix [0, start_pos) already in ``caches`` plus itself, and
        ``true_len`` stays chunk-relative. Windowed ring caches can't
        resume a ring mid-stream, so chunked prefill is flat-cache only."""
        if start_pos is not None:
            assert not isinstance(caches, dict), (
                "chunked prefill (start_pos) is not supported for "
                "local:global window ring caches")
        x = self._inputs_to_h(batch, params)
        offset = jnp.int32(0) if start_pos is None else start_pos
        positions = jnp.arange(x.shape[1]) + offset
        x, caches, _ = self._run_stack(x, params, positions=positions,
                                       caches=caches, cache_pos=offset,
                                       true_len=true_len)
        if true_len is None:
            last = x[:, -1:]
        else:
            last = lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        logits = common.unembed(last, params, self.shd)
        return logits, caches

    def decode_step(self, params, token, pos, caches):
        """One decode step. token: [B,1] int32; pos: scalar int32 or [B]
        int32 (continuous batching: each batch row decodes at its own
        position; rows attend only to their own cache prefix)."""
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(token, params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        if jnp.ndim(pos) == 0:
            positions = jnp.array([0], jnp.int32) + pos
        else:
            positions = pos.astype(jnp.int32)[:, None]   # [B, 1]
        x, caches, _ = self._run_stack(x, params, positions=positions,
                                       caches=caches, cache_pos=pos)
        logits = common.unembed(x, params, self.shd)
        return logits, caches

    # -- paged serving -------------------------------------------------------

    def _paged_block(self, x, p, *, layer, pos, tables, pool, is_moe):
        cfg, shd = self.cfg, self.shd
        h, pool = common.paged_decode_attention(
            common.rms_norm(x, p["norm1"]), p["attn"], cfg, shd, pos=pos,
            layer=layer, tables=tables, pool=pool, scores_f32=self.scores_f32)
        x = x + h
        y = common.rms_norm(x, p["norm2"])
        if is_moe:
            ff, _ = moe_mod.moe_apply(y, p["moe"], cfg, shd,
                                      impl=self.moe_impl)
        else:
            ff = common.mlp(y, p["mlp"], shd)
        return x + ff, pool

    def paged_decode_step(self, params, token, pos, tables, pool):
        """One decode step straight on a paged KV pool.

        token: [B, 1] int32; pos: [B] int32 per-slot positions; tables:
        [B, NB] block ids; pool: (k, v) stacked [L, P, block, kvh, dh]. The
        layers carry the pool itself: each writes every slot's new K/V into
        its block in place and attends over the slot's live blocks
        (``common.paged_decode_attention``). Returns (logits, pool)."""
        cfg = self.cfg
        if self.shd.mesh is not None and self.shd.mesh.size > 1:
            raise NotImplementedError(
                "paged decode runs on one device: the pool is not sharded "
                "over KV heads")
        assert cfg.local_global_period <= 0 and cfg.sliding_window <= 0, (
            "paged decode attends every live position; windowed layers "
            "serve from ring caches")
        x = common.embed(token, params, jnp.dtype(cfg.dtype))
        x = self.shd(x, "batch", "seq", "act_embed")
        pos = pos.astype(jnp.int32)
        step = partial(self._paged_block, pos=pos, tables=tables)
        for i in range(cfg.first_k_dense):
            x, pool = step(x, params["dense_prefix"][f"layer_{i}"],
                           layer=jnp.int32(i), pool=pool, is_moe=False)

        def body(carry, p):
            xc, pool, li = carry
            xc, pool = step(xc, p, layer=li, pool=pool, is_moe=cfg.is_moe)
            return (xc, pool, li + 1), None

        (x, pool, _), _ = lax.scan(
            body, (x, tuple(pool), jnp.int32(cfg.first_k_dense)),
            params["layers"])
        logits = common.unembed(x, params, self.shd)
        return logits, pool
