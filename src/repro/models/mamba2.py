"""Mamba2 (SSD) blocks + Zamba2 hybrid (Mamba2 backbone with a *shared*
attention block applied every ``cfg.attn_every`` layers, distinct KV cache per
application site) [arXiv:2411.15242].

The SSD scan uses the chunkwise-parallel algorithm (intra-chunk masked
matmuls + inter-chunk recurrent state passing) — sub-quadratic, and the
single-step recurrence used for decode agrees exactly (property-tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import ParamBuilder
from repro.models.xlstm import _causal_conv
from repro.parallel.sharding import Sharder


def mamba_init(pb: ParamBuilder, cfg: ModelConfig, L):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    nh = di // cfg.head_dim
    pre, pax = (L,), ("layers",)
    proj_out = 2 * di + 2 * n + nh
    pb.dense("norm", pre + (d,), pax + ("norm",), zero=True)
    pb.dense("w_in", pre + (d, proj_out), pax + ("embed", "ssm_inner"), fan_in=d)
    pb.dense("conv", pre + (cfg.ssm_conv_width, di + 2 * n),
             pax + ("conv_width", "ssm_inner"), fan_in=cfg.ssm_conv_width)
    pb.dense("a_log", pre + (nh,), pax + (None,), zero=True)
    pb.dense("d_skip", pre + (nh,), pax + (None,), one=True)
    pb.dense("dt_bias", pre + (nh,), pax + (None,), zero=True)
    pb.dense("out_norm", pre + (di,), pax + ("ssm_inner",), zero=True)
    pb.dense("w_out", pre + (di, d), pax + ("ssm_inner", "embed"), fan_in=di)


def ssd_chunkwise(x, b_mat, c_mat, dt, a, state, chunk=256):
    """Chunkwise SSD. x: [B,T,H,P]; b_mat/c_mat: [B,T,N]; dt: [B,T,H] (>0);
    a: [H] (<0). state: [B,H,N,P] carried. Returns (y, new_state)."""
    bs, t, nh, p = x.shape
    n = b_mat.shape[-1]
    w = min(chunk, t)
    assert t % w == 0
    nc = t // w

    def rs(v):
        return v.reshape(bs, nc, w, *v.shape[2:]).swapaxes(0, 1)

    xs, bs_, cs, dts = rs(x), rs(b_mat), rs(c_mat), rs(dt)

    def body(carry, inp):
        S = carry                                          # [B,H,N,P] fp32
        xc, bc, cc, dtc = inp
        xf = xc.astype(jnp.float32)
        bf, cf = bc.astype(jnp.float32), cc.astype(jnp.float32)
        logf = dtc * a                                     # [B,W,H] <= 0
        lc = jnp.cumsum(logf, axis=1)
        ltot = lc[:, -1]                                   # [B,H]
        # intra-chunk
        dm = lc[:, :, None, :] - lc[:, None, :, :]         # [B,W,W,H]
        mask = jnp.tril(jnp.ones((w, w), bool))
        # mask before exp: above the diagonal dm > 0 overflows to inf, and
        # where() sends that entry the gradient 0 * inf = NaN
        A = jnp.exp(jnp.where(mask[None, :, :, None], dm, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", cf, bf)            # [B,W,W]
        scores = cb[..., None] * A * dtc[:, None, :, :]    # [B,W,W,H]
        y_intra = jnp.einsum("btsh,bshp->bthp", scores, xf)
        # inter-chunk
        y_inter = jnp.einsum("btn,bhnp->bthp", cf, S) * jnp.exp(lc)[..., None]
        # state update
        sdecay = jnp.exp(ltot[:, None] - lc) * dtc         # [B,W,H]
        S = jnp.exp(ltot)[..., None, None] * S + jnp.einsum(
            "bsn,bshp,bsh->bhnp", bf, xf, sdecay)
        return S, y_intra + y_inter

    S, ys = lax.scan(body, state, (xs, bs_, cs, dts))
    y = ys.swapaxes(0, 1).reshape(bs, t, nh, p)
    return y, S


def ssd_step(x, b_mat, c_mat, dt, a, state):
    """Single-step recurrence. x: [B,1,H,P]; b/c: [B,1,N]; dt: [B,1,H]."""
    S = state
    xf = x[:, 0].astype(jnp.float32)                       # [B,H,P]
    bf, cf = b_mat[:, 0].astype(jnp.float32), c_mat[:, 0].astype(jnp.float32)
    dtc = dt[:, 0]                                         # [B,H]
    decay = jnp.exp(dtc * a)                               # [B,H]
    S = decay[..., None, None] * S + jnp.einsum(
        "bn,bhp,bh->bhnp", bf, xf, dtc)
    y = jnp.einsum("bn,bhnp->bhp", cf, S)
    return y[:, None], S


def mamba_block(x, p, cfg: ModelConfig, shd: Sharder, state, *, chunk=256):
    """state: (S [B,H,N,P], conv_state) or None."""
    b, t, d = x.shape
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    nh = di // cfg.head_dim
    pdim = cfg.head_dim
    y = common.rms_norm(x, p["norm"])
    proj = jnp.einsum("btd,de->bte", y, p["w_in"].astype(y.dtype))
    proj = shd(proj, "batch", "seq", "act_heads")
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * n]
    dt_raw = proj[..., di + di + 2 * n:]                   # [B,T,H]
    if state is None:
        S = jnp.zeros((b, nh, n, pdim), jnp.float32)
        conv_state = None
    else:
        S, conv_state = state
    xbc, new_conv = _causal_conv(xbc, p["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xssm = xbc[..., :di].reshape(b, t, nh, pdim)
    b_mat = xbc[..., di:di + n]
    c_mat = xbc[..., di + n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))           # [H] < 0
    if t == 1 and state is not None:
        ys, S = ssd_step(xssm, b_mat, c_mat, dt, a, S)
    else:
        ys, S = ssd_chunkwise(xssm, b_mat, c_mat, dt, a, S,
                              chunk=min(chunk, t))
    ys = ys + p["d_skip"].astype(jnp.float32)[:, None] * xssm.astype(jnp.float32)
    h = ys.reshape(b, t, di).astype(x.dtype)
    h = common.rms_norm(h, p["out_norm"])
    h = h * jax.nn.silu(z)
    out = jnp.einsum("btc,cd->btd", h, p["w_out"].astype(h.dtype))
    out = shd(out, "batch", "seq", "act_embed")
    new_state = None if state is None else (S, new_conv)
    return x + out, new_state


class Zamba2:
    """Mamba2 stack with one shared attention+MLP block every ``attn_every``
    layers. KV caches are sequence-sharded for long-context decode (SP)."""

    def __init__(self, cfg: ModelConfig, mesh=None, *, chunk=256, remat=True,
                 attn_impl="blocked", q_block=512, shd_rules=None,
                 barrier=False):
        self.cfg = cfg
        self.shd = Sharder(mesh, rules=shd_rules, barrier=barrier)
        self.chunk = chunk
        self.remat = remat
        self.attn_impl = attn_impl
        self.q_block = q_block
        every = cfg.attn_every or (cfg.num_layers + 1)
        self.attn_sites = [i for i in range(cfg.num_layers)
                           if (i + 1) % every == 0]
        self.groups = []
        start = 0
        for si in self.attn_sites + [cfg.num_layers]:
            self.groups.append(si - start)
            start = si + 1
        self.n_mamba = cfg.num_layers - len(self.attn_sites)

    def init(self, key):
        cfg = self.cfg
        pb = ParamBuilder(key, jnp.dtype(cfg.param_dtype))
        common.embed_init(pb, cfg)
        mb = pb.child("mamba")
        mamba_init(mb, cfg, self.n_mamba)
        sb = pb.child("shared_attn")      # ONE block, shared across sites
        sb.dense("norm1", (cfg.d_model,), ("norm",), zero=True)
        sb.dense("norm2", (cfg.d_model,), ("norm",), zero=True)
        ab = sb.child("attn")
        common.attn_init(ab, cfg)
        fb = sb.child("mlp")
        common.mlp_init(fb, cfg.d_model, cfg.d_ff)
        return pb.build()

    def _shared_attn(self, x, p, positions, cache, cache_pos):
        cfg, shd = self.cfg, self.shd
        h, nc = common.attention(
            common.rms_norm(x, p["norm1"]), p["attn"], cfg, shd,
            positions=positions, impl=self.attn_impl, q_block=self.q_block,
            kv_cache=cache, cache_pos=cache_pos)
        x = x + h
        x = x + common.mlp(common.rms_norm(x, p["norm2"]), p["mlp"], shd)
        return x, nc

    def _stack(self, x, params, states, *, positions, cache_pos=None):
        cfg, shd = self.cfg, self.shd
        new_states = {} if states is not None else None
        m_off = 0

        def mbody(carry, inp):
            xc = carry
            if states is None:
                p, st = inp, None
            else:
                p, st = inp
            xc, nst = mamba_block(xc, p, cfg, shd, st, chunk=self.chunk)
            return xc, nst

        if self.remat:
            mbody = jax.checkpoint(
                mbody, policy=jax.checkpoint_policies.nothing_saveable)

        for gi, g_count in enumerate(self.groups):
            if g_count:
                gp = jax.tree.map(
                    lambda v: lax.dynamic_slice_in_dim(v, m_off, g_count, 0),
                    params["mamba"])
                if states is None:
                    x, _ = lax.scan(mbody, x, gp)
                else:
                    gst = jax.tree.map(
                        lambda v: lax.dynamic_slice_in_dim(v, m_off, g_count, 0),
                        states["mamba"])
                    x, nst = lax.scan(mbody, x, (gp, gst))
                    new_states.setdefault("_m", []).append(nst)
                m_off += g_count
            if gi < len(self.attn_sites):
                cache = None if states is None else states[f"attn_{gi}"]
                x, nc = self._shared_attn(x, params["shared_attn"], positions,
                                          cache, cache_pos)
                if states is not None:
                    new_states[f"attn_{gi}"] = nc
        if states is not None:
            parts = new_states.pop("_m")
            new_states["mamba"] = jax.tree.map(
                lambda *vs: jnp.concatenate(vs, axis=0), *parts)
        return x, new_states

    def forward(self, params, batch):
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(batch["tokens"], params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        positions = jnp.arange(x.shape[1])
        x, _ = self._stack(x, params, None, positions=positions)
        return common.unembed(x, params, self.shd), 0.0

    def init_cache(self, batch_size, max_seq, dtype=jnp.bfloat16):
        cfg = self.cfg
        di = cfg.ssm_expand * cfg.d_model
        n = cfg.ssm_state
        nh = di // cfg.head_dim
        cw = cfg.ssm_conv_width
        lm = self.n_mamba
        st = {
            "mamba": (
                jnp.zeros((lm, batch_size, nh, n, cfg.head_dim), jnp.float32),
                jnp.zeros((lm, batch_size, cw - 1, di + 2 * n), jnp.float32),
            )
        }
        for i in range(len(self.attn_sites)):
            shape = (batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim)
            st[f"attn_{i}"] = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        return st

    def cache_axes(self):
        st = {
            "mamba": (
                ("layers", "batch", "act_heads", None, None),
                ("layers", "batch", None, "ssm_inner"),
            )
        }
        for i in range(len(self.attn_sites)):
            ax = ("batch", "kv_seq", "act_kv_heads", None)
            st[f"attn_{i}"] = (ax, ax)
        return st

    def prefill(self, params, batch, states, start_pos=None):
        """Prefill a chunk at absolute positions [start, start+S).

        Mamba/conv state in ``states`` carries left-to-right across chunks
        (the conv left-pad and SSD state resume by construction);
        ``start_pos`` offsets the shared-attention KV writes and RoPE so a
        prompt can be fed in pow2 chunks without retracing per length."""
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(batch["tokens"], params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        offset = jnp.int32(0) if start_pos is None else start_pos
        positions = jnp.arange(x.shape[1]) + offset
        x, states = self._stack(x, params, states, positions=positions,
                                cache_pos=offset)
        return common.unembed(x[:, -1:], params, self.shd), states

    def decode_step(self, params, token, pos, states):
        """One decode step. pos: scalar int32 or [B] int32 (continuous
        batching: each row decodes at its own attention position)."""
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(token, params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        if jnp.ndim(pos) == 0:
            positions = jnp.array([0], jnp.int32) + pos
        else:
            positions = pos.astype(jnp.int32)[:, None]   # [B, 1]
        x, states = self._stack(x, params, states, positions=positions,
                                cache_pos=pos)
        return common.unembed(x, params, self.shd), states
