"""xLSTM (arXiv:2405.04517): mLSTM (matrix-memory, chunkwise-parallel) +
sLSTM (scalar-memory, time-recurrent) blocks.

Layer pattern: every ``cfg.slstm_every``-th block is sLSTM, the rest mLSTM
(7:1 for the assigned xlstm-1.3b). mLSTM layers are scanned in homogeneous
groups; sLSTM layers are unrolled between groups.

Numerics: gates computed in fp32; the input gate pre-activation is clamped
(soft capacity for the exponential gate) instead of carrying the xLSTM
paper's running-max stabilizer — the chunkwise and recurrent forms then agree
exactly, which the property tests assert.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import ParamBuilder
from repro.parallel.sharding import Sharder

I_CLAMP = 8.0  # clamp on input-gate pre-activation (exp gate)


def _ffn_width(d):  # llama-style 8/3 rounded to 64
    return int(np.ceil(8 * d / 3 / 64) * 64)


def mlstm_init(pb: ParamBuilder, cfg: ModelConfig, L: Optional[int]):
    d = cfg.d_model
    di = 2 * d                       # up-projection factor 2
    nh = cfg.num_heads
    pre = (L,) if L is not None else ()
    pax = ("layers",) if L is not None else ()
    pb.dense("norm", pre + (d,), pax + ("norm",), zero=True)
    pb.dense("w_up", pre + (d, 2 * di), pax + ("embed", "ssm_inner"), fan_in=d)
    pb.dense("conv", pre + (4, di), pax + ("conv_width", "ssm_inner"), fan_in=4)
    pb.dense("wq", pre + (di, di), pax + ("ssm_inner", None), fan_in=di)
    pb.dense("wk", pre + (di, di), pax + ("ssm_inner", None), fan_in=di)
    pb.dense("wv", pre + (di, di), pax + ("ssm_inner", None), fan_in=di)
    pb.dense("w_gates", pre + (di, 2 * nh), pax + ("ssm_inner", None), fan_in=di)
    pb.dense("b_gates", pre + (2 * nh,), pax + (None,), zero=True)
    pb.dense("out_norm", pre + (di,), pax + ("ssm_inner",), zero=True)
    pb.dense("w_down", pre + (di, d), pax + ("ssm_inner", "embed"), fan_in=di)


def _causal_conv(x, w, state=None):
    """x: [B,T,C], w: [W,C] depthwise. state: [B,W-1,C] carried for decode."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
              for i in range(width))
    new_state = xp[:, -(width - 1):]
    if state is not None:
        # Keep the carried-state dtype stable across steps: init_cache
        # allocates float32, and a drifting dtype changes the abstract
        # signature of the fused decode step, forcing a retrace.
        new_state = new_state.astype(state.dtype)
    return out, new_state


def _mlstm_gates(xi, p, nh):
    g = jnp.einsum("btc,ch->bth", xi, p["w_gates"].astype(xi.dtype))
    g = (g + p["b_gates"].astype(xi.dtype)).astype(jnp.float32)
    logi = jnp.minimum(g[..., :nh], I_CLAMP)            # [B,T,NH]
    logf = jax.nn.log_sigmoid(g[..., nh:])              # [B,T,NH] <= 0
    return logi, logf


def mlstm_chunkwise(q, k, v, logi, logf, state, chunk=256):
    """Chunkwise-parallel mLSTM. q,k,v: [B,T,NH,dh]; logi/logf: [B,T,NH].

    state: (C [B,NH,dh,dh], n [B,NH,dh]); returns (h, new_state).
    Sub-quadratic: O(T*chunk) intra + O(T/chunk) state passes.
    """
    b, t, nh, dh = q.shape
    w = min(chunk, t)
    assert t % w == 0, (t, w)
    nc = t // w
    scale = 1.0 / np.sqrt(dh)

    def reshape(x):
        return x.reshape(b, nc, w, *x.shape[2:]).swapaxes(0, 1)

    qs, ks, vs = reshape(q), reshape(k), reshape(v)       # [NC,B,W,NH,dh]
    lis, lfs = reshape(logi), reshape(logf)               # [NC,B,W,NH]

    def body(carry, inp):
        C, n = carry                                      # fp32
        qc, kc, vc, li, lf = inp
        qf = qc.astype(jnp.float32) * scale
        kf, vf = kc.astype(jnp.float32), vc.astype(jnp.float32)
        lc = jnp.cumsum(lf, axis=1)                       # [B,W,NH] inclusive
        ltot = lc[:, -1]                                  # [B,NH]
        # intra-chunk: decay matrix A[t,s] = exp(lc_t - lc_s + li_s), s<=t
        dm = lc[:, :, None, :] - lc[:, None, :, :] + li[:, None, :, :]
        mask = jnp.tril(jnp.ones((w, w), bool))
        # mask before exp: above the diagonal dm > 0 overflows to inf, and
        # where() sends that entry the gradient 0 * inf = NaN
        A = jnp.exp(jnp.where(mask[None, :, :, None], dm, -jnp.inf))
        scores = jnp.einsum("bthd,bshd->btsh", qf, kf) * A
        num_intra = jnp.einsum("btsh,bshd->bthd", scores, vf)
        den_intra = jnp.sum(scores, axis=2)               # [B,W,NH]
        # inter-chunk: carried state decayed to each position
        decay_t = jnp.exp(lc)                             # [B,W,NH]
        num_inter = jnp.einsum("bthd,bhde->bthe", qf, C) * decay_t[..., None]
        den_inter = jnp.einsum("bthd,bhd->bth", qf, n) * decay_t
        den = jnp.maximum(jnp.abs(den_intra + den_inter), 1.0)
        h = (num_intra + num_inter) / den[..., None]
        # state update: C' = exp(ltot) C + sum_s exp(ltot - lc_s + li_s) k v^T
        sdecay = jnp.exp(ltot[:, None] - lc + li)         # [B,W,NH]
        C = jnp.exp(ltot)[:, :, None, None] * C + jnp.einsum(
            "bshd,bshe,bsh->bhde", kf, vf, sdecay)
        n = jnp.exp(ltot)[..., None] * n + jnp.einsum("bshd,bsh->bhd", kf, sdecay)
        return (C, n), h

    (C, n), hs = lax.scan(body, state, (qs, ks, vs, lis, lfs))
    h = hs.swapaxes(0, 1).reshape(b, t, nh, dh)
    return h, (C, n)


def mlstm_step(q, k, v, logi, logf, state):
    """Single-token recurrence. q,k,v: [B,1,NH,dh]."""
    C, n = state
    dh = q.shape[-1]
    qf = q[:, 0].astype(jnp.float32) / np.sqrt(dh)        # [B,NH,dh]
    kf, vf = k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32)
    i_ = jnp.exp(logi[:, 0])                              # [B,NH]
    f_ = jnp.exp(logf[:, 0])
    C = f_[..., None, None] * C + i_[..., None, None] * (
        kf[..., :, None] * vf[..., None, :])
    n = f_[..., None] * n + i_[..., None] * kf
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)), 1.0)
    return (num / den[..., None])[:, None], (C, n)


def mlstm_block(x, p, cfg: ModelConfig, shd: Sharder, state, *, chunk=256):
    """state: (C, n, conv_state) or None (training, zero-init)."""
    b, t, d = x.shape
    di, nh = 2 * d, cfg.num_heads
    dh = di // nh
    y = common.rms_norm(x, p["norm"])
    up = jnp.einsum("btd,dc->btc", y, p["w_up"].astype(y.dtype))
    up = shd(up, "batch", "seq", "act_heads")
    xi, z = up[..., :di], up[..., di:]
    if state is None:
        conv_state = None
        C = jnp.zeros((b, nh, dh, dh), jnp.float32)
        n = jnp.zeros((b, nh, dh), jnp.float32)
    else:
        C, n, conv_state = state
    xc, new_conv = _causal_conv(xi, p["conv"], conv_state)
    xc = jax.nn.silu(xc)
    q = jnp.einsum("btc,ce->bte", xc, p["wq"].astype(xc.dtype)).reshape(b, t, nh, dh)
    k = jnp.einsum("btc,ce->bte", xc, p["wk"].astype(xc.dtype)).reshape(b, t, nh, dh)
    v = jnp.einsum("btc,ce->bte", xi, p["wv"].astype(xi.dtype)).reshape(b, t, nh, dh)
    logi, logf = _mlstm_gates(xc, p, nh)
    if t == 1 and state is not None:
        h, (C, n) = mlstm_step(q, k, v, logi, logf, (C, n))
    else:
        h, (C, n) = mlstm_chunkwise(q, k, v, logi, logf, (C, n),
                                    chunk=min(chunk, t))
    h = h.reshape(b, t, di).astype(x.dtype)
    h = common.rms_norm(h, p["out_norm"])
    h = h * jax.nn.silu(z)                                # output gate
    out = jnp.einsum("btc,cd->btd", h, p["w_down"].astype(h.dtype))
    out = shd(out, "batch", "seq", "act_embed")
    new_state = None if state is None else (C, n, new_conv)
    return x + out, new_state


# ---------------------------------------------------------------------------
# sLSTM


def slstm_init(pb: ParamBuilder, cfg: ModelConfig):
    d, nh = cfg.d_model, cfg.num_heads
    dh = d // nh
    pb.dense("norm", (d,), ("norm",), zero=True)
    pb.dense("w_in", (d, 4 * d), ("embed", "ssm_inner"), fan_in=d)
    pb.dense("r", (4, nh, dh, dh), (None, "heads", None, None), fan_in=dh)
    pb.dense("b", (4 * d,), (None,), zero=True)
    pb.dense("out_norm", (d,), ("norm",), zero=True)
    ff = _ffn_width(d)
    fb = pb.child("ffn")
    common.mlp_init(fb, d, ff)


def slstm_block(x, p, cfg: ModelConfig, shd: Sharder, state):
    """Time-recurrent sLSTM with exponential gating + stabilizer.

    state: (c, n, m, h) each [B, NH, dh] or None (zeros).
    """
    b, t, d = x.shape
    nh = cfg.num_heads
    dh = d // nh
    y = common.rms_norm(x, p["norm"])
    wx = jnp.einsum("btd,de->bte", y, p["w_in"].astype(y.dtype))
    wx = (wx + p["b"].astype(wx.dtype)).astype(jnp.float32)
    wx = wx.reshape(b, t, 4, nh, dh)
    r = p["r"].astype(jnp.float32)

    if state is None:
        zeros = jnp.zeros((b, nh, dh), jnp.float32)
        state = (zeros, zeros, zeros - 1e30, zeros)
        # m initialized very negative => first-step gates reduce correctly
        state = (zeros, zeros, jnp.full((b, nh, dh), -1e30), zeros)

    def step(carry, wx_t):
        c, n, m, h = carry
        rec = jnp.einsum("bhd,ghde->bghe", h, r)          # [B,4,NH,dh]
        pre = wx_t + rec
        zi, ii, fi, oi = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
        zi = jnp.tanh(zi)
        oi = jax.nn.sigmoid(oi)
        logi = jnp.minimum(ii, I_CLAMP)
        logf = jax.nn.log_sigmoid(fi)
        m_new = jnp.maximum(logf + m, logi)
        i_ = jnp.exp(logi - m_new)
        f_ = jnp.exp(logf + m - m_new)
        c = f_ * c + i_ * zi
        n = f_ * n + i_
        h_new = oi * c / jnp.maximum(jnp.abs(n), 1.0)
        return (c, n, m_new, h_new), h_new

    state, hs = lax.scan(step, state, wx.swapaxes(0, 1))
    h = hs.swapaxes(0, 1).reshape(b, t, d).astype(x.dtype)
    h = common.rms_norm(h, p["out_norm"])
    x = x + h
    x = x + common.mlp(common.rms_norm(x, p["norm"]), p["ffn"], shd)
    return x, state


# ---------------------------------------------------------------------------
# full model


class XLSTM:
    def __init__(self, cfg: ModelConfig, mesh=None, *, chunk=256, remat=True,
                 attn_impl=None, q_block=None,   # attn-free: accepted, unused
                 shd_rules=None, barrier=False):
        self.cfg = cfg
        self.shd = Sharder(mesh, rules=shd_rules, barrier=barrier)
        self.chunk = chunk
        self.remat = remat
        every = cfg.slstm_every or (cfg.num_layers + 1)
        self.slstm_idx = [i for i in range(cfg.num_layers)
                          if (i + 1) % every == 0]
        # groups of consecutive mLSTM layers between sLSTM layers
        self.groups = []
        start = 0
        for si in self.slstm_idx + [cfg.num_layers]:
            self.groups.append(si - start)  # mlstm count before this slstm
            start = si + 1
        self.n_mlstm = cfg.num_layers - len(self.slstm_idx)

    def init(self, key):
        cfg = self.cfg
        pb = ParamBuilder(key, jnp.dtype(cfg.param_dtype))
        common.embed_init(pb, cfg)
        mb = pb.child("mlstm")
        mlstm_init(mb, cfg, self.n_mlstm)
        for i in range(len(self.slstm_idx)):
            sb = pb.child(f"slstm_{i}")
            slstm_init(sb, cfg)
        return pb.build()

    def _stack(self, x, params, states):
        """states: dict or None. Returns (x, new_states)."""
        cfg, shd = self.cfg, self.shd
        new_states = {} if states is not None else None
        m_off = 0

        def mbody(carry, inp):
            xc = carry
            if states is None:
                p = inp
                st = None
            else:
                p, st = inp
            xc, nst = mlstm_block(xc, p, cfg, shd, st, chunk=self.chunk)
            return xc, nst

        if self.remat:
            mbody = jax.checkpoint(
                mbody, policy=jax.checkpoint_policies.nothing_saveable)

        for gi, g_count in enumerate(self.groups):
            if g_count:
                sl = lambda a: jax.tree.map(
                    lambda v: lax.dynamic_slice_in_dim(v, m_off, g_count, 0),
                    a)
                gp = sl(params["mlstm"])
                if states is None:
                    x, _ = lax.scan(mbody, x, gp)
                else:
                    gst = jax.tree.map(
                        lambda v: lax.dynamic_slice_in_dim(v, m_off, g_count, 0),
                        states["mlstm"])
                    x, nst = lax.scan(mbody, x, (gp, gst))
                    new_states.setdefault("_m", []).append(nst)
                m_off += g_count
            # pin the residual sharding at group boundaries: without this
            # GSPMD flips the carried-state sharding between group scans
            # (involuntary full rematerialization warnings)
            x = shd(x, "batch", "seq", "act_embed")
            if gi < len(self.slstm_idx):
                p = params[f"slstm_{gi}"]
                st = None if states is None else states[f"slstm_{gi}"]
                x, nst = slstm_block(x, p, cfg, shd, st)
                if states is not None:
                    new_states[f"slstm_{gi}"] = nst
        if states is not None:
            parts = new_states.pop("_m")
            new_states["mlstm"] = jax.tree.map(
                lambda *vs: jnp.concatenate(vs, axis=0), *parts)
        return x, new_states

    def forward(self, params, batch):
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(batch["tokens"], params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        x, _ = self._stack(x, params, None)
        return common.unembed(x, params, self.shd), 0.0

    # -- serving: state = recurrent state (O(1) in sequence length) ---------

    def init_cache(self, batch_size, max_seq, dtype=jnp.bfloat16):
        cfg = self.cfg
        d, nh = cfg.d_model, cfg.num_heads
        di = 2 * d
        dh = di // nh
        lm = self.n_mlstm
        st = {
            "mlstm": (
                jnp.zeros((lm, batch_size, nh, dh, dh), jnp.float32),
                jnp.zeros((lm, batch_size, nh, dh), jnp.float32),
                jnp.zeros((lm, batch_size, 3, di), jnp.float32),
            )
        }
        sdh = d // nh
        for i in range(len(self.slstm_idx)):
            zeros = jnp.zeros((batch_size, nh, sdh), jnp.float32)
            st[f"slstm_{i}"] = (zeros, zeros, jnp.full_like(zeros, -1e30), zeros)
        return st

    def cache_axes(self):
        st = {
            "mlstm": (
                ("layers", "batch", "act_heads", None, None),
                ("layers", "batch", "act_heads", None),
                ("layers", "batch", None, "ssm_inner"),
            )
        }
        for i in range(len(self.slstm_idx)):
            ax = ("batch", "act_heads", None)
            st[f"slstm_{i}"] = (ax, ax, ax, ax)
        return st

    def prefill(self, params, batch, states, start_pos=None):
        """Prefill a chunk; carried state in ``states`` resumes across
        chunks (mLSTM/sLSTM are position-free, so ``start_pos`` is
        accepted for the uniform chunked-prefill signature and ignored)."""
        del start_pos  # recurrent: position-free
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(batch["tokens"], params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        x, states = self._stack(x, params, states)
        return common.unembed(x[:, -1:], params, self.shd), states

    def decode_step(self, params, token, pos, states):
        del pos  # recurrent: position-free
        dtype = jnp.dtype(self.cfg.dtype)
        x = common.embed(token, params, dtype)
        x = self.shd(x, "batch", "seq", "act_embed")
        x, states = self._stack(x, params, states)
        return common.unembed(x, params, self.shd), states
