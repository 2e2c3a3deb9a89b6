"""Plain float32 reference of the dense decoder family
(``families/dense.py``), written from the published description of the
configuration (for Qwen3: the Qwen3 technical report, arXiv:2505.09388, and
the model's ``config.json``), importing nothing of the program.

Token embedding (untied from the head); per layer: RMSNorm
(``x * rsqrt(mean(x^2) + eps) * w``), q/k/v projections, for ``qwen3`` an
RMSNorm over each query and key head, rotary positions on q and k
(half-split rotation, frequencies ``theta^(-i / (head_dim / 2))``), causal
attention of every query head over its group's KV head, output projection,
residual; RMSNorm, SiLU-gated MLP, residual; final RMSNorm; output head.
No biases. Every matrix product runs at ``precision=highest``: on a TPU,
float32 products are otherwise rounded to bf16.

Norm weights arrive in the stored form ``w - 1`` (see
``families/dense.py``) and are used as ``w``.

``int8=True`` is the control: every projection, MLP and head product takes
its weights quantised to int8 per output channel and its activations
quantised to int8 per token (symmetric), the step below the configuration's
bf16 compute that a later change could be tempted to take.

The head is computed only for the rows asked for, in slices of the
vocabulary, so that a vocabulary of 152k entries never needs a float32
copy of the whole head or of all the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512
VOCAB_SLICE = 32768     # the head's columns per step, at most


def _q8(x, axis):
    """Symmetric int8 fake-quantisation of ``x`` along ``axis`` (exact in
    float32: the int8 values times one float32 scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(spec, x, w, *, int8):
    """einsum at float32/highest; with ``int8`` the activation is quantised
    over its last axis and the weight over its first (contraction) axis."""
    if int8:
        x = _q8(x, -1)
        w = _q8(w, 0)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, stored, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + stored)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freq                    # [S, half]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _attention(q, k, v):
    """Causal attention, query blocks of ``Q_BLOCK`` rows. q: [S, H, dh];
    k, v: [S, KVH, dh] with H a multiple of KVH (query head i uses KV head
    i // (H / KVH))."""
    s_len, h, dh = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qg = q.reshape(s_len, kvh, g, dh)
    outs = []
    for b0 in range(0, s_len, Q_BLOCK):
        qb = qg[b0:b0 + Q_BLOCK]
        sc = jnp.einsum("qcgd,kcd->cgqk", qb, k, precision=HI) / np.sqrt(dh)
        qi = b0 + jnp.arange(qb.shape[0])[:, None]
        ki = jnp.arange(s_len)[None, :]
        sc = jnp.where(ki <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("cgqk,kcd->qcgd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=0).reshape(s_len, h, dh)


def hidden(params, tokens, cfg, int8=False):
    """Final normed hidden states [S, D] of one sequence ``tokens`` [S]."""
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    qk_norm = cfg["model_type"] == "qwen3"
    x = jnp.take(params["embedding"], tokens, axis=0).astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        at = p["attn"]
        a = _rms(x, p["norm1"], eps)
        q = _mm("sd,dhk->shk", a, at["wq"], int8=int8)
        k = _mm("sd,dhk->shk", a, at["wk"], int8=int8)
        v = _mm("sd,dhk->shk", a, at["wv"], int8=int8)
        if qk_norm:
            q, k = _rms(q, at["q_norm"], eps), _rms(k, at["k_norm"], eps)
        o = _attention(_rope(q, pos, theta), _rope(k, pos, theta), v)
        o = o.reshape(o.shape[0], -1)
        wo = at["wo"].reshape(-1, at["wo"].shape[-1])
        x = x + _mm("sf,fd->sd", o, wo, int8=int8)
        m = _rms(x, p["norm2"], eps)
        gate = _mm("sd,df->sf", m, p["mlp"]["w_gate"], int8=int8)
        up = _mm("sd,df->sf", m, p["mlp"]["w_up"], int8=int8)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["mlp"]["w_down"],
                    int8=int8)
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"].astype(F32), eps)


def _slice_width(v: int) -> int:
    """The widest divisor of ``v`` that is at most ``VOCAB_SLICE``."""
    n = next(n for n in range(1, v + 1)
             if v % n == 0 and v // n <= VOCAB_SLICE)
    return v // n


def head(h, params, ids, int8=False):
    """Over the rows of ``h`` [R, D]: each row's best logit, the logit of
    ``ids`` [R], and the id of the best (the first, on a tie)."""
    w = params["unembed"]
    v = w.shape[1]
    c = _slice_width(v)
    if int8:
        h = _q8(h, -1)
    r = h.shape[0]

    def body(carry, j):
        best, at, arg = carry
        wj = lax.dynamic_slice_in_dim(w, j * c, c, axis=1).astype(F32)
        if int8:
            wj = _q8(wj, 0)
        lg = jnp.einsum("rd,dc->rc", h, wj, precision=HI)
        m = lg.max(axis=-1)
        local = ids - j * c
        inside = (local >= 0) & (local < c)
        got = jnp.take_along_axis(lg, jnp.clip(local, 0, c - 1)[:, None],
                                  axis=-1)[:, 0]
        at = jnp.where(inside, got, at)
        arg = jnp.where(m > best, jnp.argmax(lg, axis=-1) + j * c, arg)
        return (jnp.maximum(best, m), at, arg), None

    init = (jnp.full((r,), -jnp.inf, F32), jnp.zeros((r,), F32),
            jnp.zeros((r,), jnp.int32))
    (best, at, arg), _ = lax.scan(body, init, jnp.arange(v // c))
    return best, at, arg
