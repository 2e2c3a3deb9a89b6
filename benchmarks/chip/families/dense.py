"""The dense decoder family: what the harness needs to know of it.

A configuration file of this family holds the model's published
``config.json`` keys (Hugging Face names). This module maps them onto the
program's ``ModelConfig``, gives the weight layout that the benchmark makes
from the seed, and counts the FLOPs and bytes that the algorithm needs.
Its plain reference is ``reference/dense.py``.

The block is a pre-norm decoder: RMSNorm, attention with grouped KV heads
and rotary positions (and, where ``model_type`` is ``qwen3``, an RMSNorm
over each query and key head before the rotation), residual, RMSNorm,
SiLU-gated MLP, residual; final RMSNorm and an untied output head.
``program_config`` refuses a configuration that asks for anything else, so
that no configuration runs under a published name with a block that
differs from it.

Norm weights: the published RMSNorm multiplies by a weight ``w``; the
program stores ``w - 1`` and multiplies by ``1 + stored``. The benchmark's
tree holds the stored form, and the reference adds the one back.
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2
RMS_EPS = 1e-6          # the program's fixed RMSNorm epsilon


def dims(cfg: dict) -> tuple:
    """(layers, hidden, query heads, KV heads, head size, MLP width,
    vocabulary) of a configuration."""
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def qk_norm(cfg: dict) -> bool:
    return cfg["model_type"] == "qwen3"


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig`` that run ``cfg``
    as published; raises where the program cannot."""
    want = {"hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False, "rope_scaling": None,
            "rms_norm_eps": RMS_EPS}
    off = {k: (cfg.get(k), v) for k, v in want.items() if cfg.get(k) != v}
    if cfg.get("use_sliding_window") or off:
        raise ValueError(f"the program's dense decoder cannot run this "
                         f"configuration as published: {off}")
    L, d, h, kvh, dh, f, v = dims(cfg)
    return {"name": cfg["name"], "family": "dense", "num_layers": L,
            "d_model": d, "num_heads": h, "num_kv_heads": kvh,
            "head_dim": dh, "d_ff": f, "vocab_size": v,
            "qk_norm": qk_norm(cfg), "rope_theta": float(cfg["rope_theta"]),
            "dtype": cfg["compute_dtype"], "param_dtype": cfg["torch_dtype"],
            "source": cfg["source"]}


def layout(cfg: dict) -> dict:
    """{path: (shape, fan_in, or None for a norm)} of the weights, in the
    program's tree."""
    L, d, h, kvh, dh, f, v = dims(cfg)
    out = {
        "embedding": ((v, d), d),
        "unembed": ((d, v), d),
        "final_norm": ((d,), None),
        "layers/norm1": ((L, d), None),
        "layers/norm2": ((L, d), None),
        "layers/attn/wq": ((L, d, h, dh), d),
        "layers/attn/wk": ((L, d, kvh, dh), d),
        "layers/attn/wv": ((L, d, kvh, dh), d),
        "layers/attn/wo": ((L, h, dh, d), h * dh),
        "layers/mlp/w_gate": ((L, d, f), d),
        "layers/mlp/w_up": ((L, d, f), d),
        "layers/mlp/w_down": ((L, f, d), f),
    }
    if qk_norm(cfg):
        out["layers/attn/q_norm"] = ((L, dh), None)
        out["layers/attn/k_norm"] = ((L, dh), None)
    return out


# -- work counts -------------------------------------------------------------
#
# What the mathematics of a step requires, not what the program moves:
# weights once per step at bf16; the KV cache for the live tokens of each
# sequence only, at its own number of KV heads, never a gathered or
# head-repeated copy; a multiply-add is 2 FLOPs; norms, softmax and
# activations are left out (a fraction of a percent at these widths).


def matmul_params(cfg: dict) -> dict:
    """Parameters one token multiplies through: attention projections and
    the gated MLP of every layer, and the output head. The embedding is a
    lookup."""
    L, d, h, kvh, dh, f, v = dims(cfg)
    per_layer = d * h * dh * 2 + d * kvh * dh * 2 + 3 * d * f
    return {"layers": per_layer * L, "head": d * v}


def _kv_bytes_per_token(cfg: dict) -> int:
    L, _, _, kvh, dh, _, _ = dims(cfg)
    return L * 2 * kvh * dh * BF16


def _attn_flops(cfg: dict, n_pairs: int) -> float:
    """QK^T and PV over ``n_pairs`` (query, key) pairs, all layers."""
    L, _, h, _, dh, _, _ = dims(cfg)
    return 4.0 * h * dh * L * n_pairs


def decode_step(cfg: dict, contexts: Iterable[int]) -> tuple:
    """(FLOPs, bytes) of one decode step in which each active sequence adds
    one token attending over ``context`` positions (itself included).
    Bytes: every weight once in bf16, each sequence's live K/V, and its
    embedding row."""
    ctx = [int(c) for c in contexts]
    mp = matmul_params(cfg)
    n_params = mp["layers"] + mp["head"]
    flops = 2.0 * n_params * len(ctx) + _attn_flops(cfg, sum(ctx))
    nbytes = (n_params * BF16 + _kv_bytes_per_token(cfg) * sum(ctx)
              + cfg["hidden_size"] * BF16 * len(ctx))
    return flops, float(nbytes)


def prefill(cfg: dict, start: int, n: int) -> tuple:
    """(FLOPs, bytes) of prefilling ``n`` prompt tokens at positions
    [start, start + n) with a causal mask over the cached prefix; only the
    last position's logits are needed (the head runs once)."""
    mp = matmul_params(cfg)
    # token i (0-based in the chunk) attends over start + i + 1 keys
    pairs = n * start + n * (n + 1) // 2
    flops = (2.0 * mp["layers"] * n + 2.0 * mp["head"]
             + _attn_flops(cfg, pairs))
    nbytes = ((mp["layers"] + mp["head"]) * BF16
              + _kv_bytes_per_token(cfg) * (start + n))
    return flops, float(nbytes)
