"""The plain reference follows the published model: at a small size on the
CPU, ``reference/dense.py`` on a Qwen3 configuration gives the logits of
the Hugging Face ``Qwen3ForCausalLM`` (float32, eager attention) on the
same weights.

    python -m pytest benchmarks/chip/tests
"""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402

bench._env(chip=False)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from reference import dense  # noqa: E402

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


def _tree(model, cfg):
    """The benchmark's weight tree (the program's layout, norms stored as
    ``weight - 1``) holding the Hugging Face model's weights."""
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    d, h, kvh, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        g = lambda n: sd[f"model.layers.{i}.{n}.weight"]
        layers.append({
            "norm1": g("input_layernorm") - 1, "norm2": g(
                "post_attention_layernorm") - 1,
            "attn": {"wq": g("self_attn.q_proj").T.reshape(d, h, dh),
                     "wk": g("self_attn.k_proj").T.reshape(d, kvh, dh),
                     "wv": g("self_attn.v_proj").T.reshape(d, kvh, dh),
                     "wo": g("self_attn.o_proj").T.reshape(h, dh, d),
                     "q_norm": g("self_attn.q_norm") - 1,
                     "k_norm": g("self_attn.k_norm") - 1},
            "mlp": {"w_gate": g("mlp.gate_proj").T, "w_up": g("mlp.up_proj").T,
                    "w_down": g("mlp.down_proj").T}})
    stacked = jax.tree.map(lambda *a: jnp.asarray(np.stack(a)), *layers)
    return {"embedding": jnp.asarray(sd["model.embed_tokens.weight"]),
            "unembed": jnp.asarray(sd["lm_head.weight"].T),
            "final_norm": jnp.asarray(sd["model.norm.weight"] - 1),
            "layers": stacked}


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_matches_published_qwen3(seed):
    cfg = dict(json.loads(
        (HERE / "configs" / "qwen3-32b-l4.json").read_text()))
    cfg.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, intermediate_size=512,
               vocab_size=1000)
    hf_keys = {k: v for k, v in cfg.items() if k not in (
        "name", "family", "source", "reduced", "assumed", "deployment",
        "rehearsal", "engine", "compute_dtype", "architectures",
        "torch_dtype")}
    torch.manual_seed(seed)
    hf_cfg = transformers.Qwen3Config(**hf_keys, torch_dtype="float32",
                                      attn_implementation="eager")
    model = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    with torch.no_grad():                 # norms away from 1, as the seed's
        for n, p in model.named_parameters():
            if n.endswith("norm.weight"):
                p.add_(0.1 * torch.randn_like(p))
    tokens = np.random.default_rng(seed).integers(0, 1000, 37)
    with torch.no_grad():
        want = model(torch.as_tensor(tokens[None])).logits[0].numpy()

    params = _tree(model, cfg)
    h = dense.hidden(params, jnp.asarray(tokens, jnp.int32), cfg)
    got = np.asarray(jnp.einsum("sd,dv->sv", h, params["unembed"],
                                precision="highest"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    ids = jnp.asarray(want.argmin(-1), jnp.int32)
    best, at, arg = dense.head(h, params, ids)
    np.testing.assert_allclose(best, want.max(-1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(at, want.min(-1), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(arg, want.argmax(-1))
