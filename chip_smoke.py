"""Run the system's main path once on a TPU v5e and check what comes out.

    python3 chip_smoke.py               # one chip: serve granite-20b
    python3 chip_smoke.py --four-chips  # four chips: zamba2-1.2b train step

One chip: granite-20b at its published widths (d_model 6144, 48 query heads
over one KV head, head_dim 128, d_ff 24576, vocab 49152), cut to 3 of its
52 layers so that the f32 weights fit one chip's 16 GB, serves 8 requests
of about 1,000 prompt tokens and 64 new tokens each through
``launch.serve.serve``: the continuous engine with paged KV, bucketed
prefill and the prefix cache. Half of the prompts share a 512-token
prefix. The traffic is served twice: the first pass compiles, the second
is steady. A teacher-forced ``model.forward`` over each prompt and its
output then checks every served token whose reference top-two logit
margin exceeds ``MARGIN_TOL``.

Four chips: one train step of zamba2-1.2b at its published widths, cut to
one period of its pattern (5 Mamba2 layers and one site of the shared
attention block), on a (data=2, model=2) mesh and again on one chip; loss
and grad norm must agree.

Weights and data are random, drawn from fixed seeds. The script refuses to
run anywhere but on TPU v5e chips, and exits non-zero when any phase or
check fails. Its last line of output is one JSON object naming the device.
Every timing it prints is a smoke timing of one short run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# The TPU runtime and compiler log to TPU_LOG_DIR when it names a directory
# that exists, and under /tmp otherwise ("disabled" included).
if "TPU_LOG_DIR" not in os.environ:
    (ROOT / ".tpu_logs").mkdir(exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.hw import DEVICE_KINDS  # noqa: E402
from repro.core.tracing import counting_jit  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.serve.engine import Request  # noqa: E402
from repro.train.optimizer import OptConfig  # noqa: E402
from repro.train.step import StepConfig  # noqa: E402

# serving traffic: one chip's share of a decode deployment
SERVE_LAYERS = 3
BATCH, MAX_SEQ = 8, 2048
N_REQUESTS, MAX_NEW = 8, 64
PROMPT_LENS = (960, 1024)       # inclusive; all fall in the 1024 bucket
SHARED_PREFIX = 512             # even-numbered requests share it

# Served tokens come from bf16 steps (bucketed paged prefill, one-token
# decode against the paged cache); the reference is one bf16 forward over
# the whole sequence. The two sum in different orders, so a hidden value
# can round to a neighbouring bf16 number (8 mantissa bits: 2^-8 relative).
# Logits of these weights have unit scale, and one such flip in the final
# hidden state moves a logit by about 2^-8 (~0.004); a flip early in the
# stack grows through the layers. A top-two margin of 0.1 is 25 such flips:
# below it the two paths may pick either token, above it they must agree.
MARGIN_TOL = 0.1

# train step: tolerances of tests/test_distributed.py. Sharding over the
# model axis splits contractions into partial sums that are rounded to bf16
# and then reduced, so the sharded step rounds differently from one chip.
TRAIN_BATCH, TRAIN_SEQ, TRAIN_MICRO = 8, 2048, 2
LOSS_ATOL, GNORM_RTOL = 5e-3, 5e-2


class CompileClock:
    """Sums backend compile seconds (persistent-cache loads included) from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_v5e(n_chips):
    """The devices JAX found, when they are ``n_chips`` TPU v5e chips;
    otherwise exit non-zero, naming what was found."""
    devs = jax.devices()
    d = devs[0]
    found = f"platform={d.platform!r} kind={d.device_kind!r} count={len(devs)}"
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {found}")
    if d.device_kind not in DEVICE_KINDS:
        sys.exit(f"chip_smoke: needs a TPU v5e (device kinds with known "
                 f"peaks: {sorted(DEVICE_KINDS)}), found {found}")
    if len(devs) != n_chips:
        sys.exit(f"chip_smoke: this phase needs {n_chips} chip(s), "
                 f"found {found}")
    return devs


def make_prompts(vocab, n, lens, prefix_len, seed=0):
    """``n`` prompts with lengths drawn from ``lens`` (inclusive); the
    even-numbered ones start with one shared ``prefix_len``-token prefix."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len)
    prompts = []
    for i in range(n):
        p = rng.integers(0, vocab, int(rng.integers(lens[0], lens[1] + 1)))
        if i % 2 == 0:
            p[:prefix_len] = prefix
        prompts.append(p.astype(np.int32))
    return prompts


def forward_top2(model):
    """Jitted teacher-forced forward: per position, the argmax token and
    the margin between the two largest logits."""
    def f(params, tokens):
        logits, _ = model.forward(params, {"tokens": tokens})
        top = lax.top_k(logits[0].astype(jnp.float32), 2)[0]
        return jnp.argmax(logits[0], axis=-1), top[:, 0] - top[:, 1]
    return counting_jit(f, "forward")


def check_served_tokens(model, params, reqs, tol):
    """Teacher-force each prompt plus its output through ``model.forward``
    (all right-padded to one length: one compile, and causal attention
    keeps the pad from reaching real positions). Returns (checked,
    mismatched, near_ties, near_tie_mismatches, the largest reference
    margin at which a served token differs)."""
    fwd = forward_top2(model)
    n_pad = -(-max(len(r.prompt) + len(r.output) - 1 for r in reqs) // 64) * 64
    checked = mismatched = near = near_mismatched = 0
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
        toks = np.zeros((1, n_pad), np.int32)
        toks[0, :len(seq)] = seq
        top, margin = (np.asarray(a) for a in fwd(params, jnp.asarray(toks)))
        pos = len(r.prompt) - 1 + np.arange(len(r.output))
        agree = top[pos] == np.asarray(r.output)
        sure = margin[pos] > tol
        checked += int(sure.sum())
        mismatched += int((sure & ~agree).sum())
        near += int((~sure).sum())
        near_mismatched += int((~sure & ~agree).sum())
        worst = max(worst, float(margin[pos][~agree].max(initial=0.0)))
    return checked, mismatched, near, near_mismatched, worst


def serve_phase(cfg, *, batch, max_seq, max_new, prompts, tol, clock):
    """Serve ``prompts`` twice (cold, then steady with the prefix cache
    warm), check the cold pass's tokens against the forward and the steady
    pass's against the cold pass's, and print what was seen. Returns the
    list of failures."""
    failures = []
    print(f"config: {cfg.name} cut to num_layers={cfg.num_layers} "
          f"(published widths: d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads}kv head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}, params {cfg.param_dtype}, compute "
          f"{cfg.dtype})")
    print(f"traffic: {len(prompts)} requests, prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
          f"{max_new} new tokens each, batch={batch} max_seq={max_seq}")

    t0 = time.perf_counter()
    model, params, engine, reqs, stats = serve(
        cfg, prompts, batch=batch, max_seq=max_seq, max_new=max_new)
    cold_s = time.perf_counter() - t0
    compile_s, hits = clock.seconds, clock.cache_hits

    engine.reset_metrics()
    again = [Request(r.req_id, r.prompt, max_new_tokens=max_new)
             for r in reqs]
    t0 = time.perf_counter()
    steady = engine.serve(again)
    steady_s = time.perf_counter() - t0

    for r in reqs:
        print(f"  req {r.req_id}: prompt={len(r.prompt)} "
              f"cached={r.cached_prompt_tokens} out={len(r.output)} "
              f"finish={r.finish_reason or 'none'}")
        if not r.done or r.finish_reason not in ("length", "eos"):
            failures.append(f"request {r.req_id} finished "
                            f"{r.finish_reason or 'never'}")
    if stats["completed"] != len(prompts):
        failures.append(f"{stats['completed']} of {len(prompts)} completed")
    pc = stats.get("prefix_cache") or {}
    print(f"adapter={stats['adapter']} kv_block={stats['kv_block_size']} "
          f"prefix-cache hits={pc.get('hits')} "
          f"cached_tokens={pc.get('cached_tokens')} "
          f"prefill_tokens_computed={stats['prefill_tokens_computed']}")

    n_buckets = len(engine.buckets)
    prefill_compiles = engine.trace_stats.compiles("prefill")
    print(f"compiles: {engine.trace_stats.snapshot()} "
          f"(prefill buckets: {n_buckets})")
    if prefill_compiles > n_buckets:
        failures.append(f"{prefill_compiles} prefill compiles for "
                        f"{n_buckets} buckets")
    same = [a.output for a in again] == [r.output for r in reqs]
    print(f"steady pass: {steady['completed']} completed, outputs identical "
          f"to the cold pass: {same}")
    if steady["completed"] != len(prompts):
        failures.append(f"steady pass: {steady['completed']} of "
                        f"{len(prompts)} completed")
    if not same:
        failures.append("steady pass outputs differ from the cold pass")

    print(f"cold pass {cold_s:.2f} s (weights init + compiles), backend "
          f"compile {compile_s:.2f} s, {hits} persistent-cache hits")
    print(f"steady pass {steady_s:.2f} s: prefill {steady['prefill_s']:.3f} s, "
          f"decode {steady['decode_s']:.3f} s over "
          f"{steady['decode_steps']} steps")
    print(f"decode tokens/s (smoke timing, not a benchmark): "
          f"{steady['decode_tok_per_s']:.1f}")
    print(f"energy (modelled: ServePowerModel roofline x DVFS, not "
          f"measured): {steady['energy_j']:.1f} J")

    checked, bad, near, near_bad, worst = check_served_tokens(
        model, params, reqs, tol)
    print(f"check vs teacher-forced forward: {checked} positions with "
          f"top-two margin > {tol}, {bad} disagree; {near} near-ties "
          f"not checked ({near_bad} of them differ); largest margin at a "
          f"differing token {worst:.4f}")
    if checked == 0 or bad:
        failures.append(f"forward check: {bad} of {checked} disagree")
    return failures


def train_parity(cfg, *, batch, seq, micro, mesh):
    """One train step built by ``launch.train.build_trainer`` on ``mesh``
    and one on a single device, from the same weights and batch, with the
    optimiser ``launch.train --steps 1`` uses. Returns ((loss, gnorm)
    sharded, (loss, gnorm) one)."""
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    data = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    opt_cfg = OptConfig(lr=3e-3, warmup_steps=0, total_steps=1)

    def run(m):
        _, state, step = build_trainer(
            cfg, m, opt_cfg, StepConfig(num_microbatches=micro), seq=seq)
        _, metrics = step(state, data)
        return float(metrics["loss"]), float(metrics["grad_norm"])

    one = run(None)
    return run(mesh), one


def four_chip_phase():
    cfg = configs.get("zamba2-1.2b").replace(num_layers=6)
    print(f"config: {cfg.name} cut to num_layers=6: 5 Mamba2 layers + one "
          f"shared-attention site (published widths: d_model={cfg.d_model} "
          f"heads={cfg.num_heads} d_ff={cfg.d_ff} ssm_state={cfg.ssm_state} "
          f"vocab={cfg.vocab_size})")
    print(f"train step: batch={TRAIN_BATCH} seq={TRAIN_SEQ} "
          f"microbatches={TRAIN_MICRO}, mesh (data=2, model=2) vs one chip")
    t0 = time.perf_counter()
    (l4, g4), (l1, g1) = train_parity(
        cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, micro=TRAIN_MICRO,
        mesh=make_host_mesh(data=2, model=2))
    print(f"sharded: loss={l4!r} grad_norm={g4!r}")
    print(f"one chip: loss={l1!r} grad_norm={g1!r}")
    dl, dg = abs(l4 - l1), abs(g4 - g1) / max(g1, 1e-6)
    print(f"|dloss|={dl!r} (tol {LOSS_ATOL}), rel dgnorm={dg!r} "
          f"(tol {GNORM_RTOL}); {time.perf_counter() - t0:.1f} s incl. "
          f"compiles")
    failures = []
    if not (np.isfinite([l4, g4, l1, g1]).all()):
        failures.append("non-finite loss or grad norm")
    if dl > LOSS_ATOL or dg > GNORM_RTOL:
        failures.append("sharded and one-chip train steps disagree")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train-step phase, on four "
                         "chips")
    args = ap.parse_args(argv)
    devs = require_v5e(4 if args.four_chips else 1)
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        failures = four_chip_phase()
    else:
        cfg = configs.get("granite-20b").replace(num_layers=SERVE_LAYERS)
        prompts = make_prompts(cfg.vocab_size, N_REQUESTS, PROMPT_LENS,
                               SHARED_PREFIX)
        failures = serve_phase(cfg, batch=BATCH, max_seq=MAX_SEQ,
                               max_new=MAX_NEW, prompts=prompts,
                               tol=MARGIN_TOL, clock=CompileClock())
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB) on "
          f"{devs[0]}")
    if failures:
        sys.exit("chip_smoke FAILED: " + "; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
