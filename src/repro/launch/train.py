"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-20b --smoke \
        --steps 100 --batch 8 --seq 256

--smoke uses the reduced same-family config (CPU-runnable); on a TPU
deployment drop --smoke and set --mesh-data/--mesh-model to the pod shape.
Integrates checkpointing (atomic, resumable), ``repro.telemetry``
energy monitoring (J/token, per-tag attribution), and the energy-aware loop.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ShapeConfig
from repro.core.tracing import TraceStats, counting_jit
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.obs import MetricsRegistry, Tracer, write_chrome_trace
from repro.train import loop as loop_mod
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.step import (StepConfig, TrainState, make_train_step,
                              shardings, state_specs)


def build_trainer(cfg, mesh, opt_cfg, step_cfg, *, seq, trace_stats=None):
    """Build ``cfg``'s model on ``mesh`` (None: one device), draw its weights
    from ``key(0)``, and jit its train step with the state donated and, on
    a mesh, sharded by its logical axes. Returns ``(model, state,
    train_step)``; ``train_step(state, batch) -> (state, metrics)``."""
    model = build_model(cfg, mesh, q_block=min(512, seq))
    params, axes = model.init(jax.random.key(0))
    state = TrainState(params, init_opt_state(params))
    jit_kw = {}
    if mesh is not None:
        jit_kw["in_shardings"] = (
            shardings(mesh, state_specs(mesh, params, axes)), None)
    train_step = counting_jit(make_train_step(model, opt_cfg, step_cfg),
                              "train_step", trace_stats,
                              donate_argnums=(0,), **jit_kw)
    return model, state, train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--power-cap-w", type=float, default=None)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto/chrome-trace timeline JSON of "
                         "the run (train_step/checkpoint spans with "
                         "per-span attributed joules)")
    ap.add_argument("--metrics-json", default=None,
                    help="write the metrics-registry snapshot "
                         "(deterministic JSON)")
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = None
    if args.mesh_data * args.mesh_model > 1:
        mesh = make_host_mesh(data=args.mesh_data, model=args.mesh_model)

    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                        total_steps=args.steps)
    # counting_jit (not bare jax.jit): a training retrace burns the same
    # silent watts a serving retrace does — the stats land in the summary
    trace_stats = TraceStats()
    _, state, train_step = build_trainer(
        cfg, mesh, opt_cfg, StepConfig(num_microbatches=args.micro),
        seq=args.seq, trace_stats=trace_stats)

    data = SyntheticTokens(
        DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch), cfg)
    loop_cfg = loop_mod.LoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, power_cap_w=args.power_cap_w)

    def on_step(rec):
        if rec["step"] % 10 == 0 or rec["step"] == 1:
            print(f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                  f"gnorm {rec['grad_norm']:.3f} {rec['wall_s']*1e3:.0f}ms "
                  f"E={rec['energy_j']:.1f}J")

    tracer = Tracer() if args.trace_out else None
    registry = MetricsRegistry()
    state, history, summary = loop_mod.run(
        train_step, state, data, loop_cfg, on_step=on_step, tracer=tracer,
        metrics_registry=registry)
    session = summary.pop("session", None)   # live object, not JSON
    summary["train_step_compiles"] = trace_stats.compiles("train_step")
    print(f"final loss {history[-1]['loss']:.4f}  "
          f"J/token {summary['j_per_token']:.4f}  "
          f"avg {summary['avg_power_w']:.1f} W  "
          f"tags {list(summary['energy_by_tag'])}")
    if args.trace_out and tracer is not None:
        write_chrome_trace(args.trace_out, tracer, session=session,
                           meta={"process": "dalek-train",
                                 "arch": cfg.name, "steps": args.steps})
        print(f"timeline -> {args.trace_out}")
    if args.metrics_json:
        registry.write_json(args.metrics_json)
        print(f"metrics -> {args.metrics_json}")
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump({"history": history, "summary": summary}, f, default=float)
    return history


if __name__ == "__main__":
    enable_compile_cache()
    main()
