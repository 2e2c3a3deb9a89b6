"""Serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b --smoke \
        --requests 6 --max-new 16 --engine continuous --power-cap 150

Serves synthetic prompts through either engine — ``static`` (padded batch,
lock-step decode) or ``continuous`` (request queue, slot recycling,
energy-aware admission) — with per-request energy attribution from the
``repro.telemetry`` tag bus and a typed ``EnergyReport`` summary.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.registry import serving_caps
from repro.obs import write_chrome_trace
from repro.serve.engine import ContinuousEngine, Request, ServeEngine


def serve(cfg, prompts, *, batch, max_seq, max_new, engine="continuous",
          power_cap=None, prefill_buckets="auto", kv_block_size="auto",
          prefix_cache=True):
    """Serve ``prompts`` (1-D token arrays) on ``cfg``'s model.

    Builds the model, draws its weights from ``key(0)``, wraps each prompt
    in a ``Request`` of ``max_new`` tokens (audio requests get synthetic
    encoder frames), and drains them through the ``static`` or
    ``continuous`` engine. Returns ``(model, params, engine, reqs, stats)``.
    """
    caps = serving_caps(cfg)
    model = build_model(cfg, q_block=min(64, max(len(p) for p in prompts)))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    reqs = []
    for i, prompt in enumerate(prompts):
        # synthetic encoder frames stand in for a log-mel front-end
        frames = (rng.standard_normal((cfg.enc_seq, cfg.d_model))
                  .astype(np.float32) if caps.needs_frames else None)
        reqs.append(Request(i, np.asarray(prompt, np.int32),
                            max_new_tokens=max_new, frames=frames))

    if engine == "static":
        eng = ServeEngine(model, params, batch_size=batch, max_seq=max_seq,
                          prefill_buckets=prefill_buckets)
        stats = {}
        for i in range(0, len(reqs), batch):
            group = eng.serve(reqs[i:i + batch])
            for k, v in group.items():
                # compile counts are engine-lifetime cumulative, not per-call
                if isinstance(v, (int, float)) and not k.endswith("_compiles"):
                    stats[k] = stats.get(k, 0.0) + v
        stats["decode_tok_per_s"] = (stats["tokens_decoded"] /
                                     stats["decode_s"] if stats.get("decode_s")
                                     else 0.0)
        stats["energy_by_tag"] = dict(eng.tel.session.report().by_tag)
        stats["prefill_compiles"] = eng.trace_stats.compiles("prefill")
        stats["decode_compiles"] = eng.trace_stats.compiles("decode")
    else:
        eng = ContinuousEngine(model, params, batch_size=batch,
                               max_seq=max_seq, power_cap_w=power_cap,
                               prefill_buckets=prefill_buckets,
                               kv_block_size=kv_block_size,
                               prefix_cache=prefix_cache)
        stats = eng.serve(reqs)
    return model, params, eng, reqs, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=["static", "continuous"],
                    default="continuous")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--power-cap", type=float, default=None,
                    help="node power cap in W (continuous engine only)")
    ap.add_argument("--prefill-buckets", default="auto",
                    help="prompt-length bucketing: 'auto' (power-of-two "
                         "edges, bounded prefill compiles), 'off' (exact "
                         "lengths, one executable per distinct length), or "
                         "explicit comma-separated edges like '8,16,32'")
    ap.add_argument("--kv-block-size", default="auto",
                    help="paged KV cache block size (continuous engine): "
                         "'auto' (largest power-of-two <= 32 dividing "
                         "max-seq; falls back to contiguous for model "
                         "families that cannot page), 'off' (contiguous "
                         "per-slot cache), or an explicit size dividing "
                         "max-seq")
    ap.add_argument("--prefix-cache", default="auto",
                    choices=["auto", "on", "off"],
                    help="radix prefix cache over prompt blocks (requires "
                         "paged KV): shared prompt prefixes prefill once; "
                         "'auto' enables it exactly when the model family "
                         "supports paged KV")
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto/chrome-trace timeline JSON: "
                         "request-lifecycle + engine-step spans with "
                         "per-span attributed joules")
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine metrics-registry snapshot "
                         "(deterministic JSON)")
    args = ap.parse_args(argv)
    buckets = (args.prefill_buckets
               if args.prefill_buckets in ("auto", "off")
               else [int(b) for b in args.prefill_buckets.split(",")])
    kv_block = (args.kv_block_size if args.kv_block_size in ("auto", "off")
                else int(args.kv_block_size))

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    caps = serving_caps(cfg)
    # Fail fast on flag/family combinations the engine would reject later,
    # with the flag value that fixes them.
    if args.prefix_cache == "on" and not caps.prefix_cache:
        ap.error(f"--prefix-cache on: the {cfg.family!r} family serves "
                 f"through the {caps.kind!r} adapter, which has no paged KV "
                 f"to share prefixes in (use --prefix-cache auto)")
    if isinstance(kv_block, int) and not caps.paged_kv:
        ap.error(f"--kv-block-size {kv_block}: the {cfg.family!r} family "
                 f"cannot page its cache (use --kv-block-size auto)")
    if isinstance(buckets, list) and not caps.bucketed_prefill:
        ap.error(f"--prefill-buckets {args.prefill_buckets}: the "
                 f"{cfg.family!r} family prefills chunked left-to-right, "
                 f"not right-padded to buckets (use --prefill-buckets auto)")
    if args.engine == "static" and caps.kind == "recurrent":
        ap.error(f"--engine static: the {cfg.family!r} family carries "
                 f"recurrent state, which right-padded batch prefill would "
                 f"corrupt (use --engine continuous)")
    use_prefix = (caps.prefix_cache if args.prefix_cache == "auto"
                  else args.prefix_cache == "on")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len)
               for _ in range(args.requests)]
    _, _, engine, reqs, stats = serve(
        cfg, prompts, engine=args.engine, batch=args.batch,
        max_seq=args.max_seq, max_new=args.max_new,
        power_cap=args.power_cap, prefill_buckets=buckets,
        kv_block_size=kv_block, prefix_cache=use_prefix)

    print(f"arch={cfg.name} engine={args.engine} "
          f"adapter={stats.get('adapter', 'static')} family={cfg.family} "
          f"reqs={args.requests} "
          f"prefill={stats['prefill_s']*1e3:.0f}ms "
          f"decode={stats['decode_s']*1e3:.0f}ms "
          f"({stats['decode_tok_per_s']:.1f} tok/s)")
    print(f"compiles: prefill={stats['prefill_compiles']} "
          f"decode={stats['decode_compiles']} "
          f"buckets={list(engine.buckets) if engine.buckets else 'off'}")
    if stats.get("kv_block_size"):
        pc = stats.get("prefix_cache")
        pc_str = (f" prefix-cache hit-rate={pc['hit_rate']:.0%} "
                  f"cached-tokens={pc['cached_tokens']}" if pc else "")
        print(f"paged-kv: block={stats['kv_block_size']} "
              f"peak-blocks={stats['kv_pages']['peak_used']}/"
              f"{stats['kv_pages']['total_blocks']}{pc_str}")
    if engine.tel is not None:
        # full-session telemetry report from the unified API
        rep = engine.tel.session.report(tokens=stats.get("tokens_decoded"))
        print(f"energy: {rep}")
    if args.trace_out and engine.tracer is not None:
        write_chrome_trace(
            args.trace_out, engine.tracer,
            session=engine.tel.session if engine.tel is not None else None,
            meta={"process": "dalek-serve", "arch": cfg.name,
                  "engine": args.engine})
        print(f"timeline -> {args.trace_out}")
    if args.metrics_json:
        engine.metrics.write_json(args.metrics_json)
        print(f"metrics -> {args.metrics_json}")
    for r in reqs:
        j_tok = r.energy_j / max(len(r.output), 1)
        print(f"  req {r.req_id}: {len(r.output)} tokens "
              f"[{r.finish_reason or 'ok'}] {r.energy_j:.2f} J "
              f"({j_tok:.3f} J/token)")
    return stats


if __name__ == "__main__":
    enable_compile_cache()
    main()
