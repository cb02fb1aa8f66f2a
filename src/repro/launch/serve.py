"""Serving launcher: legacy static batching or continuous batching with the
paged KV cache and optional drop-masked tensor-parallel decode.

  # legacy static-batch greedy generation
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
      --batch 4 --prompt-len 32 --new-tokens 16

  # continuous batching over a Poisson request trace, lossy TP decode
  PYTHONPATH=src python -m repro.launch.serve --serve continuous --reduced \
      --lam 50 --requests 16 --tp-shards 4 -p 0.1 --telemetry-dir runs/serve
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.env import use_compile_cache
from repro.models import build_model
from repro.netsim import request_trace
from repro.serve import (ContinuousEngine, ServeEngine, TPDecodeConfig,
                         make_requests)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--serve", choices=("legacy", "continuous"),
                    default="legacy",
                    help="static batching vs continuous batching + paged KV")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # -- continuous-engine knobs -----------------------------------------
    ap.add_argument("--page", type=int, default=16,
                    help="KV block size in tokens")
    ap.add_argument("--kv-blocks", type=int, default=65,
                    help="pool size in blocks (incl. the null block)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="decode lanes (max in-flight requests)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="tokens per fused decode round")
    ap.add_argument("--lam", type=float, default=50.0,
                    help="request arrival rate (req/s, Poisson)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--drain", action="store_true",
                    help="ignore arrival times (throughput mode)")
    # -- lossy TP decode --------------------------------------------------
    ap.add_argument("--tp-shards", type=int, default=0,
                    help="tensor-parallel shards (0 = dense decode)")
    ap.add_argument("-p", "--drop-rate", type=float, default=0.0)
    ap.add_argument("--channel", default=None,
                    help="channels.registry spec, e.g. "
                         "'deadline:deadline_ms=8,straggler_frac=0.2'")
    ap.add_argument("--wire", default="f32")
    ap.add_argument("--recovery", default="renorm",
                    choices=("renorm", "scale"))
    ap.add_argument("--engine", default="xla", choices=("xla", "ring"))
    ap.add_argument("--telemetry-dir", default=None,
                    help="write a Chrome trace of the serving session here")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, grouped=False if args.reduced else True)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if args.serve == "legacy":
        eng = ServeEngine(model=model, params=params,
                          max_len=args.prompt_len + args.new_tokens,
                          temperature=args.temperature)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size,
                         size=(args.batch, args.prompt_len)), jnp.int32)
        extra = None
        if cfg.family == "vlm":
            extra = {"patches": jnp.asarray(
                rng.normal(size=(args.batch, cfg.n_patches,
                                 cfg.d_model)) * 0.02, cfg.jnp_dtype)}
        if cfg.family == "audio":
            extra = {"frames": jnp.asarray(
                rng.normal(size=(args.batch,
                                 args.prompt_len // cfg.enc_frames_ratio,
                                 cfg.d_model)) * 0.02, cfg.jnp_dtype)}
        t0 = time.time()
        out = eng.generate(prompts, args.new_tokens,
                           key=jax.random.PRNGKey(1), extra_inputs=extra)
        dt = time.time() - t0
        tps = args.batch * args.new_tokens / dt
        print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
              f"({tps:.1f} tok/s on {jax.devices()[0].platform})")
        print(np.asarray(out)[:2])
        return

    tp = None
    if args.tp_shards:
        tp = TPDecodeConfig(n_shards=args.tp_shards, p=args.drop_rate,
                            channel=args.channel, wire=args.wire,
                            recovery=args.recovery, engine=args.engine)
    telemetry = None
    if args.telemetry_dir:
        from repro.telemetry import Telemetry
        telemetry = Telemetry(out_dir=args.telemetry_dir)
    eng = ContinuousEngine(
        model=model, params=params, page=args.page,
        n_blocks=args.kv_blocks, max_batch=args.max_batch,
        chunk=args.chunk, max_len=args.prompt_len + args.new_tokens,
        temperature=args.temperature, tp=tp, telemetry=telemetry)
    trace = request_trace(args.lam, n_requests=args.requests,
                          prompt_lens=(args.prompt_len // 2,
                                       args.prompt_len),
                          max_new=(args.new_tokens // 2, args.new_tokens),
                          seed=0)
    reqs = make_requests(trace, cfg.vocab_size)
    rep = eng.run(reqs, drain=args.drain)
    print(f"arch={cfg.name} served {len(rep.requests)} requests / "
          f"{rep.tokens} tokens in {rep.wall_s:.2f}s "
          f"({rep.tokens_per_s:.1f} tok/s, {rep.rounds} rounds, "
          f"{rep.prefills} prefills)")
    print(f"latency p50={rep.latency_quantile(0.5):.1f}ms "
          f"p99={rep.latency_quantile(0.99):.1f}ms  "
          f"preempts={sum(r.n_preempt for r in rep.requests)}")
    if telemetry is not None:
        path = os.path.join(args.telemetry_dir, "serve_trace.json")
        telemetry.trace.write(path)
        print(f"trace -> {path}")


if __name__ == "__main__":
    main()
