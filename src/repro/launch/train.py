"""Training launcher: the n-worker simulation on one device
(``repro.train.simulator``: paper-scale experiments, global-view exchange,
bit-identical to the collective path). The mesh trainer
(``repro.train.trainer``) has no launcher; ``chip_smoke.py --chips 4``
drives it on four chips.

Example (the end-to-end ~100M driver is examples/train_rps_100m.py):
  PYTHONPATH=src python -m repro.launch.train --arch rps-paper-mlp \
      --steps 200 --drop-rate 0.1 --aggregator rps_model
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.data.synthetic import CharLMTask, make_worker_streams
from repro.launch.env import use_compile_cache
from repro.models import build_model
from repro.train.simulator import SimulatorConfig, run_simulation

#: largest token alphabet of the synthetic Markov task: its (V, V)
#: transition table is built on the host, 84 GB at a 102400-token vocab.
#: Larger models see token ids below this bound; their widths are untouched.
TASK_VOCAB = 4096


def _float_or_auto(v: str):
    """--compute-ms accepts a float (the modelled backward duration) or
    the literal 'auto' (measure the real backward, DESIGN.md §16)."""
    if str(v).lower() == "auto":
        return "auto"
    return float(v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rps-paper-mlp")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced variant")
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--servers", type=int, default=None,
                    help="parameter-server blocks s (DESIGN.md §10): "
                         "round-robin worker owners, rectangular (n, s) "
                         "drop masks; default: one block per worker "
                         "(s = n, the paper's layout)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--drop-rate", type=float, default=0.1)
    ap.add_argument("--channel", default=None,
                    help="drop-process spec (repro.channels), e.g. "
                         "'ge:p_bad=0.3,burst=8', 'hetero:n_pods=4,"
                         "p_cross=0.3', 'trace:lam=8000,prio=0.8' or "
                         "'trace:path=colo.npz'; default: i.i.d. "
                         "Bernoulli(--drop-rate)")
    ap.add_argument("--aggregator", default="rps_model",
                    choices=["rps_model", "rps_grad", "allreduce_model",
                             "allreduce_grad", "local"])
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="coalesce the exchange into fixed-byte buckets "
                         "of this many MiB (DESIGN.md §11) — buckets are "
                         "also the packetisation unit (per-bucket drop "
                         "masks); default: the per-leaf legacy plan")
    ap.add_argument("--buckets", type=int, default=None,
                    help="… or exactly this many size-balanced buckets")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "xla", "ring"],
                    help="exchange-arithmetic engine (DESIGN.md §12): "
                         "xla/auto = the seed f32 einsum math (bit-"
                         "identical); ring = replay the ring engine's "
                         "wire arithmetic (ring-order sums in "
                         "--exchange-dtype) to study e.g. bf16-wire "
                         "convergence on one device")
    ap.add_argument("--exchange-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="RS wire/accumulation dtype for --engine ring "
                         "(bf16 halves RS bytes on a real fabric); "
                         "absorbed by --wire, which wins when set")
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="RS-leg wire codec (DESIGN.md §13): f32 = "
                         "paper-faithful passthrough (bit-identical "
                         "default), bf16 = half the RS bytes, int8 = "
                         "quarter (stochastic rounding, per-block "
                         "scales)")
    ap.add_argument("--recovery", default="renorm",
                    help="loss-recovery policy (DESIGN.md §13/§17): "
                         "renorm = paper Algorithm 1 (divide by the "
                         "received count), scale = unbiased 1/(1-p) "
                         "zero-fill, ef = error-feedback residual on "
                         "the codec error; robust kinds (§17) for "
                         "corrupted links: median, trimmed (β-trimmed "
                         "mean, 'trimmed:beta=0.2'), clip (norm-clip at "
                         "clip_mult x the median norm)")
    ap.add_argument("--corruption", default=None,
                    help="corruption-process spec (DESIGN.md §17) over "
                         "bitflip/scale/signflip/collude, e.g. "
                         "'signflip:frac=0.1' or 'collude:gamma=10,"
                         "byzantine_frac=0.2'; default: no corruption "
                         "(bit-identical)")
    ap.add_argument("--byzantine-frac", type=float, default=0.0,
                    help="fraction of colluding workers (lowest ids, "
                         "every packet corrupted); overlays the "
                         "--corruption spec's own field and alone "
                         "selects the collude attack")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="async overlap engine (DESIGN.md §15): buckets "
                         "ship in reverse-layer order as gradients become "
                         "ready; against a deadline channel each bucket "
                         "faces its reduced slack (deadline - readiness) "
                         "and late packets are written off as dropped-"
                         "with-recovery (staleness axis in the history/"
                         "telemetry). Default: sync barrier, bit-"
                         "identical to the seed")
    ap.add_argument("--compute-ms", type=_float_or_auto, default=None,
                    help="async backward-pass cost model: modelled "
                         "backward duration the per-bucket readiness "
                         "times derive from; default 0.8 x the channel "
                         "deadline when it has one, else 1.0. 'auto' "
                         "(DESIGN.md §16) times the real backward per "
                         "bucket instead and feeds the measured "
                         "readiness into the plan")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adam"],
                    help="per-worker optimizer (paper: plain sgd)")
    ap.add_argument("--state-pack", default="f32",
                    choices=["f32", "bf16", "i8", "int8"],
                    help="at-rest trainer-state format (DESIGN.md §16): "
                         "f32 = unpacked (bit-identical default), bf16, "
                         "i8 = momentum bf16 + Adam second moments / EF "
                         "residual int8 with per-row scales and "
                         "stochastic rounding on write")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--telemetry", action="store_true",
                    help="exchange telemetry (DESIGN.md §14): per-step "
                         "structured records (per-link delivery, drop "
                         "rates, norms), a live per-link effective-p "
                         "estimate vs the theory bounds, and Chrome-trace "
                         "spans; bit-identical to a telemetry-off run")
    ap.add_argument("--telemetry-dir", default=None,
                    help="write telemetry.jsonl / summary.json / "
                         "trace.json here (implies --telemetry); render "
                         "with tools/render_experiments.py --telemetry DIR")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, grouped=False)
    task = CharLMTask(vocab=min(cfg.vocab_size, TASK_VOCAB),
                      seq_len=args.seq_len, seed=args.seed)
    batch_fn = make_worker_streams(task, args.workers, args.batch_size)

    def loss_fn(p, b):
        loss, _ = model.loss(p, b)
        return loss

    scfg = SimulatorConfig(
        n_workers=args.workers, drop_rate=args.drop_rate,
        aggregator=args.aggregator, optimizer=args.optimizer,
        lr=args.lr, steps=args.steps,
        warmup=args.warmup, batch_size=args.batch_size, seed=args.seed,
        channel=args.channel, n_servers=args.servers,
        corruption=args.corruption, byzantine_frac=args.byzantine_frac,
        bucket_mb=args.bucket_mb, n_buckets=args.buckets,
        engine=args.engine, exchange_dtype=args.exchange_dtype,
        wire=args.wire, recovery=args.recovery,
        schedule="async" if args.async_ else "sync",
        compute_ms=args.compute_ms, state_pack=args.state_pack)
    reg = None
    if args.telemetry or args.telemetry_dir:
        from repro.telemetry import Telemetry
        reg = Telemetry(out_dir=args.telemetry_dir)
    t0 = time.time()
    hist = run_simulation(loss_fn, model.init, batch_fn, scfg,
                          telemetry=reg)
    dt = time.time() - t0
    print(f"channel={hist['channel']} "
          f"eff_p={hist['channel_effective_p']:.4f}")
    if hist.get("exchange_plan"):
        ep = hist["exchange_plan"]
        print(f"exchange plan: {ep['n_buckets']} buckets × s={ep['s']} -> "
              f"{ep['collectives_per_round']} collectives/round, "
              f"model_packets={ep['model_packets']}, "
              f"wire={ep['wire']}/{ep['recovery']} "
              f"(rs_bytes_ratio={ep['rs_bytes_ratio']:.2f})")
    if hist.get("state_bytes") and args.state_pack != "f32":
        sb = hist["state_bytes"]
        comps = ", ".join(f"{k}={v}" for k, v in sb.items()
                          if k != "total" and v)
        print(f"state bytes [{args.state_pack}]: total {sb['total']} "
              f"({comps})")
    print(f"n={args.workers} s={args.servers or args.workers} "
          f"p={args.drop_rate} agg={args.aggregator} "
          f"final_loss={hist['final_loss']:.4f} "
          f"(entropy floor {task.entropy_floor():.4f}) "
          f"consensus={hist['consensus'][-1]:.3e} [{dt:.1f}s]")
    if hist.get("staleness"):
        print(f"async staleness: mean late_frac="
              f"{float(np.mean(hist['staleness'])):.3f} "
              f"(max {float(np.max(hist['staleness'])):.3f})")
    if args.checkpoint:
        mean_params = jax.tree.map(lambda x: jnp.mean(x, 0), hist["params"])
        save_pytree(args.checkpoint, mean_params)
        print("checkpoint ->", args.checkpoint)
    if reg is not None:
        reg.finalize(print_summary=True)
        if args.telemetry_dir:
            print("telemetry ->", args.telemetry_dir)
    if args.out:
        hist.pop("params")
        hist.pop("channel_state")          # jax pytrees, not JSON
        hist.pop("ef_state")
        hist.pop("state")
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)
        print("history ->", args.out)


if __name__ == "__main__":
    main()
