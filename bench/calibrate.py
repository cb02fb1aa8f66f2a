#!/usr/bin/env python3
"""The readings that a cell's limits are set from, on the chip at the
cell's own size, all in one process:

    python3 bench/calibrate.py --workload <name> --seeds 101-112 \
        [--control 4] [--faults 3]

For every seed, the program's compared numbers against the reference
(the lower readings); on the first ``--control`` seeds, the control's:
the reference computed in the precision below the configuration's, put
in the program's place (the upper readings); on the first ``--faults``
seeds, each fault that the cell can have, planted in the reference put in
the program's place. Each reading also says whether the cell's limits
(its traffic file's ``limits``) would pass it as ``correct``: the control
and every fault must not, or this exits 1. Prints one JSON line per
reading and a summary that counts the readings passed as correct.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def _seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        else:
            out.append(int(part))
    return out


def emit(**kw) -> dict:
    print(json.dumps(kw, default=float), flush=True)
    return kw


def verdict(values: dict, limits: dict) -> bool:
    """``correct`` as a run of the cell would decide it from these numbers
    and the cell's own limits (``harness.Check``)."""
    return all(harness.Check(k, float(values[k]), float(v)).ok
               for k, v in limits.items())


def serve_open_loop(cell, seeds, n_control, n_faults, lowp, seconds,
                    rates=None):
    """Program readings (each seed a whole window at the cell's load),
    the control's on the same prompts and served tokens, and the fault of
    a served token altered where it is produced. With ``rates``, instead
    the knee sweep: one window per offered rate, on the first seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from drivers import serve_open_loop as drv
    from refs import serve as ref_serve

    hf, tr = cell.config, cell.traffic
    vocab = hf["vocab_size"]
    eng = drv.make_engine(hf, tr, harness.seed32(seeds[0]))
    drv.warm(eng, tr, vocab)
    rows = []
    if rates:
        for rate in rates:
            reqs = drv.requests(harness.seed32(seeds[0]), seconds,
                                dict(tr, rate_per_s=rate), vocab)
            rep = eng.run(reqs)
            ttft, tpot = drv.latencies(rep.requests)
            end_s = max(r.finish_ms for r in rep.requests) / 1e3
            wait = [r.admitted_ms - r.arrival_ms for r in rep.requests]
            rows.append(emit(
                kind="sweep", rate_per_s=rate, requests=len(reqs),
                tokens=rep.tokens, end_s=end_s,
                serve_tokens_per_s=rep.tokens / end_s,
                ttft_p50_ms=float(np.percentile(ttft, 50)),
                ttft_p90_ms=float(np.percentile(ttft, 90)),
                tpot_p90_ms=float(np.percentile(tpot, 90)),
                queue_wait_p90_ms=float(np.percentile(wait, 90)),
                last_arrival_s=max(r.arrival_ms for r in reqs) / 1e3))
        return rows, ("serve_tokens_per_s", "ttft_p90_ms")
    shapes = jax.eval_shape(eng.model.init, jax.random.PRNGKey(0))
    make = jax.jit(lambda k: drv.weights.program_params(k, hf, shapes))
    ref = ref_serve.Reference(hf)
    ctrl = ref_serve.Reference(hf, lowp=jnp.dtype(lowp))
    limits = {"logit_gap": tr["limits"]["logit_gap"]}
    for i, seed in enumerate(seeds):
        s31 = harness.seed32(seed)
        eng.params = None
        gc.collect()
        eng.params = make(drv.weights.root_key(s31))
        rep = eng.run(drv.requests(s31, seconds, tr, vocab))
        picked = drv.sample(rep.requests, tr["check_requests"], s31)
        eng.params = None
        gc.collect()
        seqs, pairs = ref_serve.served_positions(
            [r.prompt for r in picked], [list(r.generated) for r in picked])
        packed = ref_serve.pack(seqs, tr["max_len"])
        logits = ref.logits(s31, packed)
        g = ref_serve.gaps(logits, pairs)
        rows.append(emit(kind="program", seed=seed, logit_gap=float(g.max()),
                         correct=verdict({"logit_gap": g.max()}, limits),
                         served=int(g.size),
                         unfinished=sum(r.finish_ms is None
                                        for r in rep.requests)))
        if i < n_control:
            c = ref_serve.gaps(logits, pairs, choose=ctrl.logits(s31, packed))
            rows.append(emit(kind="control", seed=seed,
                             logit_gap=float(c.max()), served=int(c.size),
                             correct=verdict({"logit_gap": c.max()}, limits)))
        if i < n_faults:
            bad = [list(p) for p in pairs]
            pos, tok = bad[0][0]
            bad[0][0] = (pos, (tok + 1) % vocab)
            f = ref_serve.gaps(logits, bad)
            rows.append(emit(kind="token_altered", seed=seed,
                             logit_gap=float(f.max()),
                             correct=verdict({"logit_gap": f.max()}, limits)))
        del logits
    return rows, ("logit_gap",)


def other_masks(sim, seed31, masks):
    """The masks the step would have drawn under another key (each step's
    key folded with a tag), the first tag whose masks differ from
    ``masks`` in some step: masks equal to the step's are no fault."""
    import jax
    _, key = jax.random.split(jax.random.PRNGKey(seed31))
    for tag in range(1, 64):
        out = []
        for t in range(len(masks)):
            k = jax.random.fold_in(jax.random.fold_in(key, t), tag)
            out.append(tuple(np.asarray(m) for m in
                             sim._masks(k, sim.ch_state)))
        if any((a != b).any() for pair, mine in zip(masks, out)
               for a, b in zip(pair, mine)):
            return out, tag
    raise RuntimeError("no other key changes the masks")


def train_sim(cell, seeds, n_control, n_faults, lowp):
    """Program readings (each seed the cell's checked steps through its
    own step object, against the reference), the control's (the
    reference in ``lowp`` put in the program's place) and, on the first
    ``n_faults`` seeds, each fault of a training step planted in the
    reference put in the program's place: half the batch left out, the
    exchange left out, the masks of another key; a state left unchanged
    reads 1 by the measure's definition and is computed, not run. On the
    control's seeds also ``backward_lowp``: the reference with only its
    backward pass in ``lowp``, which the limits have to fail as well."""
    import jax.numpy as jnp
    import markov
    from drivers import train_sim as drv
    from refs import train as ref_train

    hf, tr = cell.config, cell.traffic
    n, B, S = int(tr["workers"]), int(tr["batch_per_worker"]), \
        int(tr["seq_len"])
    checked, lr = int(tr["check_steps"]), tr["simulator"]["lr"]
    limits = tr["limits"]
    numbers = ("loss_gap", "change1_gap", "change_gap")
    lim = {k: limits[k] for k in numbers}
    sim = drv.Sim(hf, tr)
    ref = ref_train.Reference(hf)
    variants = {"control": ref_train.Reference(hf, lowp=jnp.dtype(lowp)),
                "backward_lowp": ref_train.Reference(
                    hf, lowp=jnp.dtype(lowp), backward_only=True),
                "half_batch": ref_train.Reference(hf, half_batch=True),
                "no_exchange": ref_train.Reference(hf, exchange=False)}
    rows = []

    def row(kind, seed, g, **kw):
        vals = {k: g[k] for k in numbers}
        rows.append(emit(kind=kind, seed=seed, **vals,
                         correct=verdict(vals, lim), worst=g.get("worst"),
                         **kw))

    for i, seed in enumerate(seeds):
        s31 = harness.seed32(seed)
        tokens, labels = markov.batches(s31, tr["task"], n, B, S)
        masks, losses, prog = drv.checked_steps(sim, s31, tokens, labels,
                                                checked)
        finite = bool(np.all(np.isfinite(prog["loss"])))
        sim.params = sim.opt_state = None
        del losses
        gc.collect()
        args = (s31, tokens[:checked], labels[:checked])
        truth = ref.run(*args, masks, lr, checked)
        row("program", seed, ref_train.gaps(prog, truth), finite=finite)
        if i < n_control:
            for name in ("control", "backward_lowp"):
                row(name, seed, ref_train.gaps(
                    variants[name].run(*args, masks, lr, checked), truth))
        if i < n_faults:
            for name in ("half_batch", "no_exchange"):
                row(name, seed, ref_train.gaps(
                    variants[name].run(*args, masks, lr, checked), truth))
            wrong, tag = other_masks(sim, s31, masks)
            row("other_masks", seed, ref_train.gaps(
                ref.run(*args, wrong, lr, checked), truth), tag=tag)
            still = {"loss": prog["loss"], "change": {
                t: {k: 0.0 * v for k, v in c.items()}
                for t, c in truth["change"].items()}}
            row("unchanged", seed, ref_train.gaps(still, truth))
    return rows, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=4)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--lowp", default="float8_e4m3fn",
                    help="the precision below the configuration's")
    ap.add_argument("--seconds", type=float, default=None,
                    help="serving: the window of each seed (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--sweep", default=None,
                    help="serving: comma-separated offered rates for the "
                         "knee sweep")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", harness.CACHE_DIR)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.load_cell(args.workload)
    kind = cell.traffic["driver"]
    t0 = time.time()
    seeds = _seeds(args.seeds)
    if kind == "serve_open_loop":
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
            seconds = args.seconds or json.load(f)["run_seconds"]
        rates = [float(r) for r in args.sweep.split(",")] \
            if args.sweep else None
        rows, numbers = serve_open_loop(cell, seeds, args.control,
                                        args.faults, args.lowp, seconds,
                                        rates)
    elif kind == "train_sim":
        rows, numbers = train_sim(cell, seeds, args.control, args.faults,
                                  args.lowp)
    else:
        print(f"calibrate: no readings for driver {kind!r}", file=sys.stderr)
        return 2
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        summary[kind] = {k: {"min": min(r[k] for r in sel),
                             "max": max(r[k] for r in sel)}
                         for k in numbers}
        summary[kind]["seeds"] = len(sel)
        if "correct" in sel[0]:
            summary[kind]["correct"] = sum(r["correct"] for r in sel)
    emit(kind="summary", seconds=time.time() - t0,
         device=jax.devices()[0].device_kind, summary=summary)
    passed = [r for r in rows if r["kind"] not in ("program", "sweep")
              and r["correct"]]
    if passed:
        print(f"calibrate: the cell's limits pass {len(passed)} control or "
              f"fault readings as correct", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
