#!/usr/bin/env python3
"""Smoke run of the training and serving main paths on TPU, at
deepseek-7b's published widths with the depth cut to fit the chips.

  python chip_smoke.py             # one chip: simulator training, serving
  python chip_smoke.py --chips 4   # four chips: mesh trainer, ring kernel

Weights are random, made from fixed seeds. Each phase checks what it
produced against a reference computed in the same process; a check that
fails is reported on standard error and the script goes on to the next,
then exits 1. The sizes, the cuts and every tolerance are printed on the
lines before the result. The last line of standard output is one JSON
object naming the device, printed only when every check held. Without a
TPU the script exits non-zero and prints no result.

Everything runs in this one process: a TPU belongs to the first process
that touches it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "deepseek-7b"
# bf16 keeps 8 significant bits, so one unit in the last place is at most
# 2^-7 of the value: two f32 computations of one number may round to
# neighbouring bf16 numbers
BF16_ULP = 2.0 ** -7
#: two separately compiled steps (rps_model against allreduce_model)
#: average the same per-worker values, but XLA may fuse a worker's SGD
#: update into the average and skip rounding it to bf16 (excess
#: precision, seen in the allreduce step's HLO): the average then moves
#: by up to half an ulp of the largest worker value, far more than an ulp
#: of the average where the workers cancel. Bound per element: 2 ulp of
#: the result (two final roundings) + 1 ulp of the largest worker value
#: that went into it.
TWO_PROGRAMS = "2 bf16 ulp of the value + 1 ulp of the largest worker input"

#: one chip: n stacked workers of the simulator. Sized by the compiled
#: step's memory_analysis for a v5e (16 GB): n=2 with 2 layers peaks at
#: 12.5 GiB, most of it the exchange's copies of the two 102400x4096
#: embeddings; a third worker or a fourth layer passes 14 GiB.
TRAIN = dict(workers=2, layers=2, batch=4, seq=256, steps=3, p=0.1)
#: one chip: the serving engine. 16 layers are 8.1 GB of bf16 weights; the
#: KV pool holds 256 pages of 16 tokens, 16 KB per token per layer (1 GB).
SERVE = dict(layers=16, page=16, kv_blocks=257, max_batch=4, chunk=8,
             requests=6, prompt_lens=(48, 96), max_new=(8, 16),
             check_steps=4)
#: paged decode and the no-cache forward order their bf16 arithmetic
#: differently (one reads K/V through the block table, one attends over
#: the whole sequence at once); 16 layers of bf16 activations move the
#: logits by a few bf16 units. Relative L2 error per logit row.
SERVE_LOGIT_TOL = 0.05
#: four chips: one worker per chip, each holding its whole replica. The
#: p=0 check holds two results (the local half, rps_model) while the
#: allreduce_model step runs: at 2 layers 2 x 2.32 GiB + 2.32 GiB of params
#: + 3.23 GiB of temporaries = 10.2 GiB per chip (compiled for a v5e:2x2).
#: 4 layers hold 3 x 3.07 GiB before any temporaries.
MESH = dict(layers=2, batch=2, seq=256)
#: the fused ring kernel keeps the whole (4, W) bucket, padded to the
#: payload's sublane tile, in VMEM: the widest power-of-two W that
#: compiled for a described v5e:2x2 (twice as wide runs out of VMEM).
RING_W = {"float32": 131072, "bfloat16": 32768}


def say(tag: str, **kv) -> None:
    print(f"{tag}: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


#: the checks that failed, in order
FAILED: list = []


def check(ok: bool, what: str) -> None:
    if not ok:
        FAILED.append(what)
        print(f"FAILED: {what}", file=sys.stderr, flush=True)


def _mismatches(a, b, rel=BF16_ULP, floor=0.0):
    """Elements of two results further apart than ``rel`` of the larger
    magnitude plus ``floor`` of the reference's largest, and the largest
    absolute difference (f32 scalars)."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    diff = jnp.abs(a - b)
    bound = rel * jnp.maximum(jnp.abs(a), jnp.abs(b)) \
        + floor * jnp.max(jnp.abs(b))
    return jnp.sum(diff > bound), jnp.max(diff)


def _two_programs(a, b, top):
    """Elements of two results past the TWO_PROGRAMS bound, where ``top``
    is the largest magnitude among the worker values that were averaged
    (broadcast against ``a``); also the largest absolute difference and
    the largest diff / bound (f32 scalars). Reductions only, so XLA
    fuses the whole comparison into one pass over its operands."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    diff = jnp.abs(a - b)
    bound = 2 * BF16_ULP * jnp.maximum(jnp.abs(a), jnp.abs(b)) \
        + BF16_ULP * top.astype(jnp.float32)
    ratio = jnp.where(diff > 0, diff / jnp.maximum(bound, 2.0 ** -126), 0.0)
    return jnp.sum(diff > bound), jnp.max(diff), jnp.max(ratio)


def _peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2 ** 30:.2f}GiB"


# ---------------------------------------------------------------------------
# one chip: training through the n-worker simulator
# ---------------------------------------------------------------------------

def train_phase(cfg, *, workers, batch, seq, steps, p):
    """``run_simulation`` as ``repro.launch.train`` builds it; then the
    next step's local update, and its exchange through the Pallas kernel
    against the einsum path on the same masks."""
    import jax
    import jax.numpy as jnp
    from repro.core import rps as rps_lib
    from repro.data.synthetic import CharLMTask, make_worker_streams
    from repro.launch.train import TASK_VOCAB
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.simulator import SimulatorConfig, run_simulation

    model = build_model(cfg, grouped=False)
    task = CharLMTask(vocab=min(cfg.vocab_size, TASK_VOCAB), seq_len=seq,
                      seed=0)
    batch_fn = make_worker_streams(task, workers, batch)

    def loss_fn(params, b):
        return model.loss(params, b)[0]

    scfg = SimulatorConfig(n_workers=workers, drop_rate=p,
                           aggregator="rps_model", steps=steps,
                           batch_size=batch, eval_every=1, seed=0)
    backend = rps_lib._resolve_global_backend("auto")
    hist = run_simulation(loss_fn, model.init, batch_fn, scfg)
    losses = [float(x) for x in hist["loss"]]
    say("train", steps=steps, exchange_backend=backend,
        loss=[round(x, 4) for x in losses],
        consensus=f"{hist['consensus'][-1]:.4e}")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss {losses}")
    params = hist["params"]
    del hist

    # the local half of step `steps` (per-worker SGD on each worker's own
    # batch), which leaves the workers apart for its exchange to average
    opt = make_optimizer(scfg.optimizer)

    def local_step(ps, b):
        g = jax.grad(lambda q: jnp.sum(jax.vmap(loss_fn)(q, b)))(ps)
        return opt.update(g, opt.init(ps), ps, jnp.float32(scfg.lr))[0]

    params = jax.jit(local_step, donate_argnums=(0,))(params,
                                                     batch_fn(steps))

    # one RS drop (worker 0 -> owner of the last block) and one AG drop
    # (block 0 -> the last worker), so renormalisation and the AG fallback
    # both run; each leaf is its own bucket of the per-leaf plan
    n = workers
    rs = jnp.ones((n, n), bool).at[0, n - 1].set(False)
    ag = jnp.ones((n, n), bool).at[n - 1, 0].set(False)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def compare(leaf, rs, ag):
        out = {b: rps_lib.rps_exchange_global(
            {"x": leaf}, key, p, n, masks=(rs, ag), backend=b)["x"]
            for b in ("pallas", "jnp")}
        bad, diff = _mismatches(out["pallas"], out["jnp"])
        moved = jnp.max(jnp.abs(out["jnp"].astype(jnp.float32)
                                - leaf.astype(jnp.float32)))
        return bad, diff, moved

    leaves = jax.tree_util.tree_leaves_with_path(params)
    biggest = max(leaves, key=lambda kv: kv[1].size)[1]
    hlo = compare.lower(biggest, rs, ag).compile().as_text()
    kernels = hlo.count("tpu_custom_call")
    if jax.default_backend() == "tpu":
        check(kernels >= 1, "masked_avg_grid_pallas did not compile into "
                            "the exchange")
    worst, moved, n_bad = 0.0, 0.0, 0
    for _, leaf in leaves:
        bad, diff, mv = compare(leaf, rs, ag)
        n_bad += int(bad)
        worst, moved = max(worst, float(diff)), max(moved, float(mv))
    say("train exchange check",
        compared="pallas vs jnp backend of rps_exchange_global",
        leaves=len(leaves), elements=sum(x.size for _, x in leaves),
        masks="rs[0,-1]=ag[-1,0]=dropped",
        tolerance="1 bf16 ulp (both sum <=n bf16 products exactly in "
                  "f32, divide by 1..n and round once)",
        mismatches=n_bad, max_abs_diff=f"{worst:.3e}",
        max_change_by_exchange=f"{moved:.3e}",
        tpu_custom_call=kernels)
    check(n_bad == 0, f"{n_bad} elements differ by more than one bf16 ulp")
    check(moved > 0, "the exchange changed nothing")


# ---------------------------------------------------------------------------
# one chip: serving through the continuous-batching engine
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, page, kv_blocks, max_batch, chunk, requests,
                prompt_lens, max_new, check_steps):
    """``ContinuousEngine`` with the paged cache as ``launch/serve.py
    --serve continuous --full`` builds it, answering a request trace; then
    the logits of the first decode steps through the paged cache against
    a no-cache forward of the same sequence."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.models import layers as L
    from repro.models import stack as S
    from repro.netsim import request_trace
    from repro.serve import ContinuousEngine, make_requests
    from repro.serve.kvcache import PagedCache, n_pages

    model = build_model(cfg, grouped=True)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ContinuousEngine(model=model, params=params, page=page,
                           n_blocks=kv_blocks, max_batch=max_batch,
                           chunk=chunk,
                           max_len=max(prompt_lens) + max(max_new))
    trace = request_trace(50.0, n_requests=requests,
                          prompt_lens=prompt_lens, max_new=max_new, seed=0)
    reqs = make_requests(trace, cfg.vocab_size)
    rep = eng.run(reqs, drain=True)
    answered = sum(len(r.generated) == r.max_new for r in rep.requests)
    say("serve", requests=len(reqs), answered=answered, tokens=rep.tokens,
        rounds=rep.rounds, prefills=rep.prefills)
    check(answered == len(reqs), f"{len(reqs) - answered} requests "
                                 "unanswered")

    # teacher-force request 0's own output through a fresh paged pool
    r = rep.requests[0]
    P, K = len(r.prompt), min(check_steps, r.max_new - 1)
    toks = np.concatenate([r.prompt, np.asarray(r.generated[:K])])
    cache = PagedCache(model, page, kv_blocks, writers=eng._writers)
    blocks = cache.alloc.alloc(n_pages(P + K, page))
    last, pcache = eng._prefill(params, jnp.asarray(r.prompt[None]))
    cache.write_prefill(pcache, blocks, P)
    bt = jnp.asarray(cache.block_row(blocks, eng.max_pages)[None])
    step = jax.jit(lambda ps, pool, tok, pos: model.decode_paged(
        ps, pool, {"token": tok}, pos, bt, page=page), donate_argnums=(1,))
    rows, pool = [last[0]], cache.pool
    for t in range(K):
        logits, pool = step(params, pool, jnp.asarray([[toks[P + t]]]),
                            jnp.asarray([P + t], jnp.int32))
        rows.append(logits[0])
    paged = jnp.stack(rows).astype(jnp.float32)          # (K+1, V)

    @jax.jit
    def forward(ps, tokens):                              # no cache
        x = L.embed(ps["embed"], tokens)
        x, _ = S.apply_stack(ps["layers"], x, {}, cfg, model.kinds,
                             model.specs, mode="train", grouped=True)
        return L.lm_head(ps["embed"], x, cfg.vocab_size)[..., :cfg.vocab_size]

    ref = forward(params, jnp.asarray(toks[None]))[0, P - 1:].astype(
        jnp.float32)
    rel = np.asarray(jnp.linalg.norm(paged - ref, axis=-1)
                     / jnp.linalg.norm(ref, axis=-1))
    agree = int(jnp.sum(jnp.argmax(paged, -1) == jnp.argmax(ref, -1)))
    say("serve logit check",
        compared="prefill + paged decode vs no-cache forward",
        request=r.rid, prompt_len=P, decode_steps=K,
        rel_l2_per_step=[f"{x:.2e}" for x in rel],
        tolerance=f"{SERVE_LOGIT_TOL} (bf16 activations, two op orders)",
        argmax_agree=f"{agree}/{K + 1}")
    check(bool(np.all(rel <= SERVE_LOGIT_TOL)),
          f"paged logits off the reference: {rel}")


# ---------------------------------------------------------------------------
# four chips: the mesh trainer and the fused ring kernel
# ---------------------------------------------------------------------------

def _masks_with_drops(n: int, p: float, n_buckets=None):
    """The first step key whose Bernoulli draw drops at least one RS and
    one AG packet, so renormalisation and the fallback both run; returns
    (key, rs, ag)."""
    import jax
    from repro.core import rps as rps_lib
    for t in range(1000):
        key = jax.random.PRNGKey(t)
        rs, ag = rps_lib.sample_masks(key, n, p, n_buckets=n_buckets)
        if not bool(rs.all()) and not bool(ag.all()):
            return key, rs, ag
    raise RuntimeError("no key with drops")


def mesh_phase(cfg, mesh, *, batch, seq):
    """``make_train_setup`` on a ("data",) mesh, one worker per chip:
    rps_model at p=0 against allreduce_model, and at p=0.1 one bucket of
    the step against ``rps_exchange_global`` on the gathered workers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import rps as rps_lib
    from repro.data.synthetic import CharLMTask, make_worker_streams
    from repro.launch.train import TASK_VOCAB
    from repro.models import build_model
    from repro.train.trainer import TrainConfig, make_train_setup

    n = mesh.shape["data"]
    model = build_model(cfg, grouped=True)
    task = CharLMTask(vocab=min(cfg.vocab_size, TASK_VOCAB), seq_len=seq,
                      seed=0)
    data = jax.device_put(make_worker_streams(task, n, batch)(0),
                          NamedSharding(mesh, P("data")))
    init_key = jax.random.PRNGKey(0)

    def run(aggregator, p, key, params=None, lr=0.05):
        """One step from the shared init, or from ``params`` (donated)."""
        tcfg = TrainConfig(optimizer="sgd", lr=lr, drop_rate=p,
                           aggregator=aggregator, engine="xla")
        init_state, train_step, shardings = make_train_setup(
            model, cfg, tcfg, mesh, rps_axes=("data",))
        opt = ()                                  # sgd keeps no state
        if params is None:
            p_sh, _ = shardings(jax.eval_shape(
                lambda k: init_state(k)[0], init_key))
            params, opt = jax.jit(init_state, out_shardings=(p_sh, ()))(
                init_key)
        step = jax.jit(train_step, donate_argnums=(0, 1))
        params, _, metrics = step(params, opt, data, jnp.int32(0), key)
        return params, float(metrics["loss"]), train_step.plan

    def per_chip(a, b, x):
        # each chip compares its own worker's replica; only the largest
        # worker magnitude crosses chips (a full-leaf pmax)
        bad, diff, ratio = _two_programs(
            a, b, jax.lax.pmax(jnp.abs(x), "data"))
        return (jax.lax.psum(bad, "data"), jax.lax.pmax(diff, "data"),
                jax.lax.pmax(ratio, "data"))

    cmp_mesh = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))
    keystr = jax.tree_util.keystr
    dev0 = jax.devices()[0]

    def bucket(tree, name="['attn']['wo']"):
        # the attention output projection's bucket (per-leaf plan), small
        # enough to gather all n workers onto one chip
        return next((keystr(k), jax.device_put(x, dev0)) for k, x in
                    jax.tree_util.tree_leaves_with_path(tree)
                    if keystr(k).endswith(name))

    with jax.set_mesh(mesh):
        # the local half alone ("none" steps each worker and stops; the
        # step key only draws masks): the worker values every exchange
        # below averages
        key0 = jax.random.PRNGKey(1)
        local = run("none", 0.0, key0)[0]
        rps0, loss_rps, _ = run("rps_model", 0.0, key0)
        leaves = jax.tree.leaves(rps0)
        spread = all(len(x.sharding.device_set) == n
                     and x.sharding.spec[0] == "data"
                     and x.addressable_shards[0].data.shape[0] == 1
                     for x in leaves)
        say("mesh", devices=n, aggregator="rps_model", p=0.0,
            loss=f"{loss_rps:.4f}",
            output_sharding="worker dim over 'data', one per chip"
            if spread else "NOT spread over the chips")
        check(spread, "stepped params are not spread over the chips")
        ar, loss_ar, _ = run("allreduce_model", 0.0, key0)
        stats = [cmp_mesh(a, b, x) for a, b, x in zip(
            leaves, jax.tree.leaves(ar), jax.tree.leaves(local))]
        n_bad = sum(int(r[0]) for r in stats)
        say("mesh p=0 check", compared="rps_model vs allreduce_model step",
            elements=sum(x.size for x in leaves), tolerance=TWO_PROGRAMS,
            mismatches=n_bad,
            max_abs_diff=f"{max(float(r[1]) for r in stats):.3e}",
            max_diff_over_bound=f"{max(float(r[2]) for r in stats):.3f}",
            loss_diff=f"{abs(loss_rps - loss_ar):.2e}")
        check(n_bad == 0, f"p=0 rps_model differs from allreduce ({n_bad})")
        del rps0, ar, leaves, stats
        gc.collect()

        # p=0.1: a step at lr=0 from the local half leaves each worker's
        # values bit-exact (p - 0 = p), so the step's exchange sees
        # exactly the values gathered here
        name, before = bucket(local)
        key, rs, ag = _masks_with_drops(n, 0.1)
        lossy, loss_lossy, plan = run("rps_model", 0.1, key, params=local,
                                      lr=0.0)
        del local
        after = bucket(lossy)[1]
        del lossy
        gc.collect()
    # on one chip, outside the mesh context
    want = jax.jit(lambda x: rps_lib.rps_exchange_global(
        {"x": x}, key, 0.1, n, masks=(rs, ag), backend="jnp")["x"])(before)
    bad, diff = _mismatches(after, want)
    moved = jnp.max(jnp.abs(want.astype(jnp.float32)
                            - before.astype(jnp.float32)))
    say("mesh p=0.1 check", bucket=name,
        compared="trainer step at lr=0 from the local half (xla engine) vs "
                 "rps_exchange_global on the gathered workers",
        rs_dropped=int((~rs).sum()), ag_dropped=int((~ag).sum()),
        tolerance="1 bf16 ulp (both sum the same bf16 values exactly in "
                  "f32, divide by the same count and round once)",
        mismatches=int(bad), max_abs_diff=f"{float(diff):.3e}",
        max_change_by_exchange=f"{float(moved):.3e}",
        loss=f"{loss_lossy:.4f}", buckets=plan.n_buckets)
    check(int(bad) == 0, f"p=0.1 bucket {name} differs")
    check(float(moved) > 0, "the p=0.1 exchange changed nothing")


def ring_phase(mesh, *, widths=None):
    """``engine="ring"`` against ``engine="xla"`` through
    ``rps_exchange_plan`` on the same tables and masks: one bucket per
    payload dtype, each at the widest W the fused kernel compiles for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import plan as plan_lib
    from repro.core import rps as rps_lib

    widths = RING_W if widths is None else widths
    n, p = mesh.shape["data"], 0.1
    local = {dt: jax.ShapeDtypeStruct((n * w,), jnp.dtype(dt))
             for dt, w in widths.items()}
    plan = plan_lib.make_plan(local, n, bucket_bytes=1)   # a bucket a leaf
    rng = np.random.default_rng(0)
    tables = {dt: jax.device_put(
        jnp.asarray(rng.standard_normal((n, n * w)), jnp.dtype(dt)),
        NamedSharding(mesh, P("data"))) for dt, w in widths.items()}
    key, rs, ag = _masks_with_drops(n, p, n_buckets=plan.n_buckets)

    def exchange(engine):
        def body(t, k):
            t = jax.tree.map(lambda x: x[0], t)
            out = rps_lib.rps_exchange_plan(t, k, p, "data", plan=plan,
                                            engine=engine)
            return jax.tree.map(lambda x: x[None], out)
        return jax.jit(jax.shard_map(body, mesh=mesh,
                                     in_specs=(P("data"), P()),
                                     out_specs=P("data"), check_vma=False))

    with jax.set_mesh(mesh):
        ring, xla = exchange("ring"), exchange("xla")
        fused = ring.lower(tables, key).compile().as_text().count(
            "tpu_custom_call")
        if jax.default_backend() == "tpu":
            check(fused == plan.n_buckets, f"{fused} fused dispatches for "
                                           f"{plan.n_buckets} buckets")
        got, want = ring(tables, key), xla(tables, key)
        report = {}
        for dt in widths:
            if dt == "float32":
                # the ring adds the n contributions in ring order, XLA's
                # reduce-scatter in its own: a few f32 roundings
                tol, kw = "2^-16 of the value + 2^-16 of the max", dict(
                    rel=2.0 ** -16, floor=2.0 ** -16)
            else:   # exact f32 sums of n bf16 values, rounded once
                tol, kw = "1 bf16 ulp", {}
            bad, diff = _mismatches(got[dt], want[dt], **kw)
            report[dt] = (int(bad), float(diff), tol)
    say("ring vs xla", buckets=plan.n_buckets,
        widths={dt: w for dt, w in widths.items()},
        tpu_custom_call=fused,
        rs_dropped=int((~rs).sum()), ag_dropped=int((~ag).sum()),
        **{f"{dt}_mismatches": r[0] for dt, r in report.items()},
        **{f"{dt}_max_abs_diff": f"{r[1]:.3e}" for dt, r in report.items()},
        **{f"{dt}_tolerance": r[2] for dt, r in report.items()})
    for dt, (bad, _, _) in report.items():
        check(bad == 0, f"ring and xla disagree on the {dt} bucket ({bad})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh trainer and ring "
                         "kernel checks")
    args = ap.parse_args(argv)

    from repro.launch.env import use_compile_cache
    cache = use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.train import TASK_VOCAB
    full = get_config(ARCH)
    task_vocab = min(full.vocab_size, TASK_VOCAB)
    say("device", platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices), compile_cache=cache)
    widths = (f"d_model={full.d_model} heads={full.n_heads} "
              f"kv_heads={full.n_kv_heads} d_ff={full.d_ff} "
              f"vocab={full.vocab_size}")

    if args.chips == 1:
        t = TRAIN
        say("train sizes", arch=ARCH, widths=f"[{widths}]",
            cuts=f"[layers {full.n_layers}->{t['layers']}, "
                 f"task vocab {full.vocab_size}->{task_vocab} token ids]",
            workers=t["workers"], batch=t["batch"], seq=t["seq"],
            steps=t["steps"], p=t["p"], aggregator="rps_model",
            channel="bernoulli")
        train_phase(dataclasses.replace(full, n_layers=t["layers"]),
                    **{k: v for k, v in t.items() if k != "layers"})
        say("train memory", peak=_peak_gib(devices[0]))
        gc.collect()
        s = SERVE
        say("serve sizes", arch=ARCH, widths=f"[{widths}]",
            cuts=f"[layers {full.n_layers}->{s['layers']}]",
            kv_pool_tokens=(s["kv_blocks"] - 1) * s["page"],
            page=s["page"], max_batch=s["max_batch"], chunk=s["chunk"],
            requests=s["requests"], prompt_lens=list(s["prompt_lens"]),
            max_new=list(s["max_new"]))
        serve_phase(dataclasses.replace(full, n_layers=s["layers"]),
                    **{k: v for k, v in s.items() if k != "layers"})
        say("serve memory", peak=_peak_gib(devices[0]))
    else:
        from jax.sharding import Mesh
        m = MESH
        mesh = Mesh(np.array(devices[:4]), ("data",))
        say("mesh sizes", arch=ARCH, widths=f"[{widths}]",
            cuts=f"[layers {full.n_layers}->{m['layers']}, "
                 f"task vocab {full.vocab_size}->{task_vocab} token ids]",
            workers=4, batch=m["batch"], seq=m["seq"], engine="xla")
        mesh_phase(dataclasses.replace(full, n_layers=m["layers"]), mesh,
                   batch=m["batch"], seq=m["seq"])
        ring_phase(mesh)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
