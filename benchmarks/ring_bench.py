"""Ring-engine benchmark (DESIGN.md §12): ring vs xla × wire dtype ×
bucket counts — wall-clock, HLO op counts, wire bytes, peak memory.

Sections (all committed to ``BENCH_ring.json``):

  1. **Schedule wall-clock** (subprocess, 8 forced host devices): the
     RS+AG round via ``rps_exchange_plan`` per engine × {f32, bf16 wire}
     × bucket counts. On this CPU host the "ring" engine is the
     interpret ppermute ring — 2(n−1) sequential hops per bucket vs the
     xla engine's 2 fused collectives, so CPU ring wall-clock is
     *expected to lose*; it is reported as-is and labelled by backend.
     The fused single-dispatch TPU lowering (where the ring wins by
     overlapping DMA with the masked accumulate) cannot execute here —
     its lowering is validated in section 2 instead.
  2. **HLO counts** (``tools.check_hlo``): CPU lowering op counts per
     engine (ring: 2(n−1)·buckets collective-permutes, zero RS/AG;
     xla: 2·buckets collectives), and the **TPU export** of the fused
     kernel round: exactly 1 ``tpu_custom_call`` per bucket, zero
     StableHLO collectives — the tentpole claim, checked through the
     real Mosaic pipeline.
  3. **Wire bytes**: ``plan.wire_bytes`` at f32 vs bf16 RS — the bf16
     wire halves the RS leg (the acceptance's RS-bytes claim; AG leg
     unchanged, it moves the payload dtype).
  4. **Peak memory, ~100M simulator step**: compile-level peak
     (args + outputs + temps − donated aliases) for the donated vs
     undonated step — the measured ≥20% reduction from
     ``donate_argnums`` + the global-path copy elimination.
  5. **Simulator exchange wall-clock**: ``rps_exchange_global`` per
     engine/wire on one device (xla einsum vs ring-order scan replay).

Run:  PYTHONPATH=src python -m benchmarks.ring_bench [--quick] \
          [--out BENCH_ring.json]
"""
import argparse
import json
import os
import subprocess
import sys
import textwrap

N_WORKERS = 8
DROP = 0.1
SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
ROOT = os.path.dirname(SRC)


def _tree(n, leaves=6, rows=192, cols=128):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    return {f"p{i}": jnp.asarray(rng.normal(size=(n, rows, cols)),
                                 jnp.float32) for i in range(leaves)}


from benchmarks.exchange_bench import _min_of_batches  # noqa: E402
# (one timing harness for both exchange benches — warmup/min-of-batches
# methodology fixes land in exactly one place)


# ---------------------------------------------------------------------------
# 1. collective-schedule wall-clock + CPU HLO counts (subprocess)
# ---------------------------------------------------------------------------

def bench_schedule(reps, iters, quick):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
        import sys, json
        sys.path.insert(0, %r); sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import plan as plan_lib, rps
        from repro.telemetry.timing import time_fn
        from repro.train.trainer import _shard_map
        from tools import check_hlo

        n, reps, iters = %d, %d, %d
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(0)
        tree = {f"p{i}": jnp.asarray(rng.normal(size=(n, 192, 128)),
                                     jnp.float32) for i in range(6)}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        specs = jax.tree.map(lambda _: P("data"), per_worker)
        key = jax.random.PRNGKey(0)

        def exchange_fn(plan, engine, dt):
            def body(t, k):
                sq = jax.tree.map(lambda x: x[0], t)
                out = rps.rps_exchange_plan(sq, k, %r, "data", plan=plan,
                                            engine=engine, rs_dtype=dt)
                return jax.tree.map(lambda x: x[None], out)
            return jax.jit(_shard_map(body, mesh, (specs, P()), specs,
                                      {"data"}))

        res = {"ms": {}, "hlo": {}}
        for nb in (1, 2):
            plan = plan_lib.make_plan(per_worker, n, n_buckets=nb)
            for engine in ("xla", "ring"):
                for dt, dname in ((jnp.float32, "f32"),
                                  (jnp.bfloat16, "bf16")):
                    name = f"{engine}_b{nb}_{dname}"
                    f = exchange_fn(plan, engine, dt)
                    txt = f.lower(tree, key).as_text()
                    res["hlo"][name] = check_hlo.summarize(txt)
                    res["ms"][name] = time_fn(f, tree, key, reps=reps,
                                              iters=iters, warmup=2) * 1e3
        print("RESULT " + json.dumps(res))
    """) % (N_WORKERS, SRC, ROOT, N_WORKERS, reps, iters, DROP)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a host-device study: the child must never reach for an accelerator
    # (on a chip machine the parent, which has imported jax, holds it)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200 if quick else 2400)
    if r.returncode != 0:
        raise RuntimeError(f"schedule bench subprocess failed:\n"
                           f"{r.stdout}\n{r.stderr}")
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


# ---------------------------------------------------------------------------
# 2. TPU export: the fused-dispatch claim
# ---------------------------------------------------------------------------

def bench_tpu_export(n_buckets=2):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, ROOT)
    from tools import check_hlo
    from repro.kernels import rps_ring
    try:
        from jax import export
    except ImportError:
        return {"available": False}
    n, k, W = N_WORKERS, 2, 256
    S = k * n

    def round_fn(*tables):
        pos = jnp.zeros((1,), jnp.int32)
        left = jnp.full((1,), n - 1, jnp.int32)
        right = jnp.ones((1,), jnp.int32)
        return [rps_ring.ring_bucket_fused(
            t, jnp.ones((S, 1), jnp.bfloat16), jnp.ones((S, 1)),
            jnp.full((S, 1), float(n), jnp.bfloat16), pos, left, right,
            n=n, k=k, mode="model", rs_dtype=jnp.bfloat16,
            collective_id=cid) for cid, t in enumerate(tables)]

    args = [jnp.zeros((S, W), jnp.float32) for _ in range(n_buckets)]
    txt = export.export(jax.jit(round_fn), platforms=("tpu",))(
        *args).mlir_module()
    counts = check_hlo.summarize(txt)
    return {"available": True, "n_buckets": n_buckets,
            "fused_dispatches": counts["tpu_custom_call"],
            "stablehlo_collectives": sum(
                counts[op] for op in ("reduce_scatter", "all_gather",
                                      "collective_permute", "all_reduce")),
            "fused_dispatches_per_bucket":
                counts["tpu_custom_call"] / n_buckets}


# ---------------------------------------------------------------------------
# 3. wire bytes (plan statics)
# ---------------------------------------------------------------------------

def bench_wire_bytes():
    import jax
    from repro.core import plan as plan_lib
    per_worker = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        _tree(N_WORKERS))
    plan = plan_lib.make_plan(per_worker, N_WORKERS, n_buckets=2)
    f32 = plan.wire_bytes("float32")
    bf16 = plan.wire_bytes("bfloat16")
    payload = plan.describe()["payload_bytes"]
    # RS leg = wire_bytes − AG leg (AG always moves the payload dtype)
    rs_f32, rs_bf16 = f32 - payload, bf16 - payload
    return {"wire_bytes_f32": int(f32), "wire_bytes_bf16": int(bf16),
            "rs_leg_bytes_f32": int(rs_f32),
            "rs_leg_bytes_bf16": int(rs_bf16),
            "rs_bytes_ratio_bf16_vs_f32": rs_bf16 / rs_f32}


# ---------------------------------------------------------------------------
# 4. peak memory: donated vs undonated ~100M simulator step (AOT)
# ---------------------------------------------------------------------------

def bench_sim_step_memory(quick):
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import channels as channels_lib
    from repro.core import plan as plan_lib
    from repro.optim import make_optimizer
    from repro.train import simulator as sim_lib

    n = 4
    if quick:
        d_model, n_layers, vocab = 256, 2, 2048
    else:
        d_model, n_layers, vocab = 768, 12, 32768   # ≈ 107M params

    shapes = {"emb": (vocab, d_model), "head": (d_model, vocab)}
    for i in range(n_layers):
        shapes[f"w1_{i}"] = (d_model, 4 * d_model)
        shapes[f"w2_{i}"] = (4 * d_model, d_model)
    n_params = sum(int(np.prod(v)) for v in shapes.values())

    def loss_fn(p, b):
        h = jnp.take(p["emb"], b, axis=0)
        for i in range(n_layers):
            h = jnp.tanh(h @ p[f"w1_{i}"]) @ p[f"w2_{i}"]
        logits = h @ p["head"]
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, b[..., None], -1)[..., 0]
        return jnp.mean(logz - gold)

    def peak(scfg):
        params1 = {k: jax.ShapeDtypeStruct(v, jnp.float32)
                   for k, v in shapes.items()}
        opt = make_optimizer(scfg.optimizer)
        channel = channels_lib.make_channel(scfg.channel, n,
                                            scfg.drop_rate,
                                            s=scfg.n_servers)
        plan = plan_lib.plan_from_config(params1, n, scfg.n_servers,
                                         bucket_mb=scfg.bucket_mb,
                                         n_buckets=scfg.n_buckets)
        step = sim_lib.make_sim_step(loss_fn, scfg, channel, plan, opt)
        params = {k: jax.ShapeDtypeStruct((n,) + v, jnp.float32)
                  for k, v in shapes.items()}
        opt_state = jax.eval_shape(lambda: opt.init(params))
        batch = jax.ShapeDtypeStruct((n, 4, 64), jnp.int32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        ch_state = jax.eval_shape(channel.init_state,
                                  jax.random.PRNGKey(0))
        ma = step.lower(params, opt_state, batch, key,
                        jax.ShapeDtypeStruct((), jnp.float32),
                        ch_state).compile().memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)

    base = sim_lib.SimulatorConfig(n_workers=n, drop_rate=DROP,
                                   aggregator="rps_model",
                                   channel=f"bernoulli:p={DROP}",
                                   n_buckets=2)
    p_on = peak(base)
    p_off = peak(dataclasses.replace(base, donate=False))
    return {"n_params": n_params, "n_workers": n,
            "peak_bytes_donated": int(p_on),
            "peak_bytes_undonated": int(p_off),
            "peak_memory_reduction": 1.0 - p_on / p_off}


# ---------------------------------------------------------------------------
# 5. single-device simulator exchange wall-clock per engine
# ---------------------------------------------------------------------------

def bench_global(reps, iters):
    import jax
    import jax.numpy as jnp
    from repro.core import plan as plan_lib
    from repro.core import rps as rps_lib
    tree = _tree(N_WORKERS)
    key = jax.random.PRNGKey(0)
    per_worker = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
    plan = plan_lib.make_plan(per_worker, N_WORKERS, n_buckets=2)
    out = {}
    for name, engine, dt in (("xla_f32", "xla", jnp.float32),
                             ("ring_f32", "ring", jnp.float32),
                             ("ring_bf16", "ring", jnp.bfloat16)):
        fn = jax.jit(lambda t, k, e=engine, d=dt:
                     rps_lib.rps_exchange_global(
                         t, k, DROP, N_WORKERS, mode="model", plan=plan,
                         engine=e, rs_dtype=d))
        out[name] = _min_of_batches(fn, (tree, key), reps, iters) * 1e6
    return out


def run_bench(quick=False, out=None):
    import jax
    reps, iters = (2, 4) if quick else (5, 10)
    sched = bench_schedule(reps, max(3, iters // 2), quick)
    tpu = bench_tpu_export()
    wire = bench_wire_bytes()
    mem = bench_sim_step_memory(quick)
    glob_us = bench_global(reps, iters)

    result = {
        "backend": jax.default_backend(),
        "n_workers": N_WORKERS, "drop_rate": DROP,
        "schedule_ms": {k: round(v, 3) for k, v in sched["ms"].items()},
        "schedule_hlo": sched["hlo"],
        "tpu_export": tpu,
        "wire_bytes": wire,
        "sim_step_memory": mem,
        "simulator_exchange_us": {k: round(v, 1)
                                  for k, v in glob_us.items()},
        "quick": quick,
        "note": (
            "schedule_ms is measured on forced-host CPU devices, where "
            "the 'ring' engine is the interpret ppermute ring (2(n-1) "
            "sequential hops/bucket) and is expected to trail the xla "
            "engine's single fused collectives — wall-clock reported "
            "as-is, labelled by backend. The fused one-dispatch-per-"
            "bucket TPU lowering (where the ring overlaps RDMA with the "
            "masked accumulate) is validated via jax.export in "
            "tpu_export. rs_bytes_ratio_bf16_vs_f32 = 0.5: the bf16 "
            "wire halves the RS leg. peak_memory_reduction is the "
            "donate_argnums + copy-elimination win on the ~100M-param "
            "simulator step (AOT memory_analysis)."),
    }
    if out:                        # write before asserting: a failing run
        with open(out, "w") as f:  # still ships its data (CI artifact)
            json.dump(result, f, indent=1)
        print("wrote", out)
    # acceptance guards
    assert abs(wire["rs_bytes_ratio_bf16_vs_f32"] - 0.5) < 1e-6, wire
    assert mem["peak_memory_reduction"] >= 0.20, mem
    if tpu.get("available"):
        assert tpu["fused_dispatches_per_bucket"] == 1.0, tpu
        assert tpu["stablehlo_collectives"] == 0, tpu
    for nb in (1, 2):
        h = sched["hlo"][f"ring_b{nb}_f32"]
        assert h["collective_permute"] == 2 * (N_WORKERS - 1) * nb, h
        assert h["reduce_scatter"] == 0 and h["all_gather"] == 0, h
        hx = sched["hlo"][f"xla_b{nb}_f32"]
        assert hx["reduce_scatter"] == nb and hx["all_gather"] == nb, hx
    return result


def run(csv_rows, quick=True, engine=None):
    """benchmarks.run entry (engine accepted for CLI uniformity; this
    bench always measures both engines)."""
    res = run_bench(quick=quick)
    print(json.dumps(res, indent=1))
    for k, v in res["schedule_ms"].items():
        csv_rows.append((f"ring_schedule_{k}", v * 1e3,
                         f"backend={res['backend']}"))
    csv_rows.append(("ring_mem_reduction",
                     res["sim_step_memory"]["peak_memory_reduction"] * 100,
                     f"n_params={res['sim_step_memory']['n_params']}"))
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (small model, few reps)")
    ap.add_argument("--out", default="BENCH_ring.json")
    args = ap.parse_args()
    res = run_bench(quick=args.quick, out=args.out)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
