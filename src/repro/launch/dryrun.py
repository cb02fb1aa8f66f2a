import os

from repro.launch import env as env_lib   # no jax import — safe pre-init
env_lib.apply(devices=512)                # both production meshes fit

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, print memory/cost analysis, and emit roofline rows.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --sweep --out results/dryrun
(Forcing 512 host platform devices happens above via the §16 host-perf
preamble, before any jax import — do NOT import this module from
test/bench processes.)
"""
import argparse
import json
import time
import traceback
from collections import Counter
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch import sharding as shlib
from repro.launch.mesh import make_production_mesh, rps_axes_for
from repro.models import build_model
from repro.models.inputs import input_specs, train_specs
from repro.models.registry import kind_sequence
from repro.roofline import HW, analyze_compiled
from repro.roofline.analysis import corrected_totals, measure
from repro.train.trainer import TrainConfig, make_train_setup

DROP_RATE = 0.1          # the paper's headline tolerance

# §Perf hillclimb overrides (set from CLI; None = paper-faithful baseline)
OVERRIDES = {"exchange_dtype": "float32", "exchange_every": 1,
             "capacity_factor": None, "remat_budget": None,
             "bucket_mb": None, "n_buckets": None, "engine": "xla",
             "wire": "f32", "recovery": "renorm",
             "optimizer": "sgd", "state_pack": "f32"}


def pick_microbatch(cfg: ArchConfig, b_local: int, seq: int,
                    budget_bytes: float = 128e6,
                    min_b_micro: int = 1) -> int:
    """Split the per-worker batch so the per-layer remat carry
    (B_micro · S · d · 2B) stays under budget. For FSDP archs the
    per-microbatch batch must stay divisible by the data axis (16) —
    a smaller slice would replicate examples across data shards."""
    per_ex = seq * cfg.d_model * 2
    b_micro = max(min_b_micro, int(budget_bytes // max(per_ex, 1)))
    # round down to a divisor layout: m splits b_local into b_micro chunks
    m = max(1, b_local // b_micro)
    while b_local % m or (b_local // m) % min_b_micro:
        m -= 1
        if m == 1:
            break
    return max(m, 1)


def _stack_specs(specs: Dict, n_rps: int) -> Dict:
    out = {}
    for k, s in specs.items():
        assert s.shape[0] % n_rps == 0, (k, s.shape, n_rps)
        out[k] = jax.ShapeDtypeStruct(
            (n_rps, s.shape[0] // n_rps) + tuple(s.shape[1:]), s.dtype)
    return out


def build_train_lowered(cfg: ArchConfig, shape: ShapeConfig, mesh,
                        kind_counts: Optional[Dict[str, int]] = None,
                        microbatch: Optional[int] = None,
                        grouped: bool = True):
    import dataclasses as _dc
    cfg = _dc.replace(cfg, shard_acts=True,
                      act_batch_axis="data"
                      if cfg.shard_strategy == "fsdp" else None)
    model = build_model(cfg, grouped=grouped, kind_counts=kind_counts)
    rps_axes = rps_axes_for(cfg.rps_mode, mesh)
    n_rps = int(np.prod([mesh.shape[a] for a in rps_axes])) if rps_axes else 1
    fsdp_axis = "data" if cfg.shard_strategy == "fsdp" else None
    b_local = shape.global_batch // max(n_rps, 1)
    budget = OVERRIDES.get("remat_budget") or 128e6
    min_bm = mesh.shape["data"] if cfg.shard_strategy == "fsdp" else 1
    mb = microbatch if microbatch is not None else pick_microbatch(
        cfg, b_local, shape.seq_len, budget_bytes=budget,
        min_b_micro=min_bm)
    agg = cfg.rps_mode if rps_axes else "none"
    if OVERRIDES["capacity_factor"] is not None and cfg.is_moe:
        cfg = _dc.replace(cfg, capacity_factor=OVERRIDES["capacity_factor"])
        model = build_model(cfg, grouped=grouped, kind_counts=kind_counts)
    tcfg = TrainConfig(optimizer=OVERRIDES["optimizer"], lr=0.05,
                       drop_rate=DROP_RATE,
                       aggregator=agg, microbatch=mb,
                       exchange_dtype=OVERRIDES["exchange_dtype"],
                       exchange_every=OVERRIDES["exchange_every"],
                       bucket_mb=OVERRIDES["bucket_mb"],
                       n_buckets=OVERRIDES["n_buckets"],
                       engine=OVERRIDES["engine"],
                       wire=OVERRIDES["wire"],
                       recovery=OVERRIDES["recovery"],
                       state_pack=OVERRIDES["state_pack"])
    init_state, train_step, state_shardings = make_train_setup(
        model, cfg, tcfg, mesh, rps_axes=rps_axes, fsdp_axis=fsdp_axis)

    state_shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    params_shape, opt_shape = state_shapes
    param_sh, pspecs = state_shardings(params_shape)

    def _mirror_sh(tree):
        """Shardings for a state component that mirrors the param tree —
        possibly packed (§16): same structure → the param specs, with
        entries nulled on dims quantization reduced to size 1 (the int8
        per-row scale trees); packed {"q","scale"} wrappers recurse; any
        other shape replicates."""
        from repro.optim import statepack as statepack_lib
        if statepack_lib.is_packed_i8(tree):
            return {"q": _mirror_sh(tree["q"]),
                    "scale": _mirror_sh(tree["scale"])}
        if (jax.tree_util.tree_structure(tree)
                != jax.tree_util.tree_structure(params_shape)):
            return jax.tree.map(lambda l: NamedSharding(mesh, P()), tree)

        def leaf_sh(l, spec, ps):
            ents = list(spec) + [None] * (l.ndim - len(spec))
            ents = [None if l.shape[d] != ps.shape[d] else ents[d]
                    for d in range(l.ndim)]
            return NamedSharding(mesh, P(*ents))

        return jax.tree.map(leaf_sh, tree, pspecs, params_shape)

    if jax.tree_util.tree_leaves(opt_shape):
        # momentum/adam states mirror the param tree -> same shardings
        # (adam splits into m/v components, each mirrored independently)
        if isinstance(opt_shape, dict) and "m" in opt_shape:
            opt_sh = {"m": _mirror_sh(opt_shape["m"]),
                      "v": _mirror_sh(opt_shape["v"]),
                      "t": NamedSharding(mesh, P())}
        else:
            opt_sh = _mirror_sh(opt_shape)
    else:
        opt_sh = opt_shape   # empty pytree (sgd)

    batch = _stack_specs(train_specs(cfg, shape.global_batch, shape.seq_len),
                         max(n_rps, 1))
    worker_axes = rps_axes
    data_axes = ("data",) if fsdp_axis else ()
    bspec = shlib.batch_spec(batch, worker_axes, data_axes)
    batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspec)

    # the ef recovery carries a params-shaped residual (arg 6, after the
    # always-None ch_state slot of these channel-less dryrun configs) —
    # packed at rest under a non-f32 state pack (§16), so its shapes come
    # from init_ef_state, not the raw param tree
    efp = getattr(train_step, "init_ef_state", None) is not None
    ef_shape = jax.eval_shape(train_step.init_ef_state, params_shape) \
        if efp else None
    ef_sh = _mirror_sh(ef_shape) if efp else None
    in_sh = (param_sh, opt_sh, batch_sh, None, None) \
        + ((None, ef_sh) if efp else ())
    out_sh = (param_sh, opt_sh, None) + ((ef_sh,) if efp else ())
    step = jax.jit(train_step,
                   in_shardings=in_sh,
                   out_shardings=out_sh,
                   donate_argnums=train_step.donate_argnums)
    with jax.set_mesh(mesh):      # with_sharding_constraint needs a context
        lowered = step.lower(params_shape, opt_shape, batch,
                             jnp.int32(0), jax.random.PRNGKey(0),
                             *((None, ef_shape) if efp else ()))
    # static exchange cost straight from the plan (DESIGN.md §11): the RPS
    # round is exactly 2 collectives per bucket, volume known pre-compile
    # the plan carries its own wire codec (config_wire absorbed the
    # legacy exchange_dtype knob) — describe() prices the RS leg with it
    from repro.optim import statepack as statepack_lib
    info = {"n_rps": n_rps, "microbatch": mb, "aggregator": agg,
            "state_pack": train_step.state_pack.name,
            # §16 who-owns-what-bytes: global at-rest byte counts of the
            # step's carries (AOT shapes — nothing is materialised)
            "state_bytes": statepack_lib.state_bytes_breakdown(
                params=params_shape, opt_state=opt_shape,
                ef_state=ef_shape),
            "exchange_plan": train_step.plan.describe()
            if train_step.plan is not None else None}
    return lowered, info


def _cache_spec_tree(cache_shape, cfg: ArchConfig, mesh, data_axes):
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    n_model = mesh.shape["model"]
    dax = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes
                                                else None)

    def spec(path, leaf):
        entries = [None] * leaf.ndim
        if leaf.ndim >= 2 and dax is not None \
                and leaf.shape[1] % max(n_data, 1) == 0 and leaf.shape[1] > 1:
            entries[1] = dax
        # shard a head-like or feature dim over model
        for d in range(leaf.ndim - 1, 1, -1):
            if leaf.shape[d] % n_model == 0 and leaf.shape[d] >= n_model:
                entries[d] = "model"
                break
        return P(*entries)

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def _serve_fsdp(cfg: ArchConfig) -> Optional[str]:
    """Serving param sharding: FSDP over data when the bf16 params exceed
    a 16-way-TP HBM budget (mixtral's 283 GB of experts, the 405B/1T archs);
    weights are then layer-gathered transiently (collective-term tradeoff,
    recorded in EXPERIMENTS.md)."""
    if cfg.shard_strategy == "fsdp":
        return "data"
    return "data" if cfg.param_count() * 2 / 16 > 8e9 else None


def build_decode_lowered(cfg: ArchConfig, shape: ShapeConfig, mesh,
                         kind_counts: Optional[Dict[str, int]] = None,
                         grouped: bool = True):
    model = build_model(cfg, grouped=grouped, kind_counts=kind_counts)
    fsdp_axis = _serve_fsdp(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = shlib.param_specs(params_shape, cfg, worker_axes=(),
                               fsdp_axis=fsdp_axis)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    B, S = shape.global_batch, shape.seq_len
    cache_shape = jax.eval_shape(lambda: model.init_cache(B, S))
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cspecs = _cache_spec_tree(cache_shape, cfg, mesh, data_axes)
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs)
    tok = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    tok_spec = P(data_axes if len(data_axes) > 1 else data_axes[0]) \
        if B % n_data == 0 and B > 1 else P()
    tok_sh = {"token": NamedSharding(mesh, tok_spec)}

    def serve_step(params, cache, inputs, pos):
        return model.decode_step(params, cache, inputs, pos)

    step = jax.jit(serve_step,
                   in_shardings=(param_sh, cache_sh, tok_sh, None),
                   out_shardings=(None, cache_sh),
                   donate_argnums=(1,))
    with jax.set_mesh(mesh):
        lowered = step.lower(params_shape, cache_shape, tok, jnp.int32(S - 1))
    return lowered, {"cache_seq": S}


def build_prefill_lowered(cfg: ArchConfig, shape: ShapeConfig, mesh,
                          kind_counts: Optional[Dict[str, int]] = None,
                          grouped: bool = True):
    model = build_model(cfg, grouped=grouped, kind_counts=kind_counts)
    fsdp_axis = _serve_fsdp(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = shlib.param_specs(params_shape, cfg, worker_axes=(),
                               fsdp_axis=fsdp_axis)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    specs = train_specs(cfg, shape.global_batch, shape.seq_len)
    specs.pop("labels")
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dax = data_axes if len(data_axes) > 1 else data_axes[0]
    n_data = int(np.prod([mesh.shape[a] for a in data_axes]))
    in_sh = {k: NamedSharding(
        mesh, P(dax) if s.shape[0] % n_data == 0 else P())
        for k, s in specs.items()}

    step = jax.jit(model.prefill, in_shardings=(param_sh, in_sh))
    with jax.set_mesh(mesh):
        lowered = step.lower(params_shape, specs)
    return lowered, {}


def model_flops_global(cfg: ArchConfig, shape: ShapeConfig) -> float:
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # one token


def builder_for(shape: ShapeConfig):
    return {"train": build_train_lowered,
            "prefill": build_prefill_lowered,
            "decode": build_decode_lowered}[shape.kind]


def run_one(arch: str, shape_name: str, multi_pod: bool,
            probes: bool = True, verbose: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not cfg.runs_shape(shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped (full attention, see DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "2x16x16" if multi_pod else "16x16"
    build = builder_for(shape)

    from repro.telemetry import get_current
    reg = get_current()            # spans when a --telemetry registry is on

    def span(name):
        from contextlib import nullcontext
        return reg.span(name, arch=arch, shape=shape_name,
                        mesh=mesh_desc) if reg is not None else nullcontext()

    t0 = time.time()
    with span("dryrun.lower"):
        lowered, info = build(cfg, shape, mesh)
    with span("dryrun.compile"):
        compiled = lowered.compile()
    t_compile = time.time() - t0
    full = measure(compiled)
    ma = compiled.memory_analysis()

    full_counts = dict(Counter(kind_sequence(cfg)))
    if cfg.family == "audio":
        full_counts["enc"] = cfg.enc_layers
    totals = dict(full)
    # decode flops are cache-read dominated and tiny; probe compiles only
    # pay off for train/prefill (multi-pod reuses the single-pod correction
    # ratio at render time)
    if probes and shape.kind != "decode" and max(full_counts.values()) > 1:
        # probe compiles are UNROLLED (grouped=False): scan bodies are
        # counted once by cost_analysis regardless of trip count, so only
        # unrolled probes make flops(counts) linear in the layer counts.
        base_counts = {k: 1 for k in full_counts}
        probe_meas = {}
        c0 = build(cfg, shape, mesh, kind_counts=base_counts,
                   grouped=False)[0].compile()
        probe_meas["base"] = measure(c0)
        for g in full_counts:
            cc = dict(base_counts)
            cc[g] = 2
            cg = build(cfg, shape, mesh, kind_counts=cc,
                       grouped=False)[0].compile()
            probe_meas[g] = measure(cg)
        totals = corrected_totals(full, probe_meas, base_counts, full_counts)
        totals["coll_by_op"] = full["coll_by_op"]

    report = analyze_compiled(arch, shape_name, mesh_desc,
                              int(np.prod(list(mesh.shape.values()))),
                              totals, model_flops_global(cfg, shape))
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_desc,
           "status": "ok", "compile_s": round(t_compile, 1),
           "memory_analysis": {
               "args_gb": ma.argument_size_in_bytes / 1e9,
               "temp_gb": ma.temp_size_in_bytes / 1e9,
               "output_gb": ma.output_size_in_bytes / 1e9,
               "alias_gb": ma.alias_size_in_bytes / 1e9},
           "info": info,
           "roofline": dataclass_dict(report)}
    if verbose and info.get("state_bytes"):
        sb = info["state_bytes"]
        comps = ", ".join(f"{k}={v/1e9:.2f}GB" for k, v in sb.items()
                          if k != "total" and v)
        print(f"  state bytes [{info.get('state_pack', 'f32')}]: "
              f"total {sb['total']/1e9:.2f} GB ({comps})")
    if verbose and info.get("exchange_plan"):
        ep = info["exchange_plan"]
        print(f"  exchange plan: {ep['n_buckets']} buckets × s={ep['s']} -> "
              f"{ep['collectives_per_round']} RPS collectives/round, "
              f"{ep['wire_bytes_per_round']/1e6:.1f} MB wire/round "
              f"(pad {ep['pad_frac']*100:.1f}%, "
              f"model_packets={ep['model_packets']}, "
              f"wire={ep['wire']}/{ep['recovery']}, "
              f"rs_bytes_ratio={ep['rs_bytes_ratio']:.2f})")
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_desc}] compile {t_compile:.1f}s"
              f" | hbm/dev {report.hbm_per_device/1e9:.2f} GB"
              f" (fits={report.fits})"
              f" | t_comp {report.t_compute*1e3:.2f} ms"
              f" | t_mem {report.t_memory*1e3:.2f} ms"
              f" | t_coll {report.t_collective*1e3:.2f} ms"
              f" -> {report.bottleneck}"
              f" | useful {report.useful_ratio:.2f}")
        print("  memory_analysis:", ma)
        print("  cost_analysis flops/bytes (raw per-dev):",
              full["flops"], full["bytes"])
    return out


def dataclass_dict(r):
    import dataclasses as dc
    d = dc.asdict(r)
    return d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--exchange-dtype", default="float32")
    ap.add_argument("--exchange-every", type=int, default=1)
    ap.add_argument("--capacity", type=float, default=None)
    ap.add_argument("--remat-budget", type=float, default=None)
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="coalesce the exchange into fixed-byte buckets of "
                         "this many MiB (DESIGN.md §11); default: per-leaf")
    ap.add_argument("--buckets", type=int, default=None,
                    help="… or exactly this many size-balanced buckets")
    ap.add_argument("--engine", default="xla",
                    choices=["auto", "xla", "ring"],
                    help="RS+AG lowering (DESIGN.md §12): xla = 2 "
                         "collectives/bucket; ring = fused ring engine "
                         "(1 Pallas dispatch/bucket on TPU); auto = xla")
    ap.add_argument("--wire", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="RS-leg wire codec (DESIGN.md §13); int8 = 4x "
                         "RS compression, per-block scales")
    ap.add_argument("--recovery", default="renorm",
                    choices=["renorm", "scale", "ef"],
                    help="loss-recovery policy (DESIGN.md §13); ef adds "
                         "a params-shaped residual carry to train_step")
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adam"],
                    help="optimizer whose state the dry-run carries "
                         "(adam = the 2x-params m/v pair the §16 pack "
                         "exists to shrink)")
    ap.add_argument("--state-pack", default="f32",
                    choices=["f32", "bf16", "i8", "int8"],
                    help="at-rest trainer-state format (DESIGN.md §16): "
                         "f32 = unpacked bit-identical default; bf16; "
                         "i8 = momentum bf16 + second moments / EF "
                         "residual int8 with per-row scales")
    ap.add_argument("--telemetry", action="store_true",
                    help="record lower/compile phase spans per (arch × "
                         "shape × mesh) into a Chrome trace (DESIGN.md "
                         "§14)")
    ap.add_argument("--telemetry-dir", default=None,
                    help="write summary.json / trace.json here (implies "
                         "--telemetry)")
    args = ap.parse_args()
    OVERRIDES.update(exchange_dtype=args.exchange_dtype,
                     exchange_every=args.exchange_every,
                     capacity_factor=args.capacity,
                     remat_budget=args.remat_budget,
                     bucket_mb=args.bucket_mb,
                     n_buckets=args.buckets,
                     engine=args.engine,
                     wire=args.wire,
                     recovery=args.recovery,
                     optimizer=args.optimizer,
                     state_pack=args.state_pack)

    reg = None
    if args.telemetry or args.telemetry_dir:
        from repro import telemetry as telemetry_lib
        reg = telemetry_lib.Telemetry(out_dir=args.telemetry_dir)
        telemetry_lib.set_current(reg)

    archs = ARCH_IDS if (args.sweep or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.sweep or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(arch, shape, mp,
                                           probes=not args.no_probes))
                except Exception as e:
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": "2x16x16" if mp else "16x16",
                                    "status": f"ERROR: {e}"})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print("wrote", args.out)
    if reg is not None:
        reg.finalize(print_summary=True)
        if args.telemetry_dir:
            print("telemetry ->", args.telemetry_dir)
    n_ok = sum(r.get("status") == "ok" for r in results)
    n_skip = sum("skipped" in str(r.get("status")) for r in results)
    print(f"== {n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} failed / {len(results)} total ==")
    return results


if __name__ == "__main__":
    main()
