"""Mesh trainer: stacked-replica data parallelism with RPS aggregation.

Layout (DESIGN.md §4/§5):

  rps_model archs — every RPS worker holds a full TP-sharded model replica.
    params: (n_rps, …) with the worker dim over the RPS axes (("data",) on a
    single pod, ("pod","data") across pods) and tensor-parallel dims over
    "model". Step = local SGD per worker (elementwise over the stacked dim)
    followed by the drop-masked RS+AG *model* exchange.

  rps_grad archs (llama3-405b, kimi-k2) — replicas only across pods (the
    unreliable DCN direction); within a pod, params are FSDP-sharded over
    "data" + TP over "model". Step = per-pod gradients, drop-tolerant
    *gradient* exchange across pods (grad_renorm mode), then the update.
    On a single pod n_rps = 1 and the exchange degenerates to local — ICI is
    reliable (DESIGN.md §5).

The exchange runs in a fully-manual ``shard_map`` over *all* mesh axes and
executes an :class:`repro.core.plan.ExchangePlan` computed **once at
setup** (DESIGN.md §11): the param pytree is coalesced into buckets —
2 collectives per bucket per round instead of 2 per leaf — with TP-sharded
leaves in model-dim-preserving buckets of their own. The default
(``bucket_mb``/``n_buckets`` unset) is the per-leaf plan, bit-identical to
the seed lowering; a bucketed plan is also the packetisation unit and draws
per-bucket drop masks (``Channel.sample_packets``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import channels as channels_lib
from repro.configs.base import ArchConfig
from repro.core import plan as plan_lib
from repro.core import rps as rps_lib
from repro.core import wire as wire_lib
from repro.launch import sharding as shlib
from repro.models.registry import Model
from repro.optim import make_optimizer
from repro.optim import statepack as statepack_lib


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd"                 # paper-faithful default
    lr: float = 0.05
    drop_rate: float = 0.0
    aggregator: str = "rps_model"          # rps_model | rps_grad |
                                           # allreduce_model | allreduce_grad
                                           # | none
    microbatch: int = 1                    # grad-accumulation splits
    exchange_dtype: str = "float32"        # RS accumulation dtype
    exchange_every: int = 1                # steps between exchanges
                                           # (>1 = local-SGD variant,
                                           # beyond-paper)
    channel: Optional[str] = None          # repro.channels spec for the
                                           # drop process (DESIGN.md §9);
                                           # None = i.i.d. Bernoulli
                                           # (drop_rate), the seed behaviour
                                           # — and the seed train_step
                                           # signature. A channel spec makes
                                           # train_step carry channel state:
                                           # see make_train_setup.
    corruption: Optional[str] = None       # corruption process (DESIGN.md
                                           # §17): a spec over
                                           # bitflip/scale/signflip/collude
                                           # ("signflip:frac=0.1",
                                           # "collude:gamma=10") composed
                                           # onto the channel; None (with
                                           # byzantine_frac 0) corrupts
                                           # nothing — bit-identical.
    byzantine_frac: float = 0.0            # fraction of colluding workers
                                           # (⌊byzantine_frac·n⌋ lowest
                                           # ids corrupt every packet);
                                           # alone it selects the
                                           # "collude" attack.
    n_servers: Optional[int] = None        # parameter-server blocks s
                                           # (DESIGN.md §10); None = n_rps,
                                           # the paper's square layout
                                           # (bit-identical to the seed).
    bucket_mb: Optional[float] = None      # ExchangePlan coalescing
                                           # (DESIGN.md §11): fixed-byte
                                           # buckets of this many MiB.
    n_buckets: Optional[int] = None        # … or exactly this many size-
                                           # balanced buckets. Both None =
                                           # the per-leaf legacy plan,
                                           # bit-identical to the seed.
    engine: str = "auto"                   # RS+AG lowering (DESIGN.md
                                           # §12): "xla" = psum_scatter +
                                           # all_gather per bucket (seed
                                           # schedule); "ring" = fused
                                           # ring engine (one Pallas
                                           # dispatch per bucket on TPU,
                                           # interpret ppermute ring
                                           # elsewhere); "auto" = xla on
                                           # every backend (DESIGN §12).
    wire: str = "f32"                      # RS-leg codec (DESIGN.md §13):
                                           # "f32" bit-identical default,
                                           # "bf16" (absorbs a bf16
                                           # exchange_dtype), "int8"
                                           # stochastic-rounding with
                                           # per-block scales.
    recovery: str = "renorm"               # loss recovery (DESIGN.md
                                           # §13): "renorm" = paper
                                           # Algorithm 1, "scale" =
                                           # unbiased 1/(1−p) zero-fill,
                                           # "ef" = error-feedback
                                           # residual — train_step then
                                           # carries a params-shaped
                                           # residual (see
                                           # make_train_setup).
    schedule: str = "sync"                 # round scheduling (DESIGN.md
                                           # §15): "sync" = every bucket
                                           # ships at the iteration
                                           # barrier (seed semantics,
                                           # bit-identical default);
                                           # "async" = buckets ship in
                                           # reverse-layer order against
                                           # per-bucket slack budgets —
                                           # the plan dispatches in
                                           # ship_order with alternating
                                           # ring comm slots, and late
                                           # packets are written off as
                                           # dropped-with-recovery
                                           # (counted in the telemetry).
    compute_ms: Any = None                 # async backward cost model:
                                           # modelled backward duration
                                           # the per-bucket readiness
                                           # times derive from; None
                                           # (with schedule="async") =
                                           # 0.8 × the channel deadline
                                           # when it has one, else 1.0.
                                           # "auto" starts from that
                                           # provisional model — callers
                                           # time the real backward and
                                           # substitute via
                                           # plan.with_ready_ms (§16).
    state_pack: str = "f32"                # at-rest trainer-state format
                                           # (DESIGN.md §16): "f32" =
                                           # unpacked (bit-identical
                                           # default); "bf16" = all
                                           # optimizer/EF buffers bf16;
                                           # "i8" = momentum bf16, Adam
                                           # second moments + EF residual
                                           # int8 with per-row f32 scales
                                           # and stochastic rounding on
                                           # write. Packed buffers are the
                                           # step's carries (donated);
                                           # params are never packed.
    telemetry: bool = False                # exchange telemetry (DESIGN.md
                                           # §14): metrics gain a
                                           # "telemetry" sub-dict (per-link
                                           # delivery counts, drop rates,
                                           # grad norm), computed at STEP
                                           # level from the same mask draw
                                           # the exchange consumes — taps
                                           # cannot cross the shard_map /
                                           # lax.cond trace boundaries the
                                           # exchange runs under. Primary
                                           # outputs stay bit-identical.


def _is_model_mode(agg: str) -> bool:
    return agg.endswith("_model")


def _shard_map(f, mesh, in_specs, out_specs, axis_names):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names)


def _on_mesh(specs: Any, mesh: Mesh) -> Any:
    """Param PartitionSpecs with the axes ``mesh`` lacks dropped: on a
    ("data",)-only mesh the tensor-parallel ("model") dims are replicated,
    each worker holding its whole replica."""
    def keep(ent):
        if ent is None:
            return None
        axes = tuple(a for a in (ent if isinstance(ent, tuple) else (ent,))
                     if a in mesh.axis_names)
        return axes if len(axes) > 1 else (axes[0] if axes else None)

    return jax.tree.map(lambda sp: P(*(keep(e) for e in sp)), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _local_struct(params_shape: Any, especs: Any, mesh: Mesh) -> Any:
    """Per-device (manual-region) shapes of a sharded param tree: each
    spec'd dim divided by its mesh-axis extent. This is the view the
    fully-manual exchange body sees, and the tree the ExchangePlan is
    built from."""
    def loc(sds, spec):
        entries = list(spec) + [None] * (len(sds.shape) - len(spec))
        dims = []
        for d, ent in zip(sds.shape, entries):
            if ent is None:
                dims.append(int(d))
                continue
            axes = ent if isinstance(ent, tuple) else (ent,)
            div = int(np.prod([mesh.shape[a] for a in axes]))
            dims.append(int(d) // div)
        return jax.ShapeDtypeStruct(tuple(dims), sds.dtype)

    return jax.tree.map(loc, params_shape, especs)


def make_train_setup(model: Model, cfg: ArchConfig, tcfg: TrainConfig,
                     mesh: Mesh, *, rps_axes: Tuple[str, ...],
                     fsdp_axis: Optional[str] = None):
    """Returns (init_state, train_step, shardings) for the given mesh.

    init_state(key) -> (params, opt_state): worker-stacked, identical
    replicas (the paper initialises all x_1^(i) equal).
    train_step(params, opt_state, batch, step, key) -> (params, opt_state,
    metrics). batch has leading worker dim n_rps.

    With ``tcfg.channel`` set (and an rps aggregator — baselines ignore
    channels), the drop masks come from the configured ``repro.channels``
    channel instead of the i.i.d. Bernoulli draw, and the
    step carries the channel state: ``train_step(params, opt_state, batch,
    step, key, ch_state) -> (params, opt_state, metrics, ch_state)`` with
    the initial state from ``train_step.init_channel_state(key)`` (the
    channel itself is exposed as ``train_step.channel``). Channel state is
    replicated — every device evolves it identically from the shared key,
    like the masks themselves.

    With ``tcfg.recovery == "ef"`` (DESIGN.md §13) the step additionally
    carries the error-feedback residual — a params-shaped, params-sharded
    pytree: ``train_step(params, opt_state, batch, step, key, ch_state,
    ef_state)`` (``ch_state`` stays ``None`` for channel-less configs)
    returning ``(…, ef_state)`` last; the zero initial residual comes
    from ``train_step.init_ef_state(params)``. Both carries are listed in
    ``train_step.donate_argnums``. Under a non-f32 ``tcfg.state_pack``
    (§16) the residual is carried *packed* (bf16, or int8 q + per-row
    scale trees) and decoded/re-encoded only inside exchanging rounds;
    the resolved pack is exposed as ``train_step.state_pack``.

    The exchange layout is precomputed here (``train_step.plan``, an
    :class:`repro.core.plan.ExchangePlan`): param specs and local shapes
    are derived once via ``jax.eval_shape`` — nothing shape-related runs
    inside the traced step body.
    """
    n_rps = 1
    for a in rps_axes:
        n_rps *= mesh.shape[a]
    n_servers = n_rps if tcfg.n_servers is None else int(tcfg.n_servers)
    pack = statepack_lib.make_state_pack(getattr(tcfg, "state_pack", None))
    opt = make_optimizer(tcfg.optimizer, state_pack=pack.name)
    channel = channels_lib.make_channel(
        tcfg.channel, n_rps, tcfg.drop_rate, s=tcfg.n_servers,
        corruption=channels_lib.make_corruption(
            getattr(tcfg, "corruption", None),
            getattr(tcfg, "byzantine_frac", 0.0) or None))
    # only rps aggregators consume masks (same gate as the simulator's
    # rps_agg) — a channel configured alongside an allreduce/none baseline
    # keeps the seed 5-arg signature and samples nothing
    rps_agg = tcfg.aggregator.startswith("rps")
    stateful = tcfg.channel is not None and rps_agg
    use_ef = rps_agg and tcfg.recovery == "ef"
    async_mode = rps_agg and tcfg.schedule == "async"
    corruption = getattr(channel, "corruption", None) if rps_agg else None
    if use_ef and corruption is not None:
        raise ValueError(
            "corruption with recovery='ef' is unsupported: the EF residual "
            "telescopes an *honest* sender's codec error (DESIGN.md §17); "
            "use a robust recovery (median/trimmed/clip) instead")
    # the scale divisor prices the channel's stationary marginal, not the
    # raw drop_rate knob (they differ for GE/hetero/trace channels)
    recovery = wire_lib.make_recovery(
        tcfg.recovery, p=channel.effective_p()) if rps_agg else None

    def init_state(key):
        p1 = model.init(key)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_rps,) + x.shape).copy(), p1)
        return stacked, opt.init(stacked)

    # ---- static setup: specs, local shapes, the ExchangePlan --------------
    # (hoisted out of the traced step — the seed recomputed eval_shape +
    # param_specs twice per trace: once in train_step, again in _exchange)
    params_shape = jax.eval_shape(init_state, jax.random.PRNGKey(0))[0]
    especs = _on_mesh(shlib.param_specs(
        params_shape, cfg, worker_axes=rps_axes, fsdp_axis=fsdp_axis,
        stacked=True), mesh)
    plan = None
    if rps_agg:
        local_shape = _local_struct(params_shape, especs, mesh)
        bucketing = tcfg.bucket_mb is not None or tcfg.n_buckets is not None
        mdims = jax.tree.map(
            lambda d: None if d is None else d + 1,        # + stacked dim
            shlib.model_dims(params_shape, cfg, stacked=True),
            is_leaf=lambda x: x is None) if bucketing else None
        from repro.train.simulator import resolve_compute_ms
        plan = plan_lib.plan_from_config(
            local_shape, n_rps, n_servers,
            bucket_mb=tcfg.bucket_mb, n_buckets=tcfg.n_buckets,
            model_dims=mdims, engine=tcfg.engine,
            wire=wire_lib.config_wire(tcfg.wire, tcfg.exchange_dtype),
            recovery=tcfg.recovery, schedule=tcfg.schedule,
            compute_ms=resolve_compute_ms(tcfg, channel))
    slack = None
    if async_mode and plan is not None:
        # static per-bucket budgets (DESIGN.md §15); channels without a
        # latency model ignore the values (sync-identical fallback)
        deadline = getattr(channel, "deadline_ms", None)
        slack = plan.slack_ms(float(deadline)) if deadline is not None \
            else np.zeros(plan.n_buckets, np.float64)

    # ---- shardings --------------------------------------------------------
    def state_shardings(params_shape):
        pspecs = _on_mesh(shlib.param_specs(
            params_shape, cfg, worker_axes=rps_axes, fsdp_axis=fsdp_axis,
            stacked=True), mesh)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs), pspecs

    def _exchange(tree, key, mode=None, masks=None, ef=None, cmask=None):
        """Drop-masked exchange over the RPS axes (stacked worker dim 0).

        ``mode=None`` derives the exchange mode from the aggregator (None
        is the *only* sentinel — the seed code did ``mode = mode or rmode``,
        which silently overwrote any falsy caller value). ``masks`` is an
        optional precomputed pair from a channel — legacy shared ``(n, s)``
        or per-bucket ``(n_buckets, n, s)`` — replicated into the manual
        region; None keeps the in-body draw the plan prescribes,
        bit-identical to the seed path for the default per-leaf plan.
        ``ef`` is the EF residual (params-shaped, params-sharded); when
        given the return is ``(tree, new_ef)``. ``cmask`` is the
        replicated step-level corruption-mask draw (§17) consumed
        alongside the channel's corruption process.

        Fully-manual shard_map over *all* mesh axes with the param
        PartitionSpecs as in_specs: every leaf arrives as its local shard,
        the RS+AG runs over the RPS axes only, and the TP/FSDP dims are
        plain local data. (A partial-manual region left the model dim to
        shardy, which de-sharded it — full params in f32 per device.)
        The body executes the precomputed plan: exactly
        ``2 × plan.n_buckets`` collectives per round."""
        if tcfg.aggregator == "none" or n_rps == 1:
            return tree if ef is None else (tree, ef)
        if tcfg.aggregator.startswith("allreduce"):
            # pinned to the param shardings: left to the compiler, the
            # broadcast mean comes out replicated — every device holding
            # all n replicas (n x the model per chip)
            out = jax.tree.map(lambda x, sp: jax.lax.with_sharding_constraint(
                jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True),
                                 x.shape), NamedSharding(mesh, sp)),
                tree, especs)
            return out if ef is None else (out, ef)
        if mode is None:
            mode = ("model" if _is_model_mode(tcfg.aggregator)
                    else "grad_renorm")
        has_masks, has_ef = masks is not None, ef is not None
        has_cmask = cmask is not None

        def body(t, key, *rest):
            it = iter(rest)
            m = next(it) if has_masks else None
            e = next(it) if has_ef else None
            cm = next(it) if has_cmask else None
            ring_ids = None
            if rps_lib.resolve_engine(tcfg.engine) == "ring":
                # the fused kernel RDMAs by *logical* device id — derive
                # the ring neighbours from the full mesh layout (the RPS
                # axes vary, TP/FSDP coords stay fixed)
                from repro.kernels.rps_ring import logical_ring_ids
                ring_ids = logical_ring_ids(
                    rps_axes, mesh_axis_names=mesh.axis_names,
                    mesh_shape=dict(mesh.shape))
            return rps_lib.rps_exchange_plan(
                t, key, tcfg.drop_rate, rps_axes, plan=plan, mode=mode,
                masks=m, rs_dtype=jnp.dtype(tcfg.exchange_dtype),
                engine=tcfg.engine, ring_ids=ring_ids,
                recovery=recovery, ef_state=e,
                corruption=corruption, corrupt_masks=cm)

        args = [tree, key]
        in_specs = [especs, P()]
        if has_masks:
            args.append(masks)
            in_specs.append((P(), P()))
        if has_ef:
            args.append(ef)
            in_specs.append(especs)
        if has_cmask:
            # replicated like the drop masks — every device holds the
            # globally-known corruption draw
            args.append(cmask)
            in_specs.append(P())
        out_specs = (especs, especs) if has_ef else especs
        fn = _shard_map(body, mesh, tuple(in_specs), out_specs,
                        set(mesh.axis_names))
        return fn(*args)

    # ---- the step ---------------------------------------------------------
    def train_step(params, opt_state, batch, step, key, ch_state=None,
                   ef_state=None):
        if use_ef and ef_state is None:
            raise ValueError("recovery='ef' carries a residual: pass "
                             "ef_state (train_step.init_ef_state(params) "
                             "for the zero start)")
        # XLA leaves while-loop carries (the grad accumulator) replicated
        # without explicit annotations — pin grads to the param shardings
        # (especs precomputed above, not re-derived per trace).
        def _pin(tree):
            if not cfg.shard_acts:
                return tree
            return jax.tree.map(
                lambda x, sp: jax.lax.with_sharding_constraint(x, sp),
                tree, especs)

        def worker_loss(p, b):
            loss, metrics = model.loss(p, b)
            return loss, metrics

        # spmd_axis_name shards every vmapped intermediate's worker dim
        # over the RPS axes — without it the scanned activations compile
        # replicated (16x memory; observed on mixtral before the fix)
        spmd = (rps_axes if len(rps_axes) > 1 else rps_axes[0]) \
            if rps_axes else None
        vmapped = jax.vmap(worker_loss, spmd_axis_name=spmd)

        def total_loss(ps, bs):
            losses, metrics = vmapped(ps, bs)
            return jnp.sum(losses), metrics

        if tcfg.microbatch > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((x.shape[0], tcfg.microbatch,
                                     x.shape[1] // tcfg.microbatch)
                                    + x.shape[2:]), batch)

            def acc(g_acc, b):
                (l, _), g = jax.value_and_grad(total_loss, has_aux=True)(
                    params, b)
                g_acc = jax.tree.map(jnp.add, g_acc, _pin(g))
                return _pin(g_acc), l

            # accumulate in the param dtype: the f32 buffer would be an
            # extra params-sized allocation; plain-SGD + model averaging is
            # robust to bf16 grad accumulation (paper recipe)
            g0 = _pin(jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                                   params))
            grads, losses = jax.lax.scan(
                acc, g0, jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), mb))
            grads = jax.tree.map(lambda g: g / tcfg.microbatch, grads)
            loss = jnp.mean(losses)
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                total_loss, has_aux=True)(params, batch)
            grads = _pin(grads)

        masks = None
        late = None
        if stateful or async_mode:
            # channel time advances every step, exchanged or not (a trace
            # cursor / burst state tracks wall-clock iterations); a
            # packetised plan draws one mask entry per bucket column.
            # Async draws at step level even for the default Bernoulli
            # channel (slack arbitration needs the channel object); a
            # channel-less config keeps ch_state = None un-carried.
            if async_mode:
                rs, ag, late, ch_state = channel.sample_async(
                    key, ch_state, slack)
            elif plan is not None and plan.per_bucket_masks:
                rs, ag, ch_state = channel.sample_packets(
                    key, ch_state, plan.n_buckets)
            else:
                rs, ag, ch_state = channel.sample(key, ch_state)
            masks = (rs, ag)
        cmask = None
        if corruption is not None:
            # corruption-mask draw at step level, same shared key as the
            # drop masks (tag-separated domains, §17); replicated into
            # the manual region like the masks themselves
            nb = None
            if masks is not None and masks[0].ndim == 3:
                nb = int(masks[0].shape[0])   # match the packet draw
            elif plan is not None and plan.per_bucket_masks:
                nb = plan.n_buckets
            cmask = channel.sample_corruption(key, n_buckets=nb)

        tel_stats = None
        if tcfg.telemetry and rps_agg and n_rps > 1:
            # step-level counters (DESIGN.md §14): the exchange itself runs
            # under shard_map (and lax.cond for exchange_every > 1), whose
            # trace boundaries taps cannot cross — so derive the stats here
            # from the SAME mask draw the exchange consumes: the channel's
            # step-level draw when stateful, else the identical
            # deterministic sample_masks(key, …) replay of the in-body
            # default (both are pure functions of the shared step key).
            from repro.telemetry import counters as counters_lib
            if masks is not None:
                rs_t, ag_t = masks
            else:
                rs_t, ag_t = rps_lib.sample_masks(
                    key, n_rps, tcfg.drop_rate, plan.s,
                    n_buckets=plan.n_buckets if plan.per_bucket_masks
                    else None)
            tel_stats = counters_lib.mask_step_stats(rs_t, ag_t)
            tel_stats["grad_norm"] = counters_lib.global_norm(grads)
            if late is not None:
                # §15 lateness bundle from the same deadline arbitration
                # the exchange consumed
                tel_stats.update(counters_lib.staleness_stats(
                    late["rs"], late["ag"]))
            if cmask is not None:
                # §17 contamination bundle from the same corruption draw
                # the exchange consumed
                tel_stats.update(counters_lib.corruption_stats(
                    cmask, rs_t))
            if tcfg.exchange_every > 1:
                # skipped rounds consume no masks: zero delivered AND
                # offered so the estimator skips them (offered == 0);
                # lateness/corruption likewise — nothing was shipped
                live = jnp.asarray(step % tcfg.exchange_every == 0,
                                   jnp.int32)
                for k in ("rs_link_delivered", "ag_link_delivered",
                          "link_offered", "rs_link_late", "ag_link_late",
                          "late_frac", "rs_link_corrupt", "corrupt_frac"):
                    if k in tel_stats:
                        tel_stats[k] = tel_stats[k] * live

        lr = jnp.float32(tcfg.lr)
        ef = ef_state if use_ef else None
        # per-step derived keys: stochastic rounding of packed state (§16;
        # dead code — eliminated — under the f32 identity pack)
        opt_key = jax.random.fold_in(key, 0x70616b)     # "pak"
        ef_key = jax.random.fold_in(key, 0x6566)        # "ef"

        def exchange_ef(tree, mode, e_packed):
            # decode the at-rest residual around the exchange only — a
            # skipped round (the lax.cond false branch below) must pass
            # the packed residual through bitwise untouched, never
            # re-quantize it
            e = statepack_lib.unpack_tree(e_packed, pack.ef_format)
            out, e_new = _exchange(tree, key, mode, masks, e, cmask)
            return out, statepack_lib.pack_tree(e_new, pack.ef_format,
                                                key=ef_key, tap="ef")

        if _is_model_mode(tcfg.aggregator) or tcfg.aggregator == "none":
            # local step, then model exchange (Algorithm 1)
            new_params, opt_state = opt.update(grads, opt_state, params, lr,
                                               key=opt_key)
            if tcfg.exchange_every > 1:
                if use_ef:      # skipped steps leave the residual alone
                    new_params, ef_state = jax.lax.cond(
                        step % tcfg.exchange_every == 0,
                        lambda te: exchange_ef(te[0], None, te[1]),
                        lambda te: te, (new_params, ef))
                else:
                    new_params = jax.lax.cond(
                        step % tcfg.exchange_every == 0,
                        lambda t: _exchange(t, key, None, masks,
                                            cmask=cmask),
                        lambda t: t, new_params)
            elif use_ef:
                new_params, ef_state = exchange_ef(new_params, None, ef)
            else:
                new_params = _exchange(new_params, key, None, masks,
                                       cmask=cmask)
        else:
            # gradient exchange, then step
            gmode = "grad_renorm" if tcfg.aggregator == "rps_grad" else None
            if use_ef:
                grads, ef_state = exchange_ef(grads, gmode, ef)
            else:
                grads = _exchange(grads, key, gmode, masks, cmask=cmask)
            new_params, opt_state = opt.update(grads, opt_state, params, lr,
                                               key=opt_key)
        mloss = loss / n_rps
        out_metrics = {"loss": mloss,
                       "lr": lr,
                       **{k: jnp.mean(v) for k, v in
                          (metrics or {}).items()}}
        if tel_stats is not None:
            out_metrics["telemetry"] = tel_stats
        out = (new_params, opt_state, out_metrics)
        if stateful:
            out = out + (ch_state,)
        if use_ef:
            out = out + (ef_state,)
        return out

    train_step.channel = channel
    train_step.init_channel_state = channel.init_state
    train_step.plan = plan
    train_step.recovery = recovery
    train_step.state_pack = pack
    # zero EF residual, shaped like the stacked params (§13), carried at
    # rest in the state pack's EF format (§16 — zeros quantize exactly)
    train_step.init_ef_state = (
        lambda params: statepack_lib.pack_tree(
            jax.tree.map(jnp.zeros_like, params), pack.ef_format)) \
        if use_ef else None
    # donation hint for jit callers (launch/dryrun.py and the benches):
    # params + opt_state always, the channel-state / EF-residual carries
    # when present — without it every step double-buffers the whole
    # sharded model
    train_step.donate_argnums = (0, 1) + ((5,) if stateful else ()) \
        + ((6,) if use_ef else ())
    return init_state, train_step, state_shardings
