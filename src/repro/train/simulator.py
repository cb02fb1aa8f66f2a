"""Single-device n-worker simulation harness.

Reproduces the paper's §6 experiments at the paper's scale (n = 16 workers)
without a cluster: worker replicas live on a stacked leading dim, the
forward/backward is vmapped, and the aggregation uses the *global-view*
exchange (`rps_exchange_global`) — bit-identical math to the collective path
(tests assert this), so convergence curves measured here transfer.

Aggregators (matching the paper's comparisons):
  rps_model       — Algorithm 1 (model averaging, drop-tolerant)   [Fig 4]
  rps_grad        — naive gradient averaging under drops           [Fig 5]
  allreduce_model / allreduce_grad — reliable baselines (p = 0)
  local           — no communication at all (sanity lower bound)

The drop process is pluggable (``SimulatorConfig.channel``, DESIGN.md §9):
any ``repro.channels`` spec — bursty Gilbert–Elliott, per-link
heterogeneous, deadline/straggler, or a replayed netsim trace — drives the
same exchanges; the default (``channel=None``) is the paper's i.i.d.
Bernoulli(drop_rate) process, bit-identical to the seed code.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import channels as channels_lib
from repro import telemetry as telemetry_lib
from repro.core import plan as plan_lib
from repro.core import rps as rps_lib
from repro.core import wire as wire_lib
from repro.optim import make_optimizer
from repro.optim import statepack as statepack_lib
from repro.telemetry import counters as counters_lib
from repro.telemetry import taps as taps_lib
from repro.telemetry import timing as timing_lib


@dataclasses.dataclass(frozen=True)
class SimulatorConfig:
    n_workers: int = 16
    drop_rate: float = 0.0
    aggregator: str = "rps_model"
    optimizer: str = "sgd"          # paper: plain SGD, no momentum/decay
    lr: float = 0.05
    steps: int = 200
    batch_size: int = 32            # paper: 32/worker
    seed: int = 0
    warmup: int = 0                 # gradual-warmup steps (paper recipe)
    eval_every: int = 10
    exchange_every: int = 1         # >1: local-SGD variant (beyond-paper)
    channel: channels_lib.ChannelSpec = None
    # drop-process model: a repro.channels spec string
    # ("ge:p_bad=0.3,burst=8", "trace:lam=8000,prio=0.8", ...) or a built
    # Channel; None = i.i.d. Bernoulli(drop_rate), the seed behaviour.
    corruption: channels_lib.CorruptionSpec = None
    # corruption process (DESIGN.md §17): a spec string over
    # ("bitflip", "scale", "signflip", "collude") —
    # e.g. "signflip:frac=0.1" or "collude:gamma=10" — composed onto the
    # channel; None (with byzantine_frac 0) corrupts nothing,
    # bit-identical to the seed.
    byzantine_frac: float = 0.0
    # fraction of colluding workers (⌊byzantine_frac·n⌋ lowest ids
    # corrupt every packet they send); overlays the spec's own field and
    # alone (corruption=None) selects the "collude" attack.
    n_servers: Optional[int] = None
    # parameter-server blocks s (DESIGN.md §10): the model is partitioned
    # into s blocks with round-robin worker owners; None = n_workers, the
    # paper's square layout (bit-identical to the seed).
    bucket_mb: Optional[float] = None
    # ExchangePlan coalescing (DESIGN.md §11): fixed-byte buckets of this
    # many MiB — buckets are also the packetisation unit (per-bucket mask
    # draws). Both bucket knobs None = the per-leaf legacy plan,
    # bit-identical to the seed.
    n_buckets: Optional[int] = None
    # … or exactly this many size-balanced buckets.
    engine: str = "auto"
    # exchange-arithmetic engine (DESIGN.md §12): "xla"/"auto" = the seed
    # f32 einsum math (bit-identical); "ring" replays the ring engine's
    # wire arithmetic — contributions summed in ring order in
    # exchange_dtype — so bf16-wire convergence is measurable on one
    # device.
    exchange_dtype: str = "float32"
    # RS wire/accumulation dtype for engine="ring" (bf16 = half the RS
    # bytes on the real fabric; here it makes the simulator's arithmetic
    # match that wire). Absorbed by the wire pipeline below: a non-f32
    # ``wire`` wins; a non-f32 exchange_dtype with wire unset selects
    # the matching linear codec.
    wire: str = "f32"
    # RS-leg codec (DESIGN.md §13): "f32" (bit-identical default),
    # "bf16" (half the RS bytes), "int8" (quarter — stochastic-rounding
    # quantisation with per-block scales).
    recovery: str = "renorm"
    # loss-recovery policy (DESIGN.md §13): "renorm" = paper Algorithm 1
    # (divide by the received count), "scale" = unbiased 1/(1−p)
    # zero-fill (divisor n(1−p) at the channel's effective_p), "ef" =
    # renorm + an error-feedback residual on the codec error, carried
    # as an extra params-shaped leaf of step state (donated,
    # checkpointable).
    schedule: str = "sync"
    # round scheduling (DESIGN.md §15): "sync" = every bucket ships at
    # the iteration barrier (the seed semantics, bit-identical default);
    # "async" = buckets ship in reverse-layer order as their gradients
    # become ready — against a deadline channel each bucket faces its
    # *reduced* slack (deadline − readiness) and packets that would have
    # made the sync deadline but miss the slack are LATE: written off as
    # dropped-with-recovery, counted on the history's staleness axis.
    # Channels without a latency model fall back to sync-identical masks
    # (zero lateness).
    compute_ms: Any = None
    # async backward-pass cost model: the modelled backward duration the
    # per-bucket readiness times are derived from. None (with
    # schedule="async") defaults to 0.8 × the channel's deadline_ms when
    # it has one, else 1.0. "auto" (§16) replaces the bytes-proportional
    # model entirely: the real backward is timed per bucket
    # (:func:`measure_bucket_ready_ms`) and the measured readiness times
    # are substituted into the plan before the step compiles.
    state_pack: str = "f32"
    # at-rest trainer-state format (DESIGN.md §16): "f32" = unpacked, the
    # bit-identical default; "bf16" = all optimizer/EF buffers in bf16;
    # "i8" = momentum bf16, Adam second moments + EF residual int8 with
    # per-row f32 scales and stochastic rounding on write (the wire
    # codec's grid, repro.core.quant). Packed buffers are what the step
    # carries and donates; params are never packed.
    donate: bool = True
    # donate params/opt_state/channel state into the jitted step
    # (donate_argnums) so the sweep never double-buffers the model;
    # False keeps the seed's copying behaviour (the A/B for
    # benchmarks/ring_bench.py's peak-memory delta).
    telemetry: bool = False
    # exchange telemetry (DESIGN.md §14): the jitted step additionally
    # returns the tapped counter bundle (per-link delivery counts,
    # divisors, grad/param norms) and run_simulation records structured
    # per-step records + the live per-link drop-rate estimate. The
    # primary outputs are bit-identical either way — the taps are extra
    # pure outputs; False (default) adds nothing to the traced graph.


def _exchange(tree, key, scfg: SimulatorConfig, *, is_grad: bool,
              masks=None, plan=None, recovery=None, ef_state=None,
              late=None, corruption=None, corrupt_masks=None):
    n = scfg.n_workers
    agg = scfg.aggregator
    use_ef = ef_state is not None
    if agg == "local":
        return (tree, ef_state) if use_ef else tree
    if agg.startswith("allreduce"):
        out = jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.mean(x, 0, keepdims=True),
                                       x.shape), tree)
        return (out, ef_state) if use_ef else out
    mode = "grad" if is_grad else "model"
    return rps_lib.rps_exchange_global(
        tree, key, scfg.drop_rate, n, mode=mode, masks=masks,
        s=scfg.n_servers, plan=plan, engine=scfg.engine,
        rs_dtype=jnp.dtype(scfg.exchange_dtype),
        recovery=recovery, ef_state=ef_state, late=late,
        corruption=corruption, corrupt_masks=corrupt_masks)


def resolve_wire(scfg) -> str:
    """The config's effective wire codec (duck-typed over
    SimulatorConfig / TrainConfig): :func:`repro.core.wire.config_wire`
    over the ``wire`` + legacy ``exchange_dtype`` knobs."""
    return wire_lib.config_wire(scfg.wire, scfg.exchange_dtype)


def wants_measured_ready(scfg) -> bool:
    """True when ``compute_ms="auto"``: the plan's readiness times come
    from timing the real backward (:func:`measure_bucket_ready_ms`), not
    the bytes-proportional cost model."""
    return (getattr(scfg, "schedule", "sync") == "async"
            and isinstance(scfg.compute_ms, str)
            and scfg.compute_ms.lower() == "auto")


def resolve_compute_ms(scfg, channel=None) -> Optional[float]:
    """The async cost model's backward-pass duration (duck-typed over
    SimulatorConfig / TrainConfig): the explicit ``compute_ms`` knob, or
    — under ``schedule="async"`` with it unset — 0.8 × the channel's
    iteration deadline (most of the budget spent computing, the regime
    async exists for), else 1.0. ``None`` for sync configs. For
    ``compute_ms="auto"`` this returns the deadline-derived provisional
    value — the caller measures the real backward and substitutes via
    :meth:`repro.core.plan.ExchangePlan.with_ready_ms` before any step
    compiles against the plan."""
    if getattr(scfg, "schedule", "sync") != "async":
        return None
    if scfg.compute_ms is not None and not wants_measured_ready(scfg):
        return float(scfg.compute_ms)
    deadline = getattr(channel, "deadline_ms", None)
    return 0.8 * float(deadline) if deadline is not None else 1.0


def measure_bucket_ready_ms(loss_fn: Callable, params: Any, batch: Any,
                            plan, reps: int = 2, iters: int = 1) -> list:
    """Measured per-bucket gradient readiness times (``--compute-ms=auto``).

    Bucket ``b``'s gradients are available once the backward pass has
    covered buckets ``b..B−1`` (the pytree is layer-ordered, backward runs
    last → first), so its readiness ≈ the wall time of the *suffix
    gradient*: grad of the vmapped loss w.r.t. the leaves of buckets
    ``b..B−1`` only, earlier buckets held constant. Each suffix is timed
    with the shared bench timer (compile excluded, best-of); timing noise
    is smoothed into a valid readiness profile by enforcing monotone
    non-increase toward the last bucket — exactly the invariant
    :func:`repro.core.plan.bucket_ready_ms` has by construction.

    ``params`` is the stacked (n, …) worker tree and ``batch`` one stacked
    batch — the measured graph is the step's own backward, not a proxy.
    Returns plan-order readiness in ms, feed to ``plan.with_ready_ms``.
    """
    leaves, treedef = jax.tree.flatten(params)
    times = []
    for b in range(plan.n_buckets):
        sfx = sorted(i for bk in plan.buckets[b:] for i in bk.leaf_ids)
        fixed = [i for i in range(len(leaves)) if i not in set(sfx)]

        def fn(sub, const, bt, sfx=sfx, fixed=fixed):
            lv: List[Any] = [None] * len(leaves)
            for i, v in zip(sfx, sub):
                lv[i] = v
            for i, v in zip(fixed, const):
                lv[i] = v
            ps = jax.tree.unflatten(treedef, lv)
            return jnp.sum(jax.vmap(loss_fn)(ps, bt))

        g = jax.jit(jax.grad(fn))
        sub = [leaves[i] for i in sfx]
        const = [leaves[i] for i in fixed]
        sec = timing_lib.time_fn(g, sub, const, batch, reps=reps,
                                 iters=iters, label=f"ready_b{b}")
        times.append(sec * 1e3)
    # suffix b ⊇ suffix b+1 ⇒ true times are non-increasing; project the
    # noisy measurements onto that cone (max over the tail from the right)
    ready = np.maximum.accumulate(np.asarray(times)[::-1])[::-1]
    return [float(r) for r in ready]


def make_exchange_plan(params: Any, scfg: SimulatorConfig, channel=None):
    """The :class:`repro.core.plan.ExchangePlan` a config prescribes, built
    from a *per-worker* param tree (no stacked dim): per-leaf legacy when
    the bucket knobs are unset (bit-identical to the seed), fixed-byte /
    count-balanced coalescing otherwise (DESIGN.md §11). The §13 wire
    pipeline rides on the plan (``wire``/``recovery`` fields), as does
    the §15 schedule (``channel`` sizes the async cost model's default
    ``compute_ms`` against the channel deadline)."""
    if not scfg.aggregator.startswith("rps"):
        return None
    return plan_lib.plan_from_config(params, scfg.n_workers, scfg.n_servers,
                                     bucket_mb=scfg.bucket_mb,
                                     n_buckets=scfg.n_buckets,
                                     engine=scfg.engine,
                                     wire=resolve_wire(scfg),
                                     recovery=scfg.recovery,
                                     schedule=getattr(scfg, "schedule",
                                                      "sync"),
                                     compute_ms=resolve_compute_ms(
                                         scfg, channel))


def make_sim_step(loss_fn: Callable, scfg: SimulatorConfig, channel,
                  plan, opt, telemetry: Optional[bool] = None):
    """The jitted simulator step, factored out so tests and benchmarks can
    inspect its compilation (donation, peak memory) directly.

    Hot-path buffers are donated (``donate_argnums``: params, opt_state,
    the channel state and — for the ``ef`` recovery — the EF residual)
    unless ``scfg.donate`` is False — a 100M-param sweep otherwise
    double-buffers the whole model every step.
    signature: step(params, opt_state, batch, key, lr, ch_state
    [, ef_state], exchange=True) -> (params, opt_state, loss, consensus,
    ch_state[, ef_state][, staleness][, stats]) — the EF slot appears
    exactly when ``scfg.recovery == "ef"`` on an rps aggregator (the
    residual is an extra stacked params-shaped leaf of step state,
    DESIGN.md §13); the ``staleness`` scalar (this round's late-packet
    fraction, §15) exactly when ``scfg.schedule == "async"``; the
    ``corrupt_frac`` scalar (this round's corrupt-delivered packet
    fraction, §17) exactly when the channel carries a corruption
    process.

    ``telemetry`` (default ``scfg.telemetry``) appends the tapped stats
    dict (DESIGN.md §14): a trace-time collector installed around the
    step body routes the exchange taps (per-link delivery counts,
    divisors, EF residual) plus grad/param norms out as ONE extra pure
    output. The primary outputs trace to the identical graph either way
    — nothing is inserted into their dataflow and donation is untouched
    — so the f32+renorm default stays bit-identical (pinned in
    tests/test_telemetry.py).
    """
    n = scfg.n_workers
    is_grad_mode = scfg.aggregator.endswith("_grad")
    rps_agg = scfg.aggregator.startswith("rps")
    use_ef = rps_agg and scfg.recovery == "ef"
    async_mode = rps_agg and scfg.schedule == "async"
    corruption = getattr(channel, "corruption", None) if rps_agg else None
    if use_ef and corruption is not None:
        raise ValueError(
            "corruption with recovery='ef' is unsupported: the EF residual "
            "telescopes an *honest* sender's codec error (DESIGN.md §17); "
            "use a robust recovery (median/trimmed/clip) instead")
    telemetry = scfg.telemetry if telemetry is None else telemetry
    # §16: the EF residual is carried at rest in the state pack's EF
    # format; decode/encode happen inside the traced step, only on rounds
    # that exchange (a skipped round must not re-quantize the residual)
    pack = statepack_lib.make_state_pack(getattr(scfg, "state_pack", None))
    # the scale divisor uses the channel's stationary marginal, not the
    # raw drop_rate knob (they differ for GE/hetero/trace channels)
    recovery = wire_lib.make_recovery(
        scfg.recovery, p=channel.effective_p()) if rps_agg else None
    slack = None
    if async_mode:
        # static per-bucket deadline budget from the plan's readiness
        # times; channels without a latency model ignore the values
        # (their sample_async is the sync-identical fallback)
        deadline = getattr(channel, "deadline_ms", None)
        slack = plan.slack_ms(float(deadline)) if deadline is not None \
            else np.zeros(plan.n_buckets, np.float64)

    def body(tap, params, opt_state, batch, key, lr, ch_state, ef_state,
             exchange):
        def total(ps, bs):
            return jnp.sum(jax.vmap(loss_fn)(ps, bs))

        masks = None
        late = None
        cmask = None
        staleness = jnp.float32(0)
        corrupt_frac = jnp.float32(0)
        if rps_agg:     # channel time advances every step, exchange or not
            with jax.named_scope("rps.masks"):
                if async_mode:  # per-bucket slack arbitration (§15)
                    rs, ag, late, ch_state_new = channel.sample_async(
                        key, ch_state, slack)
                elif plan.per_bucket_masks:  # packetised: draw per bucket
                    rs, ag, ch_state_new = channel.sample_packets(
                        key, ch_state, plan.n_buckets)
                else:
                    rs, ag, ch_state_new = channel.sample(key, ch_state)
                masks, ch_state = (rs, ag), ch_state_new
                if corruption is not None:  # same key, tag-separated (§17)
                    nb = rs.shape[0] if rs.ndim == 3 else None
                    cmask = channel.sample_corruption(key, n_buckets=nb)
        if corruption is not None and exchange:
            # the step's contamination observable: the fraction of
            # delivered packets that arrived wrong this round
            corrupt_frac = counters_lib.corruption_stats(
                cmask, masks[0])["corrupt_frac"].astype(jnp.float32)
        if async_mode and exchange:
            # the step's staleness observable: the fraction of offered
            # packets written off as late this round (0 on skipped steps
            # — no exchange consumes the draw)
            staleness = counters_lib.staleness_stats(
                late["rs"], late["ag"])["late_frac"].astype(jnp.float32)
        loss, grads = jax.value_and_grad(total)(params, batch)
        if tap is not None:
            taps_lib.emit("grad_norm", counters_lib.global_norm(grads))
        late_x = late if exchange else None
        # per-step derived keys: stochastic rounding of packed state
        # (dead code — eliminated — under the f32 identity pack)
        opt_key = jax.random.fold_in(key, 0x70616b)     # "pak"
        ef_key = jax.random.fold_in(key, 0x6566)        # "ef"
        # decode the at-rest EF residual only on exchanging rounds —
        # `exchange` is static, so skipped rounds trace no quant ops and
        # the residual passes through bitwise untouched
        ef_in = statepack_lib.unpack_tree(ef_state, pack.ef_format) \
            if (use_ef and exchange) else None
        if is_grad_mode:
            if exchange:
                out = _exchange(grads, key, scfg, is_grad=True,
                                masks=masks, plan=plan, recovery=recovery,
                                ef_state=ef_in, late=late_x,
                                corruption=corruption, corrupt_masks=cmask)
                if use_ef:
                    grads, ef_new = out
                    ef_state = statepack_lib.pack_tree(
                        ef_new, pack.ef_format, key=ef_key, tap="ef",
                        sequenced=True)
                else:
                    grads = out
            params, opt_state = opt.update(grads, opt_state, params, lr,
                                           key=opt_key)
        else:
            params, opt_state = opt.update(grads, opt_state, params, lr,
                                           key=opt_key)
            if exchange:
                out = _exchange(params, key, scfg, is_grad=False,
                                masks=masks, plan=plan, recovery=recovery,
                                ef_state=ef_in, late=late_x,
                                corruption=corruption, corrupt_masks=cmask)
                if use_ef:
                    params, ef_new = out
                    ef_state = statepack_lib.pack_tree(
                        ef_new, pack.ef_format, key=ef_key, tap="ef",
                        sequenced=True)
                else:
                    params = out
        mean_p = jax.tree.map(lambda x: jnp.mean(x, 0, keepdims=True), params)
        consensus = jax.tree.reduce(
            lambda a, x: a + jnp.sum(jnp.square(x.astype(jnp.float32))),
            jax.tree.map(lambda x, m: x - m, params, mean_p), jnp.float32(0))
        if tap is not None:
            taps_lib.emit("param_norm", counters_lib.global_norm(params))
        base = (params, opt_state, loss / n, consensus, ch_state)
        return base + ((ef_state,) if use_ef else ()) \
            + ((staleness,) if async_mode else ()) \
            + ((corrupt_frac,) if corruption is not None else ())

    if telemetry:
        def step_fn(params, opt_state, batch, key, lr, ch_state,
                    ef_state=None, exchange=True):
            with taps_lib.tap_collector() as tap:
                base = body(tap, params, opt_state, batch, key, lr,
                            ch_state, ef_state, exchange)
            return base + (tap.tree(),)
    else:
        def step_fn(params, opt_state, batch, key, lr, ch_state,
                    ef_state=None, exchange=True):
            return body(None, params, opt_state, batch, key, lr,
                        ch_state, ef_state, exchange)

    donate = ((0, 1, 5) + ((6,) if use_ef else ())) if scfg.donate else ()
    return jax.jit(step_fn, static_argnames=("exchange",),
                   donate_argnums=donate)


def run_simulation(loss_fn: Callable, init_fn: Callable,
                   batch_fn: Callable, scfg: SimulatorConfig,
                   eval_fn: Optional[Callable] = None,
                   state: Optional[Dict[str, Any]] = None,
                   start_step: int = 0,
                   telemetry=None) -> Dict[str, Any]:
    """loss_fn(params, batch) -> scalar; init_fn(key) -> params;
    batch_fn(step) -> stacked batch pytree with leading dim n_workers.

    Returns history dict with per-eval mean loss and consensus distance
    (the Lemma-3 quantity Σ_i ‖x_i − x̄‖²), plus the full carried state
    under ``"state"`` (params, opt_state, channel and EF-residual state)
    — a checkpointable pytree bundle (``checkpoint.ckpt``). Passing it
    back via ``state=``/``start_step=`` resumes the run bitwise
    identically (the per-step keys/lr are functions of the step index).

    Telemetry (DESIGN.md §14): ``telemetry`` takes a
    :class:`repro.telemetry.Telemetry` registry to report into (the
    launch CLIs pass theirs); ``scfg.telemetry`` alone builds a private
    in-memory one. Either way the returned history is a
    :class:`repro.telemetry.RunHistory` — the legacy mapping, plus
    ``.records`` (structured per-step records) and ``.summary``
    (per-link observed-vs-expected drop rates with the α bounds). The
    per-step stat bundle stays on device during the loop and is drained
    **after** it, so the async-dispatch pipeline (and the <5% overhead
    budget) survives telemetry.
    """
    n = scfg.n_workers
    key = jax.random.PRNGKey(scfg.seed)
    k_init, key = jax.random.split(key)
    p1 = init_fn(k_init)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), p1)
    # the plan reads shapes only: keep no device copy of the unstacked
    # replica alive through the run (a whole model's worth of memory)
    p1 = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p1)
    opt = make_optimizer(scfg.optimizer,
                         state_pack=getattr(scfg, "state_pack", None))
    opt_state = opt.init(params)
    # the drop process: channels are sampled inside the jitted step with the
    # shared per-step key; their state (e.g. Gilbert–Elliott link states,
    # trace cursor) is carried across steps alongside params/opt_state
    channel = channels_lib.make_channel(
        scfg.channel, n, scfg.drop_rate, s=scfg.n_servers,
        corruption=channels_lib.make_corruption(
            getattr(scfg, "corruption", None),
            getattr(scfg, "byzantine_frac", 0.0) or None))
    rps_agg = scfg.aggregator.startswith("rps")
    corrupting = rps_agg and getattr(channel, "corruption", None) is not None
    use_ef = rps_agg and scfg.recovery == "ef"
    ch_state = channel.init_state(jax.random.fold_in(key, 0x636831)) \
        if rps_agg else None
    # EF residual: per-worker, params-shaped, zero at start (DESIGN §13),
    # carried at rest in the state pack's EF format (§16 — zeros quantize
    # exactly, so the packed start is still the exact zero residual)
    pack = statepack_lib.make_state_pack(scfg.state_pack)
    ef_state = statepack_lib.pack_tree(
        wire_lib.init_ef_state(params), pack.ef_format) if use_ef else None
    if state is not None:       # resume from a checkpointed bundle
        params = state["params"]
        opt_state = state["opt_state"]
        ch_state = state.get("ch_state", ch_state)
        ef_state = state.get("ef_state", ef_state)
    reg = telemetry
    use_tel = scfg.telemetry or reg is not None
    if use_tel and reg is None:
        reg = telemetry_lib.Telemetry()
    # the exchange layout, computed once — never inside the jitted step
    # (DESIGN.md §11); grads share the params' tree so one plan serves both
    async_mode = rps_agg and scfg.schedule == "async"
    if use_tel:
        with reg.span("plan_build"):
            plan = make_exchange_plan(p1, scfg, channel)
        reg.bind(plan=plan, n=n,
                 p=channel.effective_p() if rps_agg else None,
                 channel=channel if rps_agg else None,
                 aggregator=scfg.aggregator)
    else:
        plan = make_exchange_plan(p1, scfg, channel)
    if plan is not None and wants_measured_ready(scfg):
        # --compute-ms=auto: time the real backward per bucket and swap
        # the measured readiness into the plan before any step compiles
        ready = measure_bucket_ready_ms(loss_fn, params,
                                        batch_fn(start_step), plan)
        plan = plan.with_ready_ms(ready)
    step_fn = make_sim_step(loss_fn, scfg, channel, plan, opt,
                            telemetry=use_tel)

    history = telemetry_lib.RunHistory(
        {"step": [], "loss": [], "consensus": [], "eval": [],
         "staleness": [],
         # the §15 staleness axis: per-eval-step late-packet fraction
         # (always present; stays empty for sync schedules)
         "corrupt_frac": [],
         # the §17 contamination axis: per-eval-step corrupt-delivered
         # fraction (stays empty without a corruption process)
         "channel": repr(channel),
         "channel_effective_p": channel.effective_p() if rps_agg
         else 0.0,
         "exchange_plan": plan.describe() if plan is not None
         else None})
    pending = []    # (t, lr, loss, consensus, late, corrupt, stats) — post-loop
    for t in range(start_step, scfg.steps):
        kt = jax.random.fold_in(key, t)
        lr = scfg.lr * min(1.0, (t + 1) / max(scfg.warmup, 1))
        batch = batch_fn(t)
        outs = step_fn(
            params, opt_state, batch, kt, jnp.float32(lr), ch_state,
            *((ef_state,) if use_ef else ()),
            exchange=(t % scfg.exchange_every == 0))
        if use_tel:
            stats = outs[-1]
            outs = outs[:-1]
        corrupt_frac = None
        if corrupting:
            corrupt_frac = outs[-1]
            outs = outs[:-1]
        staleness = None
        if async_mode:
            staleness = outs[-1]
            outs = outs[:-1]
        if use_ef:
            (params, opt_state, loss, consensus, ch_state,
             ef_state) = outs
        else:
            params, opt_state, loss, consensus, ch_state = outs
        if use_tel:
            pending.append((t, lr, loss, consensus, staleness,
                            corrupt_frac, stats))
        if t % scfg.eval_every == 0 or t == scfg.steps - 1:
            history["step"].append(t)
            history["loss"].append(float(loss))
            history["consensus"].append(float(consensus))
            if async_mode:
                history["staleness"].append(float(staleness))
            if corrupting:
                history["corrupt_frac"].append(float(corrupt_frac))
            if eval_fn is not None:
                mean_params = jax.tree.map(lambda x: jnp.mean(x, 0), params)
                history["eval"].append(float(eval_fn(mean_params)))
    if use_tel:
        with reg.span("record_drain", steps=len(pending)):
            for (t, lr, loss, consensus, staleness, corrupt_frac,
                 stats) in pending:
                extra = {} if staleness is None \
                    else {"staleness": float(staleness)}
                if corrupt_frac is not None:
                    extra["corrupt_frac"] = float(corrupt_frac)
                reg.record_step(t, stats, loss=loss, consensus=consensus,
                                lr=lr, **extra)
                if staleness is not None:
                    # lateness counter track in the Chrome trace (§15);
                    # the schema gate covers these events
                    reg.trace.counter("lateness",
                                      {"late_frac": float(staleness)})
                if corrupt_frac is not None:
                    # contamination counter track (§17) — the schema gate
                    # covers these events too
                    reg.trace.counter("corruption",
                                      {"corrupt_frac": float(corrupt_frac)})
        history.records = list(reg.memory.records)
        history.summary = reg.summary()
    history["final_loss"] = history["loss"][-1]
    history["params"] = params
    # final channel state: lets callers verify channel time advanced once
    # per wall-clock step (exchanged or skipped — DESIGN.md §9)
    history["channel_state"] = ch_state
    history["ef_state"] = ef_state
    history["state"] = {"params": params, "opt_state": opt_state,
                        "ch_state": ch_state, "ef_state": ef_state}
    # §16: per-component at-rest byte counts of what the step carries —
    # the same breakdown the dryrun report asserts on
    history["state_bytes"] = statepack_lib.state_bytes_breakdown(
        params=params, opt_state=opt_state, ef_state=ef_state)
    return history
