"""RPS with real collectives.

The paper's RS+AG decomposition *is* the reduce-scatter/all-gather all-reduce
schedule, so the collective implementation maps Algorithm 1 onto
``lax.psum_scatter`` + ``lax.all_gather`` over the unreliable (data-parallel
/ cross-pod) mesh axes, with Bernoulli drop masks:

  - RS-drop:  worker i's block j is zeroed out of the psum_scatter addend
              when the (i → owner j) packet drops. The owner renormalises by
              the *received* count — computable locally because the per-step
              PRNG key is shared, so every device knows the global mask.
  - AG-drop:  after all_gather, receiver i replaces block j by its own local
              pre-average block when the broadcast to i drops (model mode) —
              a dropped model block is still a valid model block.

Gradient mode (the paper's Fig-5 baseline) instead sums received gradient
contributions **without renormalising** (a missing packet is simply absent
from the sum, as in stock gradient-averaging systems) and applies **no
update** for AG-dropped blocks — the two asymmetries that make gradient
averaging fragile under loss.

Everything here runs *inside* an existing shard_map/pjit context. The number
of parameter-server blocks ``s`` is decoupled from the worker count n
(DESIGN.md §10): masks are rectangular (n, s), block j is owned by worker
``j % n`` (round-robin; multiple blocks per worker when s > n), and the
default s = n reproduces the paper's one-server-per-worker layout
bit-identically — owner j is then the j-th device on the RPS axes (the
paper's random owner assignment is symmetric across blocks — validated
against the permuted W-matrix oracle in tests).

Since DESIGN.md §11 there is exactly **one** RS+AG engine entry:
:func:`_exchange_table` runs the drop-masked round on an ``(s, blk[, m])``
block table, and every public entry point — :func:`rps_exchange_flat` (one
flat vector), :func:`rps_exchange_leaf` (partial-manual per-leaf),
:func:`rps_exchange_plan` (bucketed collective pytree path) and
:func:`rps_exchange_global` (stacked single-device view) — is a thin
executor of an :class:`repro.core.plan.ExchangePlan` layout over it.

Since DESIGN.md §12 the *lowering* of that round is pluggable
(``engine=``): "xla" keeps the two opaque collectives per bucket
(psum_scatter + all_gather, the seed lowering, bit-identical default);
"ring" executes the same round as an explicit bi-phase ring schedule
(:mod:`repro.kernels.rps_ring`) — one fused Pallas dispatch per bucket on
TPU (n−1 ``make_async_remote_copy`` hops per phase, double-buffered, with
in-kernel mask gating / renormalisation / AG-select and a donated table),
and the bit-exact ``lax.ppermute`` interpret ring everywhere else.
"auto" is xla on every backend: the fused kernel holds a whole bucket in
VMEM, which a real-width leaf does not fit, so "ring" stays an explicit
choice until the kernel streams its table from HBM.

Since DESIGN.md §13 the *wire treatment* is pluggable too: a
:mod:`repro.core.wire` codec (``wire=`` — f32 passthrough / bf16 / int8
stochastic rounding, absorbing the old ``rs_dtype`` knob) composed with a
loss-recovery policy (``recovery=`` — the paper's renorm, unbiased
1/(1−p) ``scale``, or the stateful error-feedback ``ef`` whose residual
the plan/global paths carry via ``ef_state=``).

Since DESIGN.md §17 the adversity model is two-axis: packets can arrive
*wrong*, not just missing. ``corruption=`` threads a
:mod:`repro.channels.corruption` process (bit-flip / scaled / sign-flip /
colluding-worker masks sampled alongside the drop masks) through every
path, applied to the sender's offered contribution before the codec; the
Byzantine-robust recoveries (``median`` / ``trimmed`` / ``clip``,
:mod:`repro.core.robust`) aggregate the pre-reduce per-worker table —
the xla path gathers the table (one all_gather, n× the RS bytes) and
aggregates locally, the ring engine raises (its hop-reduce never
materialises per-row structure).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

from repro.core import plan as plan_lib
from repro.core import robust as robust_lib
from repro.core import wire as wire_lib

AxisNames = Union[str, Tuple[str, ...]]

_LANE = 128     # TPU lane width: the row length of a block's payload


def _axis_tuple(axis_name: AxisNames) -> Tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def axis_size(axis_name: AxisNames) -> int:
    n = 1
    for a in _axis_tuple(axis_name):
        n *= lax.axis_size(a)
    return n


def _my_index(axis_name: AxisNames) -> jax.Array:
    names = _axis_tuple(axis_name)
    idx = lax.axis_index(names[0])
    for a in names[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def owners(n: int, s: Optional[int] = None) -> jnp.ndarray:
    """Block → owner-worker assignment for s server blocks over n workers.

    Round-robin: block j is averaged by worker ``j % n``. With ``s == n``
    (the paper's one-server-per-worker layout, and the default everywhere)
    this is the identity map; with ``s < n`` only the first s workers own a
    block; with ``s > n`` workers own multiple blocks (DESIGN.md §10).
    """
    s = n if s is None else int(s)
    return jnp.arange(s) % n


def owner_mask(n: int, s: Optional[int] = None) -> jnp.ndarray:
    """Boolean (n, s) matrix, True at (owner(j), j) — the entries every
    drop mask forces True (a worker never drops its own block). For
    ``s == n`` this is the identity matrix (the seed's forced diagonal)."""
    s = n if s is None else int(s)
    own = owners(n, s)
    return jnp.zeros((n, s), bool).at[own, jnp.arange(s)].set(True)


def sample_masks(key: jax.Array, n: int, p: float,
                 s: Optional[int] = None,
                 n_buckets: Optional[int] = None):
    """(rs, ag) boolean (n, s) masks, owner entries forced True.

    rs[i, j]: worker i's block-j packet reaches the owner (worker j % n).
    ag[i, j]: the broadcast of block j reaches worker i.
    Computed identically on every device from the shared per-step key.

    ``s`` is the number of parameter-server blocks (DESIGN.md §10);
    ``s=None`` keeps the paper's square ``s == n`` layout and is
    bit-identical to the seed behaviour (the forced owner entries are then
    the diagonal).

    ``n_buckets`` (DESIGN.md §11): when given, every bucket of a bucketed
    :class:`repro.core.plan.ExchangePlan` is its own packetisation unit
    and draws an independent mask pair — the returned masks are
    ``(n_buckets, n, s)``. ``None`` (default) keeps the legacy one-draw
    shape ``(n, s)``.

    This is the i.i.d. Bernoulli drop process of the paper. The pluggable
    generalisation lives in ``repro.channels`` (DESIGN.md §9): any
    ``Channel.sample`` produces an ``(rs, ag)`` pair with the same
    conventions, which every exchange below accepts via ``masks=``;
    ``channels.BernoulliChannel`` delegates here so the default channel is
    bit-identical to this function.
    """
    s = n if s is None else int(s)
    shape = (n, s) if n_buckets is None else (int(n_buckets), n, s)
    k1, k2 = jax.random.split(key)
    rs = jax.random.bernoulli(k1, 1.0 - p, shape)
    ag = jax.random.bernoulli(k2, 1.0 - p, shape)
    own = owner_mask(n, s)
    return rs | own, ag | own


def _scatter_layout(n: int, s: int):
    """Static layout of s round-robin-owned blocks on an n-device axis.

    ``psum_scatter(tiled)`` hands device i the i-th *contiguous* chunk of
    the leading dim, so the s blocks (owner(j) = j % n) are padded with
    dummy blocks up to S = k·n (k = ceil(s/n)) and permuted to owner-major
    order: scatter row i·k + c holds block c·n + i, i.e. device i receives
    exactly the k blocks it owns. Returns (k, S, order, inv) with
    ``order``/``inv`` the permutation and its inverse — both ``None`` when
    k == 1 (s ≤ n, owner(j) = j), where the permutation is the identity,
    so the default square layout skips the gathers entirely.
    """
    k = -(-s // n)
    S = k * n
    if k == 1:                            # s <= n: identity permutation
        return k, S, None, None
    r = jnp.arange(S)
    order = (r % k) * n + r // k          # scatter row -> block index
    inv = (r % n) * k + r // n            # block index -> scatter row
    return k, S, order, inv


def _pad_mask_blocks(m: jax.Array, S: int) -> jax.Array:
    """Extend an (n, s) mask with always-delivered dummy block columns."""
    s = m.shape[1]
    if S == s:
        return m
    return jnp.concatenate(
        [m, jnp.ones((m.shape[0], S - s), m.dtype)], axis=1)


def _masks_to_scatter(rs: jax.Array, ag: jax.Array, S: int, order):
    """(rs, ag) padded to S dummy-extended columns and permuted to the
    owner-major scatter order — the one mask transformation both collective
    paths share (``order=None`` = identity, the s ≤ n layouts)."""
    rs_sc, ag_sc = _pad_mask_blocks(rs, S), _pad_mask_blocks(ag, S)
    if order is not None:
        rs_sc, ag_sc = rs_sc[:, order], ag_sc[:, order]
    return rs_sc, ag_sc


# ---------------------------------------------------------------------------
# The one collective RS+AG engine (DESIGN.md §11); two lowerings (§12)
# ---------------------------------------------------------------------------

ENGINES = ("auto", "xla", "ring")


def resolve_engine(engine: Optional[str]) -> str:
    """"auto" (and None) → the XLA collective pair on every backend (the
    fused ring kernel keeps a whole bucket in VMEM; see the module
    docstring). Static — resolved at trace time."""
    if engine is None or engine == "auto":
        return "xla"
    if engine not in ("xla", "ring"):
        raise ValueError(f"engine={engine!r}, want one of {ENGINES}")
    return engine


def _divisor(rec: wire_lib.Recovery, mode: str, rs: jax.Array,
             n: int) -> jax.Array:
    """The (…, S) f32 per-block divisor the recovery policy prescribes,
    from (…, n, S) RS masks (the worker axis is reduced; any leading
    dims — e.g. the global path's group dim — pass through). The ONE
    place divisor policy lives: computable locally on every device (the
    mask is globally known, the ``scale`` divisor is a static constant):

      renorm / ef  — the received count (the paper's Algorithm 1) for
                     model / grad_renorm modes; the worker count n for
                     the naive "grad" mode (the paper's fragile Fig-5
                     baseline keeps its no-renormalisation asymmetry);
      scale        — the *expected* count n(1−p) in every mode: unbiased
                     zero-fill recovery (Weintraub et al., 2025).
    """
    shape = rs.shape[:-2] + rs.shape[-1:]
    if rec.kind == "scale":
        return jnp.full(shape, rec.expected_count(n), jnp.float32)
    if mode == "model" or mode == "grad_renorm":
        counts = jnp.sum(rs.astype(jnp.float32), axis=-2)
        return jnp.maximum(counts, 1.0)
    if mode == "grad":
        return jnp.full(shape, float(n), jnp.float32)  # no renormalisation
    raise ValueError(mode)


def _exchange_table(blocks: jax.Array, rs: jax.Array, ag: jax.Array, *,
                    names: Tuple[str, ...], n: int, i: jax.Array,
                    mode: str, rs_dtype=jnp.float32,
                    pin: Optional[Callable] = None,
                    engine: str = "xla", ring_ids=None,
                    wire=None, recovery=None, key=None,
                    send=None, late=None, corrupt=None,
                    comm_slot: int = 0) -> jax.Array:
    """One drop-masked RS+AG round on an ``(s, blk[, m])`` block table
    inside a shard_map region over ``names`` (the RPS axes).

    This is the single engine entry every exchange path executes: pad the
    table to the owner-major scatter layout, run the round under the
    chosen ``engine`` lowering — "xla": one tiled ``psum_scatter`` with
    the RS mask applied sender-side, the recovery divisor applied
    locally, one tiled ``all_gather`` and the AG-mask select (exactly
    two collectives per call); "ring": the DESIGN §12 ring schedule (one
    fused Pallas dispatch per bucket on TPU, the bit-exact interpret
    ppermute ring elsewhere); "auto"/None resolves per backend — and
    crop back to block order. ``pin`` is an optional per-intermediate
    sharding hook (the partial-manual per-leaf path pins its TP dim);
    identity when None. ``ring_ids`` forwards precomputed ring-neighbour
    logical device ids (``rps_ring.logical_ring_ids``) for the TPU
    kernel on meshes with non-RPS axes.

    Wire pipeline (DESIGN.md §13): ``wire`` picks the RS-leg codec
    (``None`` = a linear codec of the legacy ``rs_dtype`` knob, which
    the codec abstraction absorbs — the f32 default is bit-identical to
    the seed); ``recovery`` the divisor policy (a
    ``repro.core.wire.Recovery`` or spec string; None = the paper's
    renorm). ``key`` seeds stochastic rounding for quantised codecs
    (None = round-to-nearest-even). ``send`` overrides this device's
    wire representation — the EF recovery passes the
    residual-compensated, already-encoded intent (a plain array for
    linear codecs, the ``codec.encode`` pair for quantised ones); the
    AG-drop fallback always stays the *raw* local ``blocks``.

    Async staleness axis (DESIGN.md §15): ``late`` is an optional
    ``(rs_late, ag_late)`` pair of this call's ``(n, s)`` lateness masks
    from the channel's deadline arbitration — packets already *excluded*
    from ``rs``/``ag`` (a late packet is a dropped packet as far as the
    round's arithmetic goes); it only feeds the lateness tap counters.
    ``comm_slot`` names the dispatch slot an async schedule assigned this
    call: the ring engine derives its barrier/DMA ``collective_id`` from
    it, so consecutive buckets in alternating slots can be in flight at
    once (double-buffered against the backward dot-generals). Slot 0 is
    the sync default and keeps today's collective_id — bit-identical.

    Corruption axis (DESIGN.md §17): ``corrupt`` is an optional
    ``(cmask, corruption, ckey)`` triple — cmask this call's ``(n, s)``
    adversarial mask (True = worker i's packet for block j arrives
    *wrong*), ``corruption`` a ``repro.channels.corruption.Corruption``,
    ``ckey`` the per-device transform key (bitflip only). The transform
    is applied to this device's *offered* contribution before the codec
    (an adversarial sender, the Yin et al. Byzantine-worker model), so
    both engines and every codec see the same corrupted wire values; the
    AG-drop fallback keeps the *honest* local ``blocks`` — a worker
    never corrupts its own copy. ``corrupt=None`` (and an all-False
    cmask) is bit-identical to the pre-§17 paths.

    Robust recoveries (median/trimmed/clip, ``rec.needs_table``)
    aggregate the per-worker contribution table *before* the reduce — a
    sum-only collective destroys exactly the per-row structure they
    need. The xla path therefore replaces psum_scatter with one
    all_gather of the offered tables (n× the RS bytes — the price of
    robustness) and aggregates locally; the ring engine reduces on the
    hops and never materialises the table, so robust + engine="ring"
    raises (``auto`` falls back to xla).
    """
    from repro.telemetry import taps
    codec = wire_lib.resolve_codec(wire, rs_dtype)
    rec = wire_lib.make_recovery(recovery)
    if rec.needs_state and send is None:
        # ef without a compensated send would silently run as plain
        # renorm, dropping the codec error every round — only the
        # plan/global paths (which carry the residual) may pass it
        raise ValueError("recovery='ef' carries a residual: use "
                         "rps_exchange_plan / rps_exchange_global with "
                         "ef_state=")
    raw_pin = pin      # None = fully-manual region (the fused-kernel gate)
    if pin is None:
        def pin(x):
            return x
    s = rs.shape[-1]
    k, S, order, inv = _scatter_layout(n, s)
    trail = blocks.ndim - 1
    wide = (slice(None),) + (None,) * trail      # (S, 1[, 1]) broadcast

    def to_scatter(x, fill=0.0):
        """Pad a block-ordered (s, …) per-block array to S rows and
        permute to owner-major order — the transformation the table and
        masks go through, applied to every send component too."""
        if S != x.shape[0]:
            x = jnp.pad(x,
                        ((0, S - x.shape[0]),) + ((0, 0),) * (x.ndim - 1),
                        constant_values=fill)
        return x if order is None else x[order]

    blocks = pin(to_scatter(blocks))
    rs_sc, ag_sc = _masks_to_scatter(rs, ag, S, order)
    div = _divisor(rec, mode, rs_sc, n)          # (S,) f32, known locally

    if taps.active() is not None:
        # per-call (= per-bucket on the plan path) telemetry, computed on
        # the UNPADDED masks so the dummy always-delivered columns never
        # bias the counts; owner entries excluded (not wire events).
        # Sits before the engine branch, so both lowerings are covered.
        from repro.telemetry import counters as _ctr
        taps.emit("rs_link_delivered", _ctr.link_delivered(rs))
        taps.emit("ag_link_delivered", _ctr.link_delivered(ag))
        taps.emit("divisor", _divisor(rec, mode, rs, n))
        if late is not None:
            taps.emit("rs_link_late", _ctr.link_late(late[0]))
            taps.emit("ag_link_late", _ctr.link_late(late[1]))
        if corrupt is not None:
            taps.emit("rs_link_corrupt",
                      _ctr.link_corrupt(corrupt[0], rs))
        taps.annotate("exchange", {
            "n": n, "s": int(s), "mode": mode,
            "engine": "xla" if rec.needs_table
            else resolve_engine(engine),
            "codec": codec.name, "recovery": rec.kind})

    # ---- wire representation of this device's contribution -------------
    offer = blocks
    if corrupt is not None:
        # adversarial sender (DESIGN §17): transform the offered value
        # BEFORE the codec so every engine/codec sees the same corrupted
        # wire; `blocks` (the honest local copy, the AG fallback) is
        # untouched. EF never composes with corruption (the plan/global
        # paths raise), so `send` is always None here.
        cmask_c, corr_c, ckey_c = corrupt
        row_c = to_scatter(cmask_c[i], fill=False)     # (S,) this sender
        offer = corr_c.apply(blocks, row_c[wide], ckey_c)
    if codec.quantized:
        if send is None:
            enc = codec.encode(offer, key)
        else:
            q, sc = send
            enc = (to_scatter(q), to_scatter(sc, fill=1.0))
        send_arr = codec.decode(*enc)            # f32 on the wire grid
    else:
        enc = None
        send_arr = offer if send is None else pin(to_scatter(send))
    acc_dtype = codec.accum_dtype

    if rec.needs_table:
        # ---- robust recovery: aggregate the pre-reduce table ----------
        if mode == "grad":
            raise ValueError(
                f"recovery={rec.kind!r} needs the renormalising modes "
                "(model/grad_renorm); the naive 'grad' mode has no "
                "per-contribution table semantics")
        if engine not in (None, "auto", "xla"):
            raise ValueError(
                f"recovery={rec.kind!r} needs the pre-reduce per-worker "
                "table; the ring engine reduces on the hops and never "
                "materialises it — use engine='xla' (the 'auto' default "
                "falls back to xla automatically)")
        with jax.named_scope("rps.robust_gather"):
            # one all_gather of the offered tables (n× the RS bytes):
            # every device holds all n contributions pre-reduce
            g = send_arr.astype(jnp.float32)[None]
            for a in reversed(names):
                g = lax.all_gather(g, a, axis=0, tiled=True)
        with jax.named_scope("rps.robust"):
            table = g.reshape(n, S, -1).transpose(1, 0, 2)   # (S, n, d)
            tilde = robust_lib.robust_aggregate(table, rs_sc.T, rec)
            tilde = tilde.reshape((S,) + blocks.shape[1:]) \
                .astype(blocks.dtype)
        with jax.named_scope("rps.decode"):
            recv = ag_sc[i][wide]
            out = jnp.where(recv, tilde, blocks)  # keep honest local block
            if inv is not None:
                out = out[inv]
            return pin(out[:s])

    if resolve_engine(engine) == "ring":
        from repro.kernels import rps_ring
        # forward the RAW pin: rps_ring keys "fused kernel vs ppermute
        # ring" on pin is None (a pin marks a partial-manual region the
        # Pallas dispatch cannot serve) — the normalised identity above
        # would make the fused TPU path unreachable
        with jax.named_scope("rps.ring"):
            out = rps_ring.ring_exchange_scatter_table(
                blocks, rs_sc, ag_sc, names=names, n=n, i=i, k=k,
                mode=mode, rs_dtype=acc_dtype, pin=raw_pin,
                ring_ids=ring_ids, codec=codec, enc=enc,
                send=None if send_arr is blocks else send_arr, div=div,
                comm_slot=comm_slot)
            if inv is not None:
                out = out[inv]                    # back to block order
            return pin(out[:s])
    rs_f = rs_sc.astype(acc_dtype)

    # ---- Reduce-Scatter with send-side drops --------------------------
    # Linear codecs accumulate in the wire dtype (f32 default: the
    # renormalised-mean precision / paper-faithful setting; bf16 halves
    # the RS wire bytes). Quantised codecs accumulate the decoded
    # contributions in f32 — psum_scatter is opaque, so the XLA engine
    # models a decode-at-receiver transport (the ring engine carries the
    # quantised payload on the actual hops).
    # (f32 also works around an XLA-CPU AllReducePromotion crash on
    # sub-32-bit reduce-scatter under partial-manual shard_map.)
    with jax.named_scope("rps.reduce_scatter"):
        masked = pin(send_arr.astype(acc_dtype) * rs_f[i][wide])
        sums = masked
        for a in names:  # scatter over the flattened axes, major to minor
            sums = pin(lax.psum_scatter(sums, a, scatter_dimension=0,
                                        tiled=True))
        sums = pin(sums.reshape((k,) + blocks.shape[1:]))
    with jax.named_scope("rps.recovery"):
        my_div = lax.dynamic_slice_in_dim(div, i * k, k).astype(acc_dtype)
        tilde = sums / my_div[wide]

    # ---- All-Gather with receive-side drops ------------------------------
    with jax.named_scope("rps.all_gather"):
        gathered = pin(tilde.astype(blocks.dtype))    # AG moves model dtype
        for a in reversed(names):
            gathered = pin(lax.all_gather(gathered, a, axis=0, tiled=True))
    with jax.named_scope("rps.decode"):
        recv = ag_sc[i][wide]
        if mode == "model" or mode == "grad_renorm":
            out = jnp.where(recv, gathered, blocks)   # keep local block
        else:                                         # "grad": no update
            out = jnp.where(recv, gathered, jnp.zeros_like(blocks))
        if inv is not None:
            out = out[inv]                            # back to block order
        return pin(out[:s])


def _rows(d: int) -> Tuple[int, ...]:
    """The shape a block's d payload elements are exchanged in: (d/128,
    128) rows of the TPU's lane width where that divides, else flat. The
    round's arithmetic is elementwise per block, so the view changes no
    value; it keeps XLA's TPU compiler from spending time and host memory
    in proportion to d on a flat operand (tens of GB at real widths)."""
    return (d // _LANE, _LANE) if d % _LANE == 0 else (d,)


def _lane_rows(tbl: jax.Array) -> jax.Array:
    """A flat ``(s, blk, 1)`` bucket table in :func:`_rows` layout (a TP
    bucket keeps its model dim last)."""
    s, blk, m = tbl.shape
    return tbl.reshape((s,) + _rows(blk)) if m == 1 else tbl


def _bucket_masks(rs: jax.Array, ag: jax.Array, b: int):
    """Bucket b's (n, s) mask pair: per-bucket ``(n_buckets, n, s)`` masks
    index their own draw, legacy ``(n, s)`` masks are shared by every
    bucket (the seed one-draw-per-round semantics)."""
    if rs.ndim == 3:
        return rs[b], ag[b]
    return rs, ag


def _resolve_masks(key, n: int, p: float, plan: plan_lib.ExchangePlan,
                   masks):
    """Default mask draw for a plan: per-bucket draws for packetised
    (fixed-byte) plans, one shared draw for the legacy layouts."""
    if masks is not None:
        rs, ag = masks
        if rs.ndim == 3 and rs.shape[0] != plan.n_buckets:
            raise ValueError(f"per-bucket masks carry {rs.shape[0]} "
                             f"buckets, plan has {plan.n_buckets}")
        return rs, ag
    return sample_masks(key, n, p, plan.s,
                        n_buckets=plan.n_buckets
                        if plan.per_bucket_masks else None)


def _resolve_corruption(corruption, corrupt_masks, key, n: int, s: int,
                        n_buckets=None):
    """Resolve the per-round corruption masks (DESIGN.md §17): the
    channel-supplied ``corrupt_masks`` win; otherwise the process samples
    its own from the shared round key (internally tag-folded, so the
    draw never correlates with the drop masks). Returns None when there
    is no corruption — the bit-identical default."""
    if corruption is None:
        if corrupt_masks is not None:
            raise ValueError("corrupt_masks without a corruption process")
        return None
    if corrupt_masks is None:
        return corruption.sample(key, n, s, n_buckets=n_buckets)
    if corrupt_masks.ndim == 3 and n_buckets is not None \
            and corrupt_masks.shape[0] != n_buckets:
        raise ValueError(f"corrupt_masks carry {corrupt_masks.shape[0]} "
                         f"buckets, plan has {n_buckets}")
    return corrupt_masks


#: key-domain tag for corruption transform randomness ("corr"), disjoint
#: from the 0x77697265 ("wire") encode-dither domain
_CORRUPT_TAG = 0x636F7272


def rps_exchange_flat(v: jax.Array, key: jax.Array, p: float,
                      axis_name: AxisNames, *, mode: str = "model",
                      masks=None, rs_dtype=jnp.float32,
                      s: Optional[int] = None, engine: str = "xla",
                      ring_ids=None, wire=None, recovery=None,
                      corruption=None, corrupt_masks=None):
    """One RPS round on a flat per-device vector v: (D,) -> (D,).

    mode:
      "model"      — Algorithm 1 (renormalised average; AG-drop keeps the
                     local block).
      "grad"       — naive gradient averaging (sum/n, AG-drop → zero update).
      "grad_renorm"— RS-drop-tolerant gradient aggregation (renormalised;
                     AG-drop falls back to the local gradient). This is the
                     mode used for FSDP-sharded archs (DESIGN.md §5).

    ``s`` — number of parameter-server blocks (DESIGN.md §10). Defaults to
    the worker count n (inferred from ``masks`` when given); ``s == n`` is
    bit-identical to the seed one-block-per-worker layout. Other s values
    pad the block table to k·n dummy-extended blocks in owner-major order
    so the schedule is still one psum_scatter + one all_gather.

    ``engine`` — the round's lowering (DESIGN.md §12): "xla" (default,
    two collectives, bit-identical to the seed), "ring" (fused Pallas
    dispatch on TPU / interpret ppermute ring elsewhere), or "auto".

    ``wire``/``recovery`` — the wire pipeline (DESIGN.md §13): RS-leg
    codec ("f32"/"bf16"/"int8"; None = a linear codec of ``rs_dtype``,
    bit-identical to the seed) and loss-recovery policy
    ("renorm"/"scale"; the stateful "ef" lives on the plan/global paths
    that carry state). The ``scale`` divisor uses this call's ``p``
    unless the passed ``Recovery`` already carries its own (a channel's
    ``effective_p``).

    Returns the exchanged vector (for "grad" modes: the per-block gradient
    each worker should apply).
    """
    names = _axis_tuple(axis_name)
    n = axis_size(axis_name)
    i = _my_index(axis_name)
    D = v.shape[0]

    rec = wire_lib.make_recovery(recovery, p=p)
    if rec.needs_state:
        raise ValueError("recovery='ef' carries a residual: use "
                         "rps_exchange_plan / rps_exchange_global with "
                         "ef_state=")
    codec = wire_lib.resolve_codec(wire, rs_dtype)
    # fold the device index into the encode key: the per-step key is
    # replicated, and identical uniforms on every worker would correlate
    # the stochastic-rounding dither — the 1/n error averaging the codec
    # variance accounting relies on needs independent per-worker draws
    k_enc = jax.random.fold_in(jax.random.fold_in(key, 0x77697265), i) \
        if codec.quantized else None

    rs, ag = sample_masks(key, n, p, s) if masks is None else masks
    s = rs.shape[-1]
    cmask = _resolve_corruption(corruption, corrupt_masks, key, n, s)
    corrupt = None
    if cmask is not None:
        ckey = jax.random.fold_in(jax.random.fold_in(key, _CORRUPT_TAG), i)
        corrupt = (cmask, corruption, ckey)
    pad = (-D) % s
    blk = (D + pad) // s
    vp = jnp.pad(v, (0, pad)) if pad else v
    out = _exchange_table(vp.reshape(s, blk), rs, ag, names=names, n=n,
                          i=i, mode=mode, rs_dtype=rs_dtype,
                          engine=engine, ring_ids=ring_ids,
                          wire=codec, recovery=rec, key=k_enc,
                          corrupt=corrupt)
    out = out.reshape(-1)
    return out[:D] if pad else out


def rps_exchange(tree: Any, key: jax.Array, p: float,
                 axis_name: AxisNames, *, mode: str = "model",
                 masks=None, rs_dtype=jnp.float32,
                 s: Optional[int] = None, engine: str = "xla",
                 ring_ids=None, wire=None, recovery=None,
                 corruption=None, corrupt_masks=None) -> Any:
    """Pytree wrapper around :func:`rps_exchange_flat` — semantically the
    single-bucket plan (``plan.single_bucket_plan``): the whole tree is
    one ``ravel_pytree`` buffer, exchanged in one RS+AG round.

    Forwards ``rs_dtype`` (the seed version silently dropped it, so bf16 RS
    accumulation was unreachable from the pytree API), the server-block
    count ``s``, the ``engine`` knob and the §13 ``wire``/``recovery``
    pipeline.
    """
    flat, unravel = ravel_pytree(tree)
    return unravel(rps_exchange_flat(flat, key, p, axis_name, mode=mode,
                                     masks=masks, rs_dtype=rs_dtype, s=s,
                                     engine=engine, ring_ids=ring_ids,
                                     wire=wire, recovery=recovery,
                                     corruption=corruption,
                                     corrupt_masks=corrupt_masks))


def rps_exchange_plan(tree: Any, key: jax.Array, p: float,
                      axis_name: AxisNames, *,
                      plan: plan_lib.ExchangePlan, mode: str = "model",
                      masks=None, rs_dtype=jnp.float32,
                      pin: Optional[Callable] = None,
                      engine: Optional[str] = None,
                      ring_ids=None, wire=None, recovery=None,
                      ef_state: Any = None, late=None,
                      corruption=None, corrupt_masks=None) -> Any:
    """Bucketed collective exchange of a (worker-local) pytree inside a
    shard_map region: exactly ``2 × plan.n_buckets`` collectives per round
    on the "xla" engine (one psum_scatter + one all_gather per bucket),
    one fused ring dispatch per bucket on the TPU "ring" engine —
    however many leaves the tree has.

    ``plan`` is an :class:`repro.core.plan.ExchangePlan` built **once at
    setup** from this tree's (local) shapes. ``masks`` accepts the legacy
    shared ``(n, s)`` pair or a per-bucket ``(n_buckets, n, s)`` pair; the
    default draw follows ``plan.per_bucket_masks``. A
    ``per_leaf_plan`` reproduces the seed per-leaf tree-map of
    :func:`rps_exchange_flat` bit-identically; a ``single_bucket_plan``
    reproduces :func:`rps_exchange`. ``engine=None`` defers to
    ``plan.engine``.

    The per-bucket loop is software-pipelined: bucket b+1's table
    gather/blockify is emitted *before* bucket b's collective, so the
    scheduler can overlap the reshape/concat work with the in-flight
    round and at most two bucket tables are live at once (the all-up-
    front gather kept every table alive across the whole round).

    Wire pipeline (DESIGN.md §13): ``wire``/``recovery`` default to the
    plan's own fields (``plan.wire``/``plan.recovery`` — "f32"/"renorm"
    unless configured, bit-identical to the seed). The stateful ``ef``
    recovery takes the residual pytree via ``ef_state`` (same structure
    as ``tree``; :func:`repro.core.wire.init_ef_state` builds the zero
    initial one) and then returns ``(exchanged_tree, new_ef_state)``
    instead of the bare tree — the caller carries the residual across
    rounds (trainer/simulator state, donated alongside params).

    Async schedule (DESIGN.md §15): a ``schedule="async"`` plan
    dispatches buckets in ``plan.ship_order`` — reverse bucket order,
    the order the backward pass makes gradients ready — and alternates
    the ring engine's dispatch slot (``comm_slot`` → distinct
    ``collective_id``s) so consecutive bucket rounds double-buffer
    against the backward dot-generals on TPU. ``late`` optionally
    carries the channel's ``{"rs", "ag"}`` per-bucket lateness masks
    (``(n_buckets, n, s)``) for the tap counters; the masks in
    ``masks`` are already deadline-arbitrated, so lateness never
    changes the arithmetic. Sync plans keep today's plan-order loop and
    slot 0 — bit-identical.
    """
    names = _axis_tuple(axis_name)
    n = axis_size(axis_name)
    if plan.n != n:
        raise ValueError(f"plan built for n={plan.n}, axes give n={n}")
    i = _my_index(axis_name)
    engine = plan.engine if engine is None else engine
    wire = plan.wire if wire is None else wire
    recovery = plan.recovery if recovery is None else recovery
    codec = wire_lib.resolve_codec(wire, rs_dtype)
    rec = wire_lib.make_recovery(recovery, p=p)
    use_ef = rec.needs_state
    if use_ef and ef_state is None:
        raise ValueError("recovery='ef' needs ef_state= (the carried "
                         "residual; wire.init_ef_state(tree) to start)")
    if use_ef and corruption is not None:
        raise ValueError(
            "corruption with recovery='ef' is unsupported: the EF "
            "residual telescopes an *honest* sender's codec error — an "
            "adversarial wire breaks the feedback loop; use a robust "
            "recovery (median/trimmed/clip)")
    rs, ag = _resolve_masks(key, n, p, plan, masks)
    cmasks = _resolve_corruption(
        corruption, corrupt_masks, key, n, plan.s,
        n_buckets=plan.n_buckets if plan.per_bucket_masks else None)
    from repro.telemetry import taps
    if taps.active() is not None:
        taps.annotate("plan", {
            "n_buckets": plan.n_buckets, "s": plan.s,
            "rs_leg_bytes": int(plan.rs_leg_bytes(codec))})
    leaves = plan.check_leaves(tree)
    ef_leaves = plan.check_leaves(ef_state) if use_ef else None
    is_async = plan.schedule == "async"
    order = plan.ship_order
    outs: list = [None] * plan.n_buckets
    new_ef: list = [None] * plan.n_buckets
    tbl = _lane_rows(plan.gather_bucket(leaves, order[0]))
    for pos, b in enumerate(order):
        nxt = _lane_rows(plan.gather_bucket(leaves, order[pos + 1])) \
            if pos + 1 < plan.n_buckets else None  # prefetch next bucket
        rs_b, ag_b = _bucket_masks(rs, ag, b)
        late_b = (late["rs"][b], late["ag"][b]) if late is not None \
            else None
        corrupt_b = None
        if cmasks is not None:
            cm_b = cmasks[b] if cmasks.ndim == 3 else cmasks
            ck_b = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(key, _CORRUPT_TAG), b), i)
            corrupt_b = (cm_b, corruption, ck_b)
        # per-bucket AND per-device encode keys (see rps_exchange_flat:
        # correlated dither across workers would defeat the averaging)
        k_b = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(key, 0x77697265), b), i) \
            if codec.quantized else None
        send = None
        if use_ef:
            # EF: send the residual-compensated intent; the codec error
            # of *this* round becomes the residual replayed into the next
            # round's send (e' = intent − decode(encode(intent))).
            # Delivery-aware (DESIGN §13): a block whose RS packet
            # dropped injected nothing into the average, so its residual
            # stays outstanding — only delivered blocks take the fresh
            # codec error. Without this the random delivery subset
            # breaks the per-worker telescoping the EF guarantee rests
            # on (iid stochastic-rounding errors stop cancelling).
            # deterministic encode under EF: the feedback loop supplies
            # the unbiasing, so stochastic rounding's dither would only
            # add fresh variance the residual can never cancel
            e_tbl = _lane_rows(plan.gather_bucket(ef_leaves, b))
            intent = tbl + e_tbl
            if codec.quantized:
                send = codec.encode(intent, None)
                delivered = codec.decode(*send)
            else:
                delivered = codec.fake_quant(intent)
                send = delivered
            gate = rs_b[i][(slice(None),) + (None,) * (tbl.ndim - 1)]
            new_ef[b] = jnp.where(
                gate != 0, (intent - delivered).astype(tbl.dtype), e_tbl)
            if taps.active() is not None:
                taps.emit("ef_resid_sq",
                          jnp.sum(jnp.square(e_tbl.astype(jnp.float32))))
        outs[b] = _exchange_table(tbl, rs_b, ag_b, names=names, n=n,
                                  i=i, mode=mode, rs_dtype=rs_dtype,
                                  pin=pin, engine=engine,
                                  ring_ids=ring_ids, wire=codec,
                                  recovery=rec, key=k_b, send=send,
                                  late=late_b, corrupt=corrupt_b,
                                  comm_slot=(pos % 2) if is_async else 0)
        tbl = nxt
    if use_ef:
        return plan.scatter(outs), plan.scatter(new_ef)
    return plan.scatter(outs)


def _blockify(x: jax.Array, s: int, model_dim: Optional[int]):
    """Reshape a (worker-local) leaf to (s, blk, m) — one row per server
    block — where m collects the model-sharded dim (kept intact — reshaping
    it would force an XLA resharding gather) and the remaining dims are
    flattened and padded to a multiple of s. Returns (blocks, restore_fn)."""
    shape = x.shape
    if model_dim is None:
        flat = x.reshape(-1, 1)
    else:
        flat = jnp.moveaxis(x, model_dim, -1)
        flat = flat.reshape(-1, shape[model_dim])
    free, m = flat.shape
    pad = (-free) % s
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    blocks = flat.reshape(s, (free + pad) // s, m)

    def restore(b):
        f = b.reshape(free + pad, m)[:free]
        if model_dim is None:
            return f.reshape(shape)
        inter = f.reshape(tuple(s for i, s in enumerate(shape)
                                if i != model_dim) + (shape[model_dim],))
        return jnp.moveaxis(inter, -1, model_dim)

    return blocks, restore


def rps_exchange_leaf(x: jax.Array, rs: jax.Array, ag: jax.Array,
                      axis_name: AxisNames, *, mode: str,
                      model_dim: Optional[int] = None,
                      engine: str = "xla", rs_dtype=jnp.float32,
                      wire=None, recovery=None,
                      key: Optional[jax.Array] = None) -> jax.Array:
    """Per-leaf RS+AG exchange inside a partial-manual shard_map region.

    `model_dim` marks a dim that stays auto-sharded (tensor-parallel): it is
    kept intact so no cross-model-axis resharding is triggered. Masks are the
    shared (n, s) rs/ag from :func:`sample_masks` (s inferred from the mask
    shape; s == n is the paper's square layout) — reusing the same column j
    for the j-th block of *every* leaf is exactly the paper's partition where
    block j is the union of all leaves' j-th blocks.

    ``engine="ring"`` here always runs the ppermute ring (the ``pin``
    hook marks a partial-manual region whose auto-sharded TP dim the
    fused Pallas dispatch cannot see — ``rps_ring`` falls back).

    ``rs_dtype`` is the RS accumulation/wire dtype, *forwarded* to the
    engine (this path used to hard-code f32, so bf16-wire exchanges were
    silently promoted — the same class of bug PR 2 fixed in
    ``rps_exchange``). f32 stays the default: the renormalised mean
    should not round per-addend. ``wire``/``recovery``/``key`` thread
    the §13 pipeline (a ``scale`` Recovery must carry its own ``p`` —
    this path sees masks, not a drop rate).
    """
    from jax.sharding import PartitionSpec as _P
    names = _axis_tuple(axis_name)
    n = axis_size(axis_name)
    i = _my_index(axis_name)
    s = rs.shape[-1]
    blocks, restore = _blockify(x, s, model_dim)

    def pin(v):
        # keep the trailing model dim sharded on the auto axes — inside the
        # partial-manual region shardy otherwise de-shards it, materialising
        # full-width f32 blocks (observed: 6.4 GB/leaf on mixtral)
        if model_dim is None:
            return v
        return jax.lax.with_sharding_constraint(
            v, _P(*([None] * (v.ndim - 1) + ["model"])))

    out = _exchange_table(blocks, rs, ag, names=names, n=n, i=i,
                          mode=mode, rs_dtype=rs_dtype, pin=pin,
                          engine=engine, wire=wire, recovery=recovery,
                          key=key)
    return restore(out)


def _resolve_global_backend(backend: str) -> str:
    if backend == "auto":
        # the fused Pallas kernel is the hot path on TPU; on CPU the XLA
        # einsum is faster than interpret-mode Pallas, so auto stays on jnp
        # (backend="pallas" still forces the kernel via interpret=True — the
        # parity tests exercise exactly that)
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"backend={backend!r}")
    return backend


def _global_groups(plan: plan_lib.ExchangePlan):
    """Bucket indices grouped by (blk, m, dtype): every group is one
    stacked batched dispatch in the global path. Fixed-byte plans are
    near-uniform (one or two groups); per-leaf legacy plans degrade to one
    group per distinct leaf size — the seed per-leaf lowering."""
    groups: dict = {}
    for b, bk in enumerate(plan.buckets):
        groups.setdefault((bk.blk, bk.m, bk.dtype), []).append(b)
    return groups


def rps_exchange_global(tree: Any, key: jax.Array, p: float, n: int, *,
                        mode: str = "model", masks=None,
                        backend: str = "auto",
                        s: Optional[int] = None,
                        plan: Optional[plan_lib.ExchangePlan] = None,
                        engine: str = "xla",
                        rs_dtype=jnp.float32, wire=None, recovery=None,
                        ef_state: Any = None, late=None,
                        corruption=None, corrupt_masks=None) -> Any:
    """Global-view exchange on *stacked* worker trees (leading dim n).

    Mathematically identical to the collective path (same masks, same block
    partition), expressed as jnp ops — runs on one device; used by the
    n-worker simulation harness and as the cross-check in tests.

    ``masks``: optional precomputed ``(rs, ag)`` pair from any
    ``repro.channels`` channel — legacy shared ``(n, s)`` or per-bucket
    ``(n_buckets, n, s)``; defaults to the draw the plan prescribes
    (``sample_masks(key, n, p, s[, n_buckets])``).

    ``s``: number of parameter-server blocks (DESIGN.md §10); inferred from
    ``masks``/``plan`` when given, defaults to n (the paper's square
    layout, bit-identical to the seed).

    ``plan``: an :class:`repro.core.plan.ExchangePlan` over the
    *per-worker* tree (leading n dim stripped). ``None`` builds the legacy
    per-leaf plan on the fly — one bucket per leaf, shared masks — which
    is exactly the seed per-leaf behaviour. Buckets of equal width execute
    as **one** stacked batched dispatch (a single grid-over-blocks
    ``masked_avg`` Pallas call on the "pallas" backend, one einsum on
    "jnp") instead of a per-leaf loop.

    ``backend``: "jnp" (einsum), "pallas" (the fused
    ``kernels.masked_avg_grid_pallas`` renormalised block average,
    interpreted off-TPU), or "auto" (pallas on TPU, jnp elsewhere).

    ``engine``: "xla" (default) sums contributions the XLA way (one
    einsum / one masked_avg dispatch per group, f32 accumulation —
    bit-identical to the seed); "ring" replays the §12 ring engine's
    arithmetic — contributions added **in ring order in the wire dtype**
    ``rs_dtype`` (``kernels.rps_ring.ring_global_sums``) — so the
    single-device simulator can study bf16-wire convergence without a
    TPU. "auto" = "xla" (this path runs no collectives, so there is
    nothing to fuse).

    Memory: the whole path computes in the group's native dtype where
    exact — no full-stack f32 copy — and the AG fallback is the input
    stack itself (model/renorm) or a mask *multiply* (grad), so no
    same-shape fallback buffer is ever materialised
    (tests/test_ring.py pins the compiled temp bytes).

    Wire pipeline (DESIGN.md §13): ``wire``/``recovery`` default to the
    plan's fields. A linear codec narrower than the payload rounds each
    contribution to the wire grid before the (f32-accumulated) sum — the
    decode-at-receiver semantics of the collective XLA engine; widening
    is exact, so the f32 default stays bit-identical *and* copy-free on
    bf16 payloads. Quantised codecs fake-quant the contributions
    (stochastic rounding keyed per group); the "ring" engine additionally
    re-quantises the running partial on every replayed hop, matching the
    collective ring's int8 RDMA wire. The stateful ``ef`` recovery takes
    the *stacked* residual via ``ef_state`` (same structure as ``tree``,
    per-worker residuals) and returns ``(out_tree, new_ef_state)``.
    """
    if plan is None:
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        if masks is not None:
            s = masks[0].shape[-1]
        plan = plan_lib.per_leaf_plan(per_worker, n, s)
    wire = plan.wire if wire is None else wire
    recovery = plan.recovery if recovery is None else recovery
    codec = wire_lib.resolve_codec(wire, rs_dtype)
    rec = wire_lib.make_recovery(recovery, p=p)
    use_ef = rec.needs_state
    if use_ef and ef_state is None:
        raise ValueError("recovery='ef' needs ef_state= (the stacked "
                         "residual; wire.init_ef_state(tree) to start)")
    if use_ef and corruption is not None:
        raise ValueError(
            "corruption with recovery='ef' is unsupported: the EF "
            "residual telescopes an *honest* sender's codec error — an "
            "adversarial wire breaks the feedback loop; use a robust "
            "recovery (median/trimmed/clip)")
    rs, ag = _resolve_masks(key, n, p, plan, masks)
    cmasks = _resolve_corruption(
        corruption, corrupt_masks, key, n, plan.s,
        n_buckets=plan.n_buckets if plan.per_bucket_masks else None)
    from repro.telemetry import taps
    if taps.active() is not None:
        # step-level counters: whole-draw per-link bundle (summed over
        # the bucket dim for per-bucket masks) + the per-bucket × per-link
        # RS matrix when the draw has one; same convention as the
        # per-call taps in _exchange_table (owners excluded)
        from repro.telemetry import counters as _ctr
        for k_, v in _ctr.mask_step_stats(rs, ag).items():
            taps.emit(k_, v)
        if late is not None:
            # async lateness bundle (DESIGN §15): the masks are already
            # deadline-arbitrated; this only counts what arrived late
            for k_, v in _ctr.staleness_stats(late["rs"],
                                              late["ag"]).items():
                taps.emit(k_, v)
        if cmasks is not None:
            # corruption bundle (DESIGN §17): what arrived *wrong*
            for k_, v in _ctr.corruption_stats(cmasks, rs).items():
                taps.emit(k_, v)
        if rs.ndim == 3:
            own_ = ~owner_mask(n, plan.s)
            taps.emit("rs_bucket_link_delivered",
                      jnp.sum(rs & own_, axis=-1, dtype=jnp.int32))
        taps.annotate("plan", {
            "n_buckets": plan.n_buckets, "s": plan.s,
            "rs_leg_bytes": int(plan.rs_leg_bytes(codec))})
        taps.annotate("exchange", {
            "n": n, "s": plan.s, "mode": mode, "engine": engine,
            "codec": codec.name, "recovery": rec.kind})
    s = plan.s
    renorm = mode in ("model", "grad_renorm")
    if mode not in ("model", "grad", "grad_renorm"):
        raise ValueError(mode)
    if engine in (None, "auto"):
        engine = "xla"
    elif engine not in ("xla", "ring"):
        raise ValueError(f"engine={engine!r}")
    if rec.needs_table:
        # robust recoveries aggregate the pre-reduce table (DESIGN §17)
        if mode == "grad":
            raise ValueError(
                f"recovery={rec.kind!r} needs the renormalising modes "
                "(model/grad_renorm); the naive 'grad' mode has no "
                "per-contribution table semantics")
        if engine == "ring":
            raise ValueError(
                f"recovery={rec.kind!r} needs the pre-reduce per-worker "
                "table; the ring engine reduces on the hops and never "
                "materialises it — use engine='xla' (the 'auto' default "
                "falls back to xla automatically)")
    backend = _resolve_global_backend(backend)
    # the Pallas masked-average kernel renormalises by the received count
    # internally — any other divisor (the scale recovery) or aggregate
    # (the robust table kinds) takes the einsum/robust path
    use_pallas = backend == "pallas" and renorm and engine == "xla" \
        and rec.kind != "scale" and not rec.needs_table
    if use_pallas:
        from repro.kernels.masked_avg import masked_avg_grid_pallas
        interp = jax.default_backend() != "tpu"
    if engine == "ring":
        from repro.kernels.rps_ring import ring_global_sums
        own = owners(n, s)

    def to_wire(x, k_enc):
        """A contribution's wire representation. Linear: round to the
        wire grid only when it actually narrows (widening is exact — the
        native stack is kept, no copy). Quantised: per-(worker, block)
        scales over the payload dim."""
        if codec.quantized:
            return codec.fake_quant(x, k_enc, lead=2)
        if jnp.dtype(codec.wire_dtype).itemsize < jnp.dtype(x.dtype).itemsize:
            return x.astype(codec.wire_dtype)
        return x

    tables = plan.gather(tree, lead=1)        # each (n, s, blk, m)
    ef_tables = plan.gather(ef_state, lead=1) if use_ef else None
    outs: list = [None] * len(tables)
    ef_outs: list = [None] * len(tables)
    for g_idx, ((blk, m, _dt), idxs) in \
            enumerate(_global_groups(plan).items()):
        G = len(idxs)
        d = blk * m
        tail = _rows(d)
        ex = (Ellipsis,) + (None,) * len(tail)    # (G, n, s) → payload
        stack = jnp.stack([tables[j].reshape((n, s) + tail) for j in idxs])
        k_g = jax.random.fold_in(jax.random.fold_in(key, 0x77697265),
                                 g_idx) if codec.quantized else None
        if rs.ndim == 3:
            rs_g = jnp.stack([rs[j] for j in idxs]).astype(jnp.float32)
            ag_g = jnp.stack([ag[j] for j in idxs])
        else:
            rs_g = jnp.broadcast_to(rs.astype(jnp.float32), (G, n, s))
            ag_g = jnp.broadcast_to(ag, (G, n, s))
        if cmasks is not None:
            # adversarial senders (DESIGN §17): transform the offered
            # contributions BEFORE the codec — `stack` (the honest local
            # copies, the AG fallback) is untouched
            if cmasks.ndim == 3:
                cm_g = jnp.stack([cmasks[j] for j in idxs])
            else:
                cm_g = jnp.broadcast_to(cmasks, (G, n, s))
            k_c = jax.random.fold_in(
                jax.random.fold_in(key, _CORRUPT_TAG), g_idx)
            stack_wire = corruption.apply(stack, cm_g[ex], k_c)
        else:
            stack_wire = stack
        if use_ef:
            # EF: send the residual-compensated intent; this round's
            # codec error becomes next round's replayed residual.
            # Delivery-aware (DESIGN §13): a dropped block's residual
            # stays outstanding — only delivered blocks take the fresh
            # error, preserving the per-worker telescoping under drops.
            # deterministic encode under EF (see rps_exchange_plan): the
            # feedback loop unbiases, dither would only add variance
            ef_stack = jnp.stack(
                [ef_tables[j].reshape((n, s) + tail) for j in idxs]
            ).astype(stack.dtype)
            intent = stack + ef_stack
            send = to_wire(intent, None) if codec.quantized \
                else codec.fake_quant(intent)
            resid = jnp.where(rs_g[ex] != 0,
                              intent - send.astype(stack.dtype), ef_stack)
            for pos, j in enumerate(idxs):
                ef_outs[j] = resid[pos].astype(stack.dtype) \
                    .reshape(n, s, blk, m)
            if taps.active() is not None:
                taps.emit("ef_resid_sq",
                          jnp.sum(jnp.square(ef_stack.astype(jnp.float32))))
        else:
            send = to_wire(stack_wire, k_g)
        div_g = _divisor(rec, mode, rs_g, n)                 # (G, s) f32
        if taps.active() is not None:
            taps.emit("divisor", div_g)
        if rec.needs_table:
            # robust aggregate over the pre-reduce table (DESIGN §17):
            # (G, n, s, d) → worker axis at -2 per (group, block) site,
            # masked by the delivery pattern — exactly the table the
            # collective xla path gathers
            table = send.astype(jnp.float32).reshape(G, n, s, d) \
                .transpose(0, 2, 1, 3)
            tilde = robust_lib.robust_aggregate(
                table, rs_g.transpose(0, 2, 1) != 0, rec)    # (G, s, d)
            tilde = tilde.reshape((G, s) + tail)
        elif engine == "ring":                # wire-dtype ring-order sums
            # the replay accumulates in the codec's accumulation dtype
            # (the wire itself for linear codecs — resolving wire= and
            # the legacy rs_dtype knob identically; f32 for quantised)
            sums = ring_global_sums(send.reshape(G, n, s, d), rs_g, own,
                                    rs_dtype=codec.accum_dtype,
                                    codec=codec).reshape((G, s) + tail)
            tilde = sums / div_g[ex].astype(sums.dtype)
        elif use_pallas:
            # the kernel casts per-VMEM-tile internally: no (G,n,s,d)
            # f32 copy of the stack is ever materialised
            blocks_k = jnp.moveaxis(send, 2, 1).reshape((G * s, n) + tail)
            mask_k = rs_g.transpose(0, 2, 1).reshape(G * s, n)
            tilde = masked_avg_grid_pallas(
                blocks_k, mask_k, interpret=interp).reshape((G, s) + tail)
        else:
            # the contraction runs on the *native*-dtype stack with f32
            # accumulation (preferred_element_type): a 0/1 mask is exact
            # in any float dtype and bf16→f32 products are exact, so the
            # sums are bit-identical to the old promote-then-einsum — but
            # no full-stack f32 copy is ever materialised
            sums = jnp.einsum("gij,gij...->gj...", rs_g.astype(send.dtype),
                              send, preferred_element_type=jnp.float32)
            tilde = sums / div_g[ex]
        gathered = tilde.astype(stack.dtype)[:, None]  # AG moves payload
        if renorm:
            # the AG fallback *is* the input stack — no f32 copy of it
            out = jnp.where(ag_g[ex], gathered, stack)
        else:
            # grad mode: a dropped block means no update — multiply by
            # the mask instead of materialising a zeros fallback
            out = gathered * ag_g[ex].astype(stack.dtype)
        for pos, j in enumerate(idxs):
            outs[j] = out[pos].reshape(n, s, blk, m)
    if use_ef:
        return plan.scatter(outs, lead=1), plan.scatter(ef_outs, lead=1)
    return plan.scatter(outs, lead=1)


def reliable_average(tree: Any, axis_name: AxisNames) -> Any:
    """Baseline: exact mean over the axes (reliable network)."""
    n = axis_size(axis_name)
    names = _axis_tuple(axis_name)

    def avg(x):
        for a in names:
            x = lax.psum(x, a)
        return x / n

    return jax.tree.map(avg, tree)
