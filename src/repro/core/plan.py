"""Bucketed ExchangePlan — the static layout of one RPS round (DESIGN.md §11).

The paper's exchange is one logical RS+AG round per iteration, but a
parameter *pytree* leaves the lowering a choice: per-leaf collectives (the
seed behaviour — 2 collectives per leaf per round) or coalesced buckets.
Real loss-tolerant transports (LTP-style bundles) coalesce parameters into
fixed-byte buckets that map onto wire packets; this module computes that
layout **once at setup time** so the traced step does no pytree
introspection at all:

  - every leaf is assigned to exactly one *bucket*;
  - tensor-parallel leaves (a ``model_dims`` entry) get their own
    model-dim-preserving bucket — the TP dim rides along intact as a
    trailing ``m`` axis, so no cross-model-axis resharding is triggered;
  - all other leaves coalesce, in pytree order, into contiguous flat
    buffers of at most ``bucket_bytes`` (or split evenly into
    ``n_buckets`` groups);
  - each bucket's payload is laid out as an ``(s, blk, m)`` block table —
    s server blocks (DESIGN.md §10) of ``blk`` elements — with the
    padding precomputed. The owner-major scatter permutation
    (``core.rps._scatter_layout``) is shared by every bucket since s is.

The bucket is also the *packetisation unit*: a fixed-byte bucket plan
(``per_bucket_masks=True``) draws an independent ``(n, s)`` drop-mask pair
per bucket — each bucket column is its own wire packet — so
``model_packets = s × n_buckets`` flows into the §6 theory bounds through
``theory.block_drop_rate`` (each server block spans ``n_buckets`` packets).
The degenerate plans are exactly the legacy layouts and stay bit-identical
to them: :func:`single_bucket_plan` is ``jax.flatten_util.ravel_pytree`` +
``rps_exchange_flat`` (the seed ``rps_exchange``), :func:`per_leaf_plan` is
the seed trainer/simulator per-leaf lowering, and both share one mask draw
across buckets (``per_bucket_masks=False``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire as wire_lib


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One coalesced exchange unit: a contiguous run of pytree leaves laid
    out as an (s, blk, m) block table. ``model_dim`` is set only for
    single-leaf TP buckets (m = that dim's width; 1 otherwise)."""
    leaf_ids: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]     # per-member per-worker shapes
    dtypes: Tuple[str, ...]                 # per-member dtypes
    sizes: Tuple[int, ...]                  # per-member free-element counts
    model_dim: Optional[int]
    m: int                                  # model-dim width (1 = flat)
    free: int                               # Σ sizes (rows before padding)
    blk: int                                # block width: ceil(free / s)
    pad: int                                # s·blk − free padding rows
    dtype: str                              # payload dtype (promoted)


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Static layout of one bucketed RPS round over an n-worker axis with
    s server blocks. Built once at setup (never inside a traced step);
    closed over by the jitted exchange."""
    n: int
    s: int
    buckets: Tuple[Bucket, ...]
    n_leaves: int
    per_bucket_masks: bool
    treedef: Any = dataclasses.field(hash=False)
    engine: str = "xla"
    # the round's lowering (DESIGN.md §12): "xla" = psum_scatter +
    # all_gather per bucket (the seed schedule, bit-identical default);
    # "ring" = the fused ring engine (one Pallas dispatch per bucket on
    # TPU, interpret ppermute ring elsewhere); "auto" = xla.
    wire: str = "f32"
    # RS-leg codec (DESIGN.md §13): "f32" passthrough (bit-identical
    # default), "bf16" linear downcast, "int8" stochastic-rounding
    # quantisation with per-block scales (repro.core.wire).
    recovery: str = "renorm"
    # loss-recovery policy (DESIGN.md §13): "renorm" = paper Algorithm 1,
    # "scale" = unbiased 1/(1−p) zero-fill, "ef" = error-feedback
    # residual carried in trainer/simulator state.
    schedule: str = "sync"
    # round scheduling (DESIGN.md §15): "sync" = all buckets ship at the
    # iteration barrier (the seed semantics, bit-identical default);
    # "async" = buckets ship in reverse-layer order as their gradients
    # become ready during the backward pass, each against its own reduced
    # deadline slack — late packets are dropped-with-recovery, never
    # waited for.
    ready_ms: Optional[Tuple[float, ...]] = None
    # per-bucket readiness times (ms into the backward pass) from the
    # backward-pass cost model (:func:`bucket_ready_ms`); set iff
    # schedule == "async".

    # ---- derived ---------------------------------------------------------
    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def packets_per_block(self) -> int:
        """Wire packets a server block spans: each bucket's column j is its
        own packet under per-bucket masks, one shared packet otherwise."""
        return self.n_buckets if self.per_bucket_masks else 1

    @property
    def model_packets(self) -> int:
        """Total loss-atomic wire packets per model replica per direction —
        the quantity the §6 packetisation bounds take (s·1 = s for the
        legacy shared-mask plans, i.e. the paper's one-packet-per-block
        layout when s = n)."""
        return self.s * self.packets_per_block

    def payload_elems(self) -> int:
        return sum(self.s * b.blk * b.m for b in self.buckets)

    @property
    def ship_order(self) -> Tuple[int, ...]:
        """Bucket dispatch order. Sync ships in plan order at the
        iteration barrier; async ships in **reverse bucket order** — the
        pytree is layer-ordered and the backward pass produces the last
        layer's gradients first, so reversed plan order is ascending
        readiness time (:func:`bucket_ready_ms`)."""
        if self.schedule == "async":
            return tuple(range(self.n_buckets - 1, -1, -1))
        return tuple(range(self.n_buckets))

    def with_ready_ms(self, ready_ms: Sequence[float]) -> "ExchangePlan":
        """The same plan with *measured* per-bucket readiness times in
        place of the cost-model's guess (``--compute-ms=auto``): callers
        time the real backward (``repro.train.simulator.
        measure_bucket_ready_ms``) and substitute here. Only an async
        plan carries readiness; lengths must match the bucket count."""
        if self.schedule != "async":
            raise ValueError("ready_ms only applies to schedule='async'")
        ready = tuple(float(r) for r in ready_ms)
        if len(ready) != self.n_buckets:
            raise ValueError(f"got {len(ready)} readiness times for "
                             f"{self.n_buckets} buckets")
        if any(r < 0 for r in ready):
            raise ValueError(f"negative readiness time in {ready}")
        return dataclasses.replace(self, ready_ms=ready)

    def slack_ms(self, deadline_ms: float) -> np.ndarray:
        """Per-bucket deadline budget under the async schedule:
        ``max(deadline − ready, 0)`` for each bucket (``(n_buckets,)``,
        plan order). A bucket whose gradients arrive after the iteration
        deadline has zero slack — every off-owner packet it offers is
        late by construction and recovery absorbs the whole bucket."""
        if self.ready_ms is None:
            raise ValueError("slack_ms needs an async plan with ready_ms "
                             "(build with schedule='async')")
        return np.maximum(float(deadline_ms)
                          - np.asarray(self.ready_ms, np.float64), 0.0)

    def rs_leg_bytes(self, wire=None) -> int:
        """Bytes one device moves on the RS leg per round: every bucket's
        scatter-padded (S, blk, m) table in the wire dtype (``wire``
        accepts any :func:`repro.core.wire.canon_wire_dtype` spelling;
        ``None`` = the plan's own codec). The int8 codec's tiny f32
        scale side-channel (one scalar per block row) is *excluded* — it
        is reported separately by :meth:`describe` so the headline
        ``rs_bytes_ratio`` is the clean payload ratio (0.25 for int8)."""
        wire = self.wire if wire is None else wire
        S = _ceil_div(self.s, self.n) * self.n
        rs_b = wire_lib.canon_wire_dtype(wire).itemsize
        return sum(S * b.blk * b.m * rs_b for b in self.buckets)

    def wire_bytes(self, rs_dtype=None) -> int:
        """Bytes one device moves per round over every bucket's
        scatter-padded (S, blk, m) table (S = ceil(s/n)·n): the RS leg
        carries the wire-codec dtype (``rs_dtype`` overrides the plan's
        own ``wire`` — any spelling ``canon_wire_dtype`` takes; f32 is
        the paper default, bf16 halves the leg, int8 quarters it), the
        AG leg the payload dtype."""
        S = _ceil_div(self.s, self.n) * self.n
        return self.rs_leg_bytes(rs_dtype) + sum(
            S * b.blk * b.m * jnp.dtype(b.dtype).itemsize
            for b in self.buckets)

    def describe(self, rs_dtype=None) -> dict:
        elems = self.payload_elems()
        free = sum(b.free * b.m for b in self.buckets)
        wire = self.wire if rs_dtype is None else \
            wire_lib.canon_wire_name(rs_dtype)
        S = _ceil_div(self.s, self.n) * self.n
        quantized = wire_lib.make_codec(wire).quantized
        return {"n": self.n, "s": self.s, "n_buckets": self.n_buckets,
                "collectives_per_round": 2 * self.n_buckets,
                "engine": self.engine,
                "wire": wire,
                "recovery": self.recovery,
                "schedule": self.schedule,
                **({"ready_ms": [float(r) for r in self.ready_ms]}
                   if self.ready_ms is not None else {}),
                "per_bucket_masks": self.per_bucket_masks,
                "model_packets": self.model_packets,
                "payload_bytes": int(sum(
                    self.s * b.blk * b.m * jnp.dtype(b.dtype).itemsize
                    for b in self.buckets)),
                "rs_leg_bytes": int(self.rs_leg_bytes(wire)),
                "rs_bytes_ratio": float(self.rs_leg_bytes(wire)
                                        / max(self.rs_leg_bytes("f32"), 1)),
                "scale_bytes": int(4 * S * self.n_buckets) if quantized
                else 0,
                "wire_bytes_per_round": int(self.wire_bytes(wire)),
                "pad_frac": float(1.0 - free / elems) if elems else 0.0}

    # ---- gather / scatter ------------------------------------------------
    def _check(self, leaves: Sequence[jax.Array], lead: int) -> None:
        if len(leaves) != self.n_leaves:
            raise ValueError(f"plan built for {self.n_leaves} leaves, "
                             f"tree has {len(leaves)}")
        for b in self.buckets:
            for lid, shp in zip(b.leaf_ids, b.shapes):
                got = tuple(leaves[lid].shape[lead:])
                if got != shp:
                    raise ValueError(
                        f"leaf {lid} shape {got} != plan shape {shp} "
                        f"(lead={lead}) — rebuild the plan for this tree")

    def check_leaves(self, tree: Any, lead: int = 0) -> list:
        """Flatten ``tree`` and validate it against the plan's shapes.
        Returns the leaf list — the input :meth:`gather_bucket` takes, so
        a pipelined per-bucket loop flattens/validates exactly once."""
        leaves = jax.tree.flatten(tree)[0]
        self._check(leaves, lead)
        return leaves

    def gather_bucket(self, leaves: Sequence[jax.Array], b: int,
                      lead: int = 0) -> jax.Array:
        """Bucket ``b``'s (lead…, s, blk, m) block table from a
        :meth:`check_leaves` leaf list. Coalesced buckets promote members
        to the bucket dtype exactly like ``ravel_pytree`` does."""
        bk = self.buckets[b]
        lshape = tuple(leaves[bk.leaf_ids[0]].shape[:lead])
        if bk.model_dim is not None:
            x = jnp.moveaxis(leaves[bk.leaf_ids[0]], lead + bk.model_dim,
                             -1)
            seg = x.reshape(lshape + (bk.free, bk.m))
        else:
            parts = [leaves[i].reshape(lshape + (-1,)).astype(bk.dtype)
                     for i in bk.leaf_ids]
            seg = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=lead)
            seg = seg[..., None]
        if bk.pad:
            seg = jnp.pad(seg, ((0, 0),) * lead
                          + ((0, bk.pad), (0, 0)))
        return seg.reshape(lshape + (self.s, bk.blk, bk.m))

    def gather(self, tree: Any, lead: int = 0) -> list:
        """Tree -> list of (lead…, s, blk, m) block tables, one per bucket.
        ``lead`` leading dims (e.g. the stacked worker dim of the global
        path) are preserved."""
        leaves = self.check_leaves(tree, lead)
        return [self.gather_bucket(leaves, b, lead)
                for b in range(self.n_buckets)]

    def scatter(self, tables: Sequence[jax.Array], lead: int = 0) -> Any:
        """Inverse of :meth:`gather`: block tables back to the pytree
        (members restored to their own dtypes/shapes)."""
        new_leaves: list = [None] * self.n_leaves
        for b, tbl in zip(self.buckets, tables):
            lshape = tuple(tbl.shape[:lead])
            seg = tbl.reshape(lshape + (self.s * b.blk, b.m))
            if b.pad:
                seg = seg[..., :b.free, :]
            if b.model_dim is not None:
                shp = b.shapes[0]
                rest = tuple(d for j, d in enumerate(shp)
                             if j != b.model_dim)
                inter = seg.reshape(lshape + rest + (b.m,))
                new_leaves[b.leaf_ids[0]] = jnp.moveaxis(
                    inter, -1, lead + b.model_dim).astype(b.dtypes[0])
            else:
                off = 0
                for lid, sz, shp, dt in zip(b.leaf_ids, b.sizes, b.shapes,
                                            b.dtypes):
                    piece = seg[..., off:off + sz, 0]
                    new_leaves[lid] = piece.reshape(lshape + shp).astype(dt)
                    off += sz
        return jax.tree.unflatten(self.treedef, new_leaves)


def _leaf_meta(leaves) -> Tuple[list, list, list]:
    shapes = [tuple(int(d) for d in x.shape) for x in leaves]
    dtypes = [jnp.dtype(x.dtype).name for x in leaves]
    sizes = [int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes]
    return shapes, dtypes, sizes


def _flat_bucket(ids, shapes, dtypes, sizes, s: int) -> Bucket:
    free = sum(sizes[i] for i in ids)
    blk = max(_ceil_div(free, s), 1)
    dtype = jnp.dtype(jnp.result_type(*[dtypes[i] for i in ids])).name
    return Bucket(leaf_ids=tuple(ids),
                  shapes=tuple(shapes[i] for i in ids),
                  dtypes=tuple(dtypes[i] for i in ids),
                  sizes=tuple(sizes[i] for i in ids),
                  model_dim=None, m=1, free=free, blk=blk,
                  pad=s * blk - free, dtype=dtype)


def _tp_bucket(i, shapes, dtypes, model_dim: int, s: int) -> Bucket:
    shp = shapes[i]
    model_dim = model_dim % len(shp)
    m = shp[model_dim]
    free = int(np.prod(shp, dtype=np.int64)) // m
    blk = max(_ceil_div(free, s), 1)
    return Bucket(leaf_ids=(i,), shapes=(shp,), dtypes=(dtypes[i],),
                  sizes=(free,), model_dim=model_dim, m=m, free=free,
                  blk=blk, pad=s * blk - free, dtype=dtypes[i])


def _flatten_model_dims(model_dims: Any, n_leaves: int) -> list:
    if model_dims is None:
        return [None] * n_leaves
    md = jax.tree.flatten(model_dims, is_leaf=lambda x: x is None)[0]
    if len(md) != n_leaves:
        raise ValueError(f"model_dims has {len(md)} leaves, tree has "
                         f"{n_leaves}")
    return md


def bucket_ready_ms(buckets: Sequence[Bucket],
                    compute_ms: float) -> Tuple[float, ...]:
    """Per-bucket gradient readiness times from the backward-pass cost
    model (DESIGN.md §15). The pytree is layer-ordered and backward
    visits layers last → first, so bucket ``b``'s gradients are complete
    once the backward has covered buckets ``b..B−1``; cost is modelled as
    proportional to payload size (dense layers: backward FLOPs and bytes
    both scale with the parameter count). ``ready[B−1]`` is earliest,
    ``ready[0] == compute_ms`` (the first layer's grads close the pass).
    """
    if compute_ms <= 0:
        raise ValueError(f"compute_ms={compute_ms} must be > 0")
    sizes = np.array([b.free * b.m for b in buckets], np.float64)
    rev_cum = np.cumsum(sizes[::-1])[::-1]          # Σ sizes[b:]
    return tuple(float(compute_ms) * rev_cum / rev_cum[0])


def _canon_pipeline(wire, recovery):
    """Validated (wire, recovery) plan fields from any spelling."""
    wire = wire_lib.canon_wire_name("f32" if wire is None else wire)
    wire_lib.make_codec(wire)                      # validate
    recovery = "renorm" if recovery is None else str(recovery)
    # validate + canonicalise through the wire layer — accepts
    # parameterised robust specs ("trimmed:beta=0.3") and round-trips
    # them to their canonical spelling (DESIGN.md §17)
    return wire, wire_lib.make_recovery(recovery).spec


def make_plan(tree: Any, n: int, s: Optional[int] = None, *,
              bucket_bytes: Optional[float] = None,
              n_buckets: Optional[int] = None,
              model_dims: Any = None,
              per_bucket_masks: Optional[bool] = None,
              engine: str = "xla", wire: str = "f32",
              recovery: str = "renorm", schedule: str = "sync",
              compute_ms: Optional[float] = None) -> ExchangePlan:
    """Build an :class:`ExchangePlan` for ``tree`` (arrays or
    ShapeDtypeStructs — only shapes/dtypes are read).

    ``bucket_bytes`` — greedy fixed-byte coalescing (a leaf larger than the
    budget gets its own bucket; leaves are never split). ``n_buckets`` —
    split the coalesced payload into that many size-balanced contiguous
    groups instead. Neither → one single bucket (the ``ravel_pytree``
    layout). Leaves with a ``model_dims`` entry are pulled out into
    model-dim-preserving buckets of their own in every mode.

    ``per_bucket_masks`` defaults to True exactly when a bucketing knob is
    given: fixed-byte buckets are wire packets and draw independent masks;
    the degenerate plans keep the legacy one-draw-per-round semantics.

    ``engine`` picks the round's lowering (DESIGN.md §12): "xla" (the
    seed two-collectives-per-bucket schedule, bit-identical default),
    "ring" (the fused ring engine) or "auto" (xla, DESIGN.md §12).

    ``wire``/``recovery`` pick the wire pipeline (DESIGN.md §13): the
    RS-leg codec ("f32" bit-identical default / "bf16" / "int8") and the
    loss-recovery policy ("renorm" paper default / "scale" / "ef") every
    executor of this plan applies.

    ``schedule`` picks the round scheduling (DESIGN.md §15): "sync" (the
    seed iteration-barrier semantics, bit-identical default) or "async"
    (buckets ship in reverse-layer order as gradients become ready;
    requires ``compute_ms`` — the modelled backward-pass duration the
    per-bucket readiness times are derived from).
    """
    if n < 1:
        raise ValueError(f"need n >= 1 workers, got {n}")
    s = n if s is None else int(s)
    if s < 1:
        raise ValueError(f"need s >= 1 server blocks, got {s}")
    if bucket_bytes is not None and n_buckets is not None:
        raise ValueError("give bucket_bytes or n_buckets, not both")
    if n_buckets is not None and int(n_buckets) < 1:
        raise ValueError(f"need n_buckets >= 1, got {n_buckets}")
    if bucket_bytes is not None and float(bucket_bytes) <= 0:
        raise ValueError(f"need bucket_bytes > 0, got {bucket_bytes}")
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot plan an empty pytree")
    shapes, dtypes, sizes = _leaf_meta(leaves)
    mdims = _flatten_model_dims(model_dims, len(leaves))

    flat_ids = [i for i in range(len(leaves)) if mdims[i] is None]
    tp_ids = [i for i in range(len(leaves)) if mdims[i] is not None]

    groups: list = []
    if flat_ids:
        if n_buckets is not None:
            k = max(1, min(int(n_buckets), len(flat_ids)))
            total = sum(sizes[i] for i in flat_ids)
            cur: list = []
            acc = 0
            for idx, i in enumerate(flat_ids):
                cur.append(i)
                acc += sizes[i]
                left = len(flat_ids) - idx - 1   # leaves still unassigned
                need = k - len(groups) - 1       # groups still to fill
                # close at the next evenly-spaced size boundary, or when
                # the remaining leaves are exactly one per remaining group
                if len(groups) < k - 1 and (
                        acc >= total * (len(groups) + 1) / k
                        or left == need):
                    groups.append(cur)
                    cur = []
            if cur:
                groups.append(cur)
        elif bucket_bytes is not None:
            cap = max(float(bucket_bytes), 1.0)
            cur, acc = [], 0.0
            for i in flat_ids:
                nbytes = sizes[i] * jnp.dtype(dtypes[i]).itemsize
                if cur and acc + nbytes > cap:
                    groups.append(cur)
                    cur, acc = [], 0.0
                cur.append(i)
                acc += nbytes
            if cur:
                groups.append(cur)
        else:
            groups.append(list(flat_ids))

    buckets = [_flat_bucket(g, shapes, dtypes, sizes, s) for g in groups]
    buckets += [_tp_bucket(i, shapes, dtypes, mdims[i], s) for i in tp_ids]
    if per_bucket_masks is None:
        per_bucket_masks = bucket_bytes is not None or n_buckets is not None
    wire, recovery = _canon_pipeline(wire, recovery)
    schedule = "sync" if schedule is None else str(schedule)
    if schedule not in ("sync", "async"):
        raise ValueError(f"schedule={schedule!r}, want 'sync' or 'async'")
    ready: Optional[Tuple[float, ...]] = None
    if schedule == "async":
        if compute_ms is None:
            raise ValueError("schedule='async' needs compute_ms (the "
                             "modelled backward-pass duration readiness "
                             "times are derived from)")
        ready = bucket_ready_ms(buckets, float(compute_ms))
    elif compute_ms is not None:
        raise ValueError("compute_ms only applies to schedule='async'")
    return ExchangePlan(n=int(n), s=s, buckets=tuple(buckets),
                        n_leaves=len(leaves),
                        per_bucket_masks=bool(per_bucket_masks),
                        treedef=treedef, engine=str(engine),
                        wire=wire, recovery=recovery,
                        schedule=schedule, ready_ms=ready)


def plan_from_config(tree: Any, n: int, s: Optional[int] = None, *,
                     bucket_mb: Optional[float] = None,
                     n_buckets: Optional[int] = None,
                     model_dims: Any = None,
                     engine: str = "xla", wire: str = "f32",
                     recovery: str = "renorm", schedule: str = "sync",
                     compute_ms: Optional[float] = None) -> ExchangePlan:
    """The config-knob → plan policy shared by the trainer and the
    simulator: ``bucket_mb`` MiB fixed-byte coalescing / ``n_buckets``
    size-balanced groups (packetised, per-bucket masks), both unset → the
    per-leaf legacy plan, bit-identical to the seed lowering. ``engine``
    threads the §12 lowering knob, ``wire``/``recovery`` the §13 wire
    pipeline, ``schedule``/``compute_ms`` the §15 async overlap mode
    into the plan."""
    if bucket_mb is not None or n_buckets is not None:
        return make_plan(tree, n, s,
                         bucket_bytes=(bucket_mb * 2 ** 20
                                       if bucket_mb is not None else None),
                         n_buckets=n_buckets, model_dims=model_dims,
                         engine=engine, wire=wire, recovery=recovery,
                         schedule=schedule, compute_ms=compute_ms)
    return per_leaf_plan(tree, n, s, engine=engine, wire=wire,
                         recovery=recovery, schedule=schedule,
                         compute_ms=compute_ms)


def single_bucket_plan(tree: Any, n: int, s: Optional[int] = None, *,
                       engine: str = "xla", wire: str = "f32",
                       recovery: str = "renorm") -> ExchangePlan:
    """The legacy ``rps_exchange`` layout: every leaf ravelled into one
    flat bucket (same member order and dtype promotion as
    ``ravel_pytree``), one shared mask draw — bit-identical to the seed."""
    return make_plan(tree, n, s, engine=engine, wire=wire,
                     recovery=recovery)


def per_leaf_plan(tree: Any, n: int, s: Optional[int] = None, *,
                  engine: str = "xla", wire: str = "f32",
                  recovery: str = "renorm", schedule: str = "sync",
                  compute_ms: Optional[float] = None) -> ExchangePlan:
    """The legacy trainer/simulator layout: one bucket per leaf (each leaf
    fully flattened — no model-dim special-casing, exactly the seed's
    per-leaf ``rps_exchange_flat`` tree-map), one shared mask draw."""
    if n < 1:
        raise ValueError(f"need n >= 1 workers, got {n}")
    s = n if s is None else int(s)
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot plan an empty pytree")
    shapes, dtypes, sizes = _leaf_meta(leaves)
    buckets = tuple(_flat_bucket([i], shapes, dtypes, sizes, s)
                    for i in range(len(leaves)))
    wire, recovery = _canon_pipeline(wire, recovery)
    schedule = "sync" if schedule is None else str(schedule)
    if schedule not in ("sync", "async"):
        raise ValueError(f"schedule={schedule!r}, want 'sync' or 'async'")
    ready: Optional[Tuple[float, ...]] = None
    if schedule == "async":
        if compute_ms is None:
            raise ValueError("schedule='async' needs compute_ms")
        ready = bucket_ready_ms(buckets, float(compute_ms))
    elif compute_ms is not None:
        raise ValueError("compute_ms only applies to schedule='async'")
    return ExchangePlan(n=int(n), s=s, buckets=buckets,
                        n_leaves=len(leaves), per_bucket_masks=False,
                        treedef=treedef, engine=str(engine),
                        wire=wire, recovery=recovery,
                        schedule=schedule, ready_ms=ready)


def decode_plan(d_model: int, batch: int, n: int,
                s: Optional[int] = None, *, dtype=jnp.float32,
                engine: str = "xla", wire: str = "f32",
                recovery: str = "renorm") -> ExchangePlan:
    """Decode-shaped plan for serving-time activation collectives
    (DESIGN.md §18): one bucket over a single ``(d_model, batch)`` leaf —
    one decode token's layer output for the whole in-flight batch,
    **model-dim major** so the s server blocks slice ``d_model``. Each
    wire packet therefore carries a contiguous d-slice shared across
    requests, which is how a tensor-parallel all-reduce packetises on a
    real fabric: losing a packet degrades one feature slice of *every*
    request slightly rather than one request completely. Built once per
    engine at setup (the decode shape is static); the per-site drop masks
    come from ``Channel.sample_packets(key, state, n_buckets=2·L)``
    drawn every decode step."""
    leaf = jax.ShapeDtypeStruct((int(d_model), int(batch)),
                                jnp.dtype(dtype))
    return make_plan(leaf, n, s, engine=engine, wire=wire,
                     recovery=recovery)
