"""Fused drop-masked ring RS+AG: one Pallas dispatch per bucket (DESIGN §12).

The XLA engine (``core.rps._exchange_table``, engine="xla") lowers every
bucket's round as two opaque collectives — ``psum_scatter`` then
``all_gather`` — so the drop-mask multiply, the renormalisation and the
AG-select each run as separate memory-bound passes and nothing overlaps
communication with compute. This module is the "ring" engine: the same
drop-masked RS+AG round executed as an explicit bi-phase ring schedule,

  RS phase   n−1 ring hops; the partial sum for server chunk c travels
             c+1 → c+2 → … → c, each host adding its own *rs-mask-gated*
             contribution in the wire dtype (``rs_dtype`` — bf16 halves
             the RS bytes);
  turnaround the owner renormalises its chunk by the received count
             (computable locally — the mask is known everywhere);
  AG phase   n−1 ring hops broadcasting the averaged chunks; each chunk
             is AG-mask-selected against the local block as it lands, so
             the fallback copy never materialises.

Two implementations share that schedule *step for step* (same adds in the
same order, so they agree bitwise whenever the sums are exact):

  - :func:`ring_exchange_scatter_table` with ``use_kernel=False`` — the
    **interpret-mode ring**: ``lax.ppermute`` transport + jnp compute.
    This is the engine every CPU test and the parity matrix runs; it is
    bit-identical to the XLA engine on exactly-summable data
    (tests/test_ring.py) and within accumulation-order ULPs otherwise.
  - :func:`ring_bucket_fused` — the TPU Pallas kernel: ONE ``pallas_call``
    per bucket for the whole round. The n−1 hops per phase are
    ``pltpu.make_async_remote_copy`` RDMAs, double-buffered over two comm
    slots so hop t's DMA overlaps the masked accumulate of hop t−1's
    payload; capacity handshakes (REGULAR semaphores signalled to the
    left neighbour) keep a sender from overwriting a slot the receiver
    has not drained. The bucket table is donated into the output
    (``input_output_aliases``), so the dispatch is in-place.

The kernel cannot execute on this repo's CPU CI, but its Mosaic lowering
is validated from any host via ``jax.export`` with ``platforms=("tpu",)``
— tests/test_ring.py asserts the exported module carries exactly **one**
``tpu_custom_call`` per bucket (the ISSUE's fused-dispatch claim) through
``tools/check_hlo.py``.

Layout contract (identical to the XLA engine): the table arrives in
owner-major scatter order — S = k·n rows, device i owning rows
[i·k, (i+1)·k) — with masks already padded/permuted by
``core.rps._masks_to_scatter``. Everything here happens *inside* that
layout; ``_exchange_table`` owns the pad/permute/crop.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

LANE = 128          # TPU lane width: trailing dim of the comm buffers


def _axis_arg(names: Tuple[str, ...]):
    return names if len(names) > 1 else names[0]


# ---------------------------------------------------------------------------
# Interpret-mode ring: lax.ppermute transport + jnp compute
# ---------------------------------------------------------------------------

def _ring_schedule_jax(blocks: jax.Array, rs_sc: jax.Array, ag_sc: jax.Array,
                       *, names: Tuple[str, ...], n: int, i: jax.Array,
                       k: int, mode: str, rs_dtype,
                       pin: Optional[Callable] = None,
                       codec=None, send: Optional[jax.Array] = None,
                       div: Optional[jax.Array] = None) -> jax.Array:
    """The ring schedule at the JAX level — the interpret-mode engine.

    blocks: (S, blk[, m]) scatter-ordered local table (S = k·n);
    rs_sc/ag_sc: (n, S) scatter-ordered masks. Mirrors the Pallas kernel
    hop for hop: chunk c's partial is initiated by device c+1 and
    accumulates contributions in ring order c+1, c+2, …, c (owner last),
    all in the wire dtype ``rs_dtype``.

    Wire pipeline (DESIGN.md §13): ``send`` overrides the contribution
    source (decoded wire-grid values — a quantised codec's fake-quant
    table or the EF-compensated intent); a quantised ``codec``
    additionally re-encodes the running partial on *every hop* — the
    int8 payload plus its per-row f32 scale travel, the receiver decodes
    before adding — exactly the transport the fused kernel RDMAs.
    ``div`` is the (S,) f32 recovery divisor, computed by the one policy
    point ``core.rps._divisor`` (this module never re-derives it).
    """
    if pin is None:
        def pin(x):
            return x
    trail = blocks.ndim - 1
    wide = (slice(None),) + (None,) * trail
    axis = _axis_arg(names)
    perm = [(j, (j + 1) % n) for j in range(n)]
    src = blocks if send is None else send
    rs_i = rs_sc.astype(rs_dtype)[i]                       # (S,) my row
    quantized = codec is not None and codec.quantized

    def contrib(c):
        b = lax.dynamic_slice_in_dim(src, c * k, k, 0).astype(rs_dtype)
        m = lax.dynamic_slice_in_dim(rs_i, c * k, k, 0)
        return b * m[wide]

    # ---- RS phase: n−1 hops of masked partial sums (wire dtype) ----------
    with jax.named_scope("ring.rs_hops"):
        acc = pin(contrib(jnp.mod(i - 1, n)))
        for t in range(n - 1):
            if quantized:
                # the hop carries the wire payload + per-row scales; the
                # receiver decodes before accumulating (matching the kernel)
                q, sc = codec.encode(acc, None, lead=0)
                q = pin(lax.ppermute(q, axis, perm))
                sc = pin(lax.ppermute(sc, axis, perm))
                acc = codec.decode(q, sc)
            else:
                acc = pin(lax.ppermute(acc, axis, perm))
            acc = pin(acc + contrib(jnp.mod(i - 2 - t, n)))

    # ---- turnaround: owner applies the recovery divisor ------------------
    with jax.named_scope("ring.recovery"):
        if div is None:
            from repro.core.rps import _divisor
            from repro.core.wire import make_recovery
            div = _divisor(make_recovery(None), mode, rs_sc, n)
        my_div = lax.dynamic_slice_in_dim(div, i * k, k).astype(rs_dtype)
        tilde = acc / my_div[wide]

    # ---- AG phase: n−1 hops broadcasting the averaged chunks -------------
    with jax.named_scope("ring.ag_hops"):
        cur = pin(tilde.astype(blocks.dtype))              # AG moves payload
        gathered = lax.dynamic_update_slice_in_dim(
            jnp.zeros_like(blocks), cur, i * k, 0)
        for t in range(n - 1):
            cur = pin(lax.ppermute(cur, axis, perm))
            gathered = lax.dynamic_update_slice_in_dim(
                gathered, cur, jnp.mod(i - 1 - t, n) * k, 0)

    with jax.named_scope("ring.decode"):
        recv = ag_sc[i][wide]
        if mode == "model" or mode == "grad_renorm":
            return pin(jnp.where(recv, gathered, blocks))  # keep local block
        return pin(jnp.where(recv, gathered, jnp.zeros_like(blocks)))


# ---------------------------------------------------------------------------
# The fused TPU kernel: one pallas_call per bucket
# ---------------------------------------------------------------------------

def _drain_steps(n: int):
    """Steps whose send-DMAs / capacity signals are still outstanding when
    the n−1-hop loop exits: the last min(2, n−1) steps."""
    return range(max(0, n - 3), n - 1)


def _make_ring_kernel(*, n: int, k: int, W: int, mode: str, rs_dtype,
                      payload_dtype, wire_dtype=None, levels: int = 0,
                      has_enc: bool = False):
    """Kernel factory. Scalars (SMEM): my ring position and the *logical*
    device ids of the left/right ring neighbours (precomputed by the
    caller — inside a shard_map the kernel itself cannot know the full
    mesh). VMEM operands: the (S, W) table, my rs row and the ag row as
    (S, 1) columns, and the (S, 1) recovery divisor.

    Wire pipeline (DESIGN.md §13), two orthogonal capabilities:

      ``has_enc``    the contribution source arrives as a separate
                     encoded table (qt, per-row scales qs) — decode is
                     fused into the gated accumulate; the raw payload
                     table stays the AG fallback. Quantised codecs and
                     the EF recovery's compensated send both use this.
      ``levels > 0`` the *hops* are quantised: every RS hop re-encodes
                     the f32 partial onto the ``wire_dtype`` (int8) grid
                     — the RDMA payload is int8 and its (k, 1) scales
                     travel as a LANE-wide f32 side-channel in a second
                     remote copy sharing the slot's capacity handshake.

    One ``pallas_call`` per bucket in every variant — the codec never
    adds a dispatch."""
    import jax.experimental.pallas.tpu as pltpu
    from jax.experimental import pallas as pl

    renorm = mode in ("model", "grad_renorm")
    requant = levels > 0

    def kernel(pos_ref, left_ref, right_ref, table_ref, rs_ref, ag_ref,
               cnt_ref, *refs):
        if has_enc:
            qt_ref, qs_ref = refs[0], refs[1]
            refs = refs[2:]
        out_ref = refs[0]
        if requant:
            (acc, send_buf, recv_buf, scale_send, scale_recv,
             ag_send, ag_recv,
             send_sem, recv_sem, ssend_sem, srecv_sem,
             ag_send_sem, ag_recv_sem, cap_sem, ag_cap_sem) = refs[1:]
        else:
            (acc, send_buf, recv_buf, ag_send, ag_recv,
             send_sem, recv_sem, ag_send_sem, ag_recv_sem,
             cap_sem, ag_cap_sem) = refs[1:]
        i = pos_ref[0]
        left, right = left_ref[0], right_ref[0]

        # Neighbour barrier: nobody RDMAs into a peer that has not entered
        # the kernel yet (the collective_id barrier semaphore).
        barrier = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(barrier, inc=1, device_id=nb,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, 2)

        def chunk_rows(c):
            # k is padded to the packed sublane tile of every sub-32-bit
            # operand (ring_bucket_fused), so Mosaic can prove alignment
            return pl.ds(pl.multiple_of(c * k, k), k)

        def contrib(c):
            rows = chunk_rows(c)
            if has_enc:     # decode fused into the gated accumulate
                blk = qt_ref[rows, :].astype(rs_dtype) \
                    * qs_ref[rows, :].astype(rs_dtype)
            else:
                blk = table_ref[rows, :].astype(rs_dtype)      # (k, W)
            m = rs_ref[rows, :].astype(rs_dtype)               # (k, 1)
            return blk * m

        # ---- RS phase --------------------------------------------------
        acc[...] = contrib(lax.rem(i + n - 1, n))
        rs_dmas = []
        for t in range(n - 1):
            slot = t % 2
            if t >= 2:
                for d in rs_dmas[t - 2]:
                    d.wait_send()                # slot buffers reusable
                # right neighbour drained its recv slot two hops ago
                pltpu.semaphore_wait(cap_sem.at[slot], 1)
            hop_dmas = []
            if requant:
                # re-encode the partial onto the wire grid: int8 payload
                # + per-row scale side-channel (same slot, own DMA)
                amax = jnp.max(jnp.abs(acc[...]), axis=1, keepdims=True)
                delta = jnp.where(amax > 0, amax, 1.0) / float(levels)
                q = jnp.clip(jnp.round(acc[...] / delta),
                             -levels, levels)
                send_buf[slot] = q.astype(wire_dtype)
                scale_send[slot] = jnp.broadcast_to(
                    delta, scale_send.shape[1:])
                sdma = pltpu.make_async_remote_copy(
                    src_ref=scale_send.at[slot],
                    dst_ref=scale_recv.at[slot],
                    send_sem=ssend_sem.at[slot],
                    recv_sem=srecv_sem.at[slot],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                sdma.start()
                hop_dmas.append(sdma)
            else:
                send_buf[slot] = acc[...]
            dma = pltpu.make_async_remote_copy(
                src_ref=send_buf.at[slot], dst_ref=recv_buf.at[slot],
                send_sem=send_sem.at[slot], recv_sem=recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            dma.start()
            hop_dmas.append(dma)
            rs_dmas.append(hop_dmas)
            # overlap: while the partial flies, build our own gated
            # contribution for the chunk about to land
            ctr = contrib(lax.rem(i + 2 * n - 2 - t, n))
            for d in hop_dmas:
                d.wait_recv()
            if requant:     # decode the landed partial before adding
                landed = recv_buf[slot].astype(rs_dtype) \
                    * scale_recv[slot][:, :1]
            else:
                landed = recv_buf[slot]
            acc[...] = landed + ctr
            pltpu.semaphore_signal(
                cap_sem.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        for t in _drain_steps(n):
            for d in rs_dmas[t]:
                d.wait_send()
            pltpu.semaphore_wait(cap_sem.at[t % 2], 1)

        # ---- turnaround: in-kernel recovery divisor --------------------
        my_div = cnt_ref[chunk_rows(i), :]                    # (k, 1)
        tilde = acc[...] / my_div
        mine = tilde.astype(payload_dtype)                    # (k, W)

        # ---- AG phase: select-as-it-lands ------------------------------
        def place(c, val):
            rows = chunk_rows(c)
            keep = ag_ref[rows, :] != 0                       # (k, 1)
            if renorm:
                fb = table_ref[rows, :]                       # local block
            else:
                fb = jnp.zeros_like(val)
            out_ref[rows, :] = jnp.where(keep, val, fb)

        place(i, mine)
        cur = mine
        ag_dmas = []
        for t in range(n - 1):
            slot = t % 2
            if t >= 2:
                ag_dmas[t - 2].wait_send()
                pltpu.semaphore_wait(ag_cap_sem.at[slot], 1)
            ag_send[slot] = cur
            dma = pltpu.make_async_remote_copy(
                src_ref=ag_send.at[slot], dst_ref=ag_recv.at[slot],
                send_sem=ag_send_sem.at[slot], recv_sem=ag_recv_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            dma.start()
            ag_dmas.append(dma)
            dma.wait_recv()
            cur = ag_recv[slot]
            place(lax.rem(i + 2 * n - 1 - t, n), cur)
            pltpu.semaphore_signal(
                ag_cap_sem.at[slot], inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        for t in _drain_steps(n):
            ag_dmas[t].wait_send()
            pltpu.semaphore_wait(ag_cap_sem.at[t % 2], 1)

    return kernel


def _aligned_rows(k: int, *dtypes) -> int:
    """Rows per ring chunk: k rounded up to the packed sublane tile of
    the narrowest operand (16 rows for bf16, 32 for int8). Mosaic refuses
    a dynamic row slice of a packed dtype that it cannot prove tile-
    aligned; 32-bit operands slice at any row."""
    tile = max(1 if jnp.dtype(d).itemsize >= 4
               else 32 // jnp.dtype(d).itemsize for d in dtypes)
    return -(-k // tile) * tile


@functools.partial(jax.jit, static_argnames=("n", "k", "mode", "rs_dtype",
                                             "collective_id", "interpret",
                                             "levels"))
def ring_bucket_fused(table: jax.Array, rs_row: jax.Array, ag_row: jax.Array,
                      counts: jax.Array, pos: jax.Array, left: jax.Array,
                      right: jax.Array, *, n: int, k: int, mode: str,
                      rs_dtype=jnp.float32, collective_id: int = 7,
                      interpret: bool = False,
                      qtable: Optional[jax.Array] = None,
                      qscale: Optional[jax.Array] = None,
                      levels: int = 0) -> jax.Array:
    """One bucket's full drop-masked RS+AG round as a single Pallas
    dispatch (TPU only; the lowering is export-checked on any host).

    table:  (S, W) local payload, scatter-ordered, W a multiple of 128;
    rs_row: (S, 1) this device's RS-mask row in the accumulation dtype;
    ag_row: (S, 1) this device's AG-mask row (nonzero = delivered);
    counts: (S, 1) per-block recovery divisor, accumulation dtype (the
            received count pre-clamped to ≥ 1 for renorm/ef, n for the
            naive grad mode, n(1−p) for the scale recovery — the kernel
            divides by it verbatim);
    pos/left/right: (1,) int32 — ring position and the *logical* device
    ids of the ring neighbours (see :func:`logical_ring_ids`).

    Wire pipeline (DESIGN.md §13): ``qtable``/``qscale`` supply an
    encoded contribution table — (S, W) wire-dtype payload with (S, 1)
    f32 per-row scales, decode fused into the in-kernel accumulate (the
    int8 codec, or an EF-compensated send with unit scales). ``levels``
    > 0 additionally re-encodes every RS hop onto the int8 grid (the
    RDMA payload is int8 plus a scale side-channel). Still exactly one
    dispatch in every variant.

    The table is donated into the output (``input_output_aliases``): the
    dispatch runs in place, no second (S, W) buffer. Where a sub-32-bit
    operand needs it, each owner's k rows are zero-padded to the packed
    sublane tile around the dispatch (:func:`_aligned_rows`).
    """
    import jax.experimental.pallas.tpu as pltpu
    from jax.experimental import pallas as pl

    S, W = table.shape
    if S != k * n:
        raise ValueError(f"table rows {S} != k*n = {k * n}")
    if W % LANE:
        raise ValueError(f"W={W} must be a multiple of {LANE}")
    has_enc = qtable is not None
    if has_enc and qscale is None:
        raise ValueError("qtable needs qscale")
    if levels > 0 and not has_enc:
        raise ValueError("levels > 0 needs qtable/qscale")
    rs_dtype = jnp.dtype(rs_dtype)
    kp = _aligned_rows(k, table.dtype, rs_dtype,
                       *((qtable.dtype,) if has_enc else ()))
    if kp != k:
        def pad_rows(x, fill=0.0):
            x = x.reshape(n, k, x.shape[-1])
            x = jnp.pad(x, ((0, 0), (0, kp - k), (0, 0)),
                        constant_values=fill)
            return x.reshape(n * kp, x.shape[-1])

        # padding rows: RS mask 0 (they add nothing), AG mask 0 (they
        # keep their zero local copy), divisor 1 (no 0/0)
        table, rs_row, ag_row = (pad_rows(table), pad_rows(rs_row),
                                 pad_rows(ag_row))
        counts = pad_rows(counts, 1.0)
        if has_enc:
            qtable, qscale = pad_rows(qtable), pad_rows(qscale, 1.0)
        out = ring_bucket_fused(table, rs_row, ag_row, counts, pos, left,
                                right, n=n, k=kp, mode=mode,
                                rs_dtype=rs_dtype,
                                collective_id=collective_id,
                                interpret=interpret, qtable=qtable,
                                qscale=qscale, levels=levels)
        return out.reshape(n, kp, W)[:, :k].reshape(S, W)
    kernel = _make_ring_kernel(
        n=n, k=k, W=W, mode=mode, rs_dtype=rs_dtype,
        payload_dtype=table.dtype,
        wire_dtype=None if not has_enc else jnp.dtype(qtable.dtype),
        levels=levels, has_enc=has_enc)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)
    in_specs = [smem, smem, smem, vmem, vmem, vmem, vmem]
    args = [pos, left, right, table, rs_row, ag_row, counts]
    if has_enc:
        in_specs += [vmem, vmem]
        args += [qtable, qscale]
    wire_slot_dtype = qtable.dtype if levels > 0 else rs_dtype
    comm = [
        pltpu.VMEM((k, W), rs_dtype),               # acc
        pltpu.VMEM((2, k, W), wire_slot_dtype),     # RS send slots
        pltpu.VMEM((2, k, W), wire_slot_dtype),     # RS recv slots
    ]
    if levels > 0:
        comm += [
            pltpu.VMEM((2, k, LANE), jnp.float32),  # scale send slots
            pltpu.VMEM((2, k, LANE), jnp.float32),  # scale recv slots
        ]
    comm += [
        pltpu.VMEM((2, k, W), table.dtype),         # AG send slots
        pltpu.VMEM((2, k, W), table.dtype),         # AG recv slots
        pltpu.SemaphoreType.DMA((2,)),              # RS send sems
        pltpu.SemaphoreType.DMA((2,)),              # RS recv sems
    ]
    if levels > 0:
        comm += [
            pltpu.SemaphoreType.DMA((2,)),          # scale send sems
            pltpu.SemaphoreType.DMA((2,)),          # scale recv sems
        ]
    comm += [
        pltpu.SemaphoreType.DMA((2,)),              # AG send sems
        pltpu.SemaphoreType.DMA((2,)),              # AG recv sems
        pltpu.SemaphoreType.REGULAR((2,)),          # RS capacity handshake
        pltpu.SemaphoreType.REGULAR((2,)),          # AG capacity handshake
    ]
    return pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((S, W), table.dtype),
        scratch_shapes=comm,
        input_output_aliases={3: 0},                # donate the table
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
        interpret=interpret,
    )(*args)


def logical_ring_ids(names: Tuple[str, ...],
                     mesh_axis_names: Optional[Sequence[str]] = None,
                     mesh_shape: Optional[dict] = None):
    """(pos, left, right) int32 scalars for the ring over ``names`` inside
    a manual region: ``pos`` is the flattened ring index, left/right the
    *logical* device ids of the ring neighbours.

    With only the ring axes given, the ring axes are assumed to be the
    whole mesh (logical id = ring index). Passing the full mesh axis
    order/shape (the trainer's mesh) places the neighbours correctly when
    non-ring axes (e.g. "model") trail or interleave.
    """
    from repro.core.rps import _my_index, axis_size
    pos = _my_index(names).astype(jnp.int32)
    n = axis_size(names)
    if mesh_axis_names is None:
        left = jnp.mod(pos - 1, n).astype(jnp.int32)
        right = jnp.mod(pos + 1, n).astype(jnp.int32)
        return pos, left, right
    # general mesh: logical id = sum(coord[a] * stride[a]); the ring
    # varies the ``names`` coords jointly (major-to-minor), all other
    # axes keep this device's coordinate.
    sizes = [int(mesh_shape[a]) for a in mesh_axis_names]
    strides = {}
    acc = 1
    for a, sz in zip(reversed(list(mesh_axis_names)), reversed(sizes)):
        strides[a] = acc
        acc *= sz
    coords = {a: lax.axis_index(a) for a in mesh_axis_names}
    base = sum((coords[a] * strides[a] for a in mesh_axis_names
                if a not in names), jnp.int32(0))

    def ring_logical(ring_pos):
        out = base
        rem = ring_pos
        for a in names:                       # major-to-minor, like _my_index
            extent = 1
            seen = False
            for b in names:
                if b == a:
                    seen = True
                    continue
                if seen:
                    extent *= int(mesh_shape[b])
            out = out + (rem // extent) * strides[a]
            rem = jnp.mod(rem, extent)
        return out.astype(jnp.int32)

    return (pos, ring_logical(jnp.mod(pos - 1, n)),
            ring_logical(jnp.mod(pos + 1, n)))


# ---------------------------------------------------------------------------
# The engine entry point _exchange_table dispatches to
# ---------------------------------------------------------------------------

def ring_exchange_scatter_table(blocks: jax.Array, rs_sc: jax.Array,
                                ag_sc: jax.Array, *,
                                names: Tuple[str, ...], n: int,
                                i: jax.Array, k: int, mode: str,
                                rs_dtype=jnp.float32,
                                pin: Optional[Callable] = None,
                                ring_ids=None,
                                use_kernel: Optional[bool] = None,
                                codec=None,
                                enc=None,
                                send: Optional[jax.Array] = None,
                                div: Optional[jax.Array] = None,
                                comm_slot: int = 0) -> jax.Array:
    """Ring-engine exchange of one scatter-ordered (S, blk[, m]) table.

    ``use_kernel=None`` picks the fused Pallas dispatch on TPU and the
    interpret-mode ppermute ring everywhere else. The kernel serves
    fully-manual regions only: a ``pin`` hook marks a partial-manual
    region whose auto-sharded dim Pallas cannot see, and on TPU that
    raises rather than quietly replaying the ppermute schedule.
    ``ring_ids`` supplies precomputed (pos, left, right) logical ids for
    multi-axis meshes (:func:`logical_ring_ids`); defaults to a ring over
    the whole mesh.

    Wire pipeline (DESIGN.md §13): a quantised ``codec`` routes through
    the int8-wire kernel variant — ``enc`` is the precomputed
    ``codec.encode`` pair of this device's (scatter-ordered) send table,
    decode fused into the in-kernel accumulate, every RS hop re-encoded.
    ``send`` overrides the contribution source for *linear* codecs (the
    EF-compensated intent); ``div`` is the (S,) f32 recovery divisor
    (None = legacy renorm/grad computation).

    Async double-buffering (DESIGN.md §15): ``comm_slot`` (0 or 1)
    selects which barrier/DMA semaphore family this dispatch uses —
    ``collective_id = 7 + slot``. A sync plan keeps every bucket on
    slot 0 (today's id, bit-identical schedule); an async plan
    alternates slots across its reverse-order bucket dispatches, so two
    consecutive ring rounds own disjoint semaphores and the scheduler
    is free to keep one in flight while the next bucket's backward
    dot-generals (and its own dispatch) are issued — the RDMA hops of
    round ``b`` overlap the compute that makes bucket ``b+1`` ready.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
        if use_kernel and pin is not None:
            raise ValueError(
                "engine='ring' on TPU needs a fully-manual region: the "
                "fused kernel cannot serve a partial-manual (pinned) "
                "exchange — use engine='xla'")
    quantized = codec is not None and codec.quantized
    if not use_kernel:
        dec = codec.decode(*enc) if quantized and send is None else send
        return _ring_schedule_jax(blocks, rs_sc, ag_sc, names=names, n=n,
                                  i=i, k=k, mode=mode, rs_dtype=rs_dtype,
                                  pin=pin, codec=codec, send=dec, div=div)
    shape = blocks.shape
    S = shape[0]
    W = 1
    for d in shape[1:]:
        W *= d
    pad = (-W) % LANE

    def widen(x, fill=0.0):
        x = x.reshape(S, -1)
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill) \
            if pad else x

    tbl = widen(blocks)
    rs_f = rs_sc.astype(rs_dtype)
    rs_row = rs_f[i][:, None]
    ag_row = (ag_sc[i][:, None] != 0).astype(jnp.float32)
    if div is None:
        from repro.core.rps import _divisor
        from repro.core.wire import make_recovery
        div = _divisor(make_recovery(None), mode, rs_sc, n)
    cnt = div[:, None].astype(rs_dtype)
    if ring_ids is None:
        ring_ids = logical_ring_ids(names)
    pos, left, right = (r.reshape(1).astype(jnp.int32) for r in ring_ids)
    qt = qs = None
    levels = 0
    if quantized:
        q, sc = enc if enc is not None else codec.encode(blocks, None)
        qt = widen(q)                      # wire-dtype table, decode fused
        qs = sc.reshape(S, -1)[:, :1].astype(jnp.float32)
        levels = codec.levels
    elif send is not None:
        # EF-compensated intent on a linear wire: the send table replaces
        # the raw payload as the contribution source (unit scales, no hop
        # requant); the AG fallback stays the raw donated ``table``
        qt = widen(send).astype(rs_dtype)
        qs = jnp.ones((S, 1), jnp.float32)
    if comm_slot not in (0, 1):
        raise ValueError(f"comm_slot={comm_slot}, want 0 or 1")
    out = ring_bucket_fused(tbl, rs_row, ag_row, cnt, pos, left, right,
                            n=n, k=k, mode=mode, rs_dtype=rs_dtype,
                            qtable=qt, qscale=qs, levels=levels,
                            collective_id=7 + comm_slot)
    if pad:
        out = out[:, :W]
    return out.reshape(shape)


def ring_global_sums(stack: jax.Array, rs_g: jax.Array, own: jax.Array, *,
                     rs_dtype=jnp.float32, codec=None) -> jax.Array:
    """Single-device (global-view) replay of the ring RS arithmetic:
    ``stack`` (G, n, s, d) worker contributions, ``rs_g`` (G, n, s) f32
    masks, ``own`` (s,) block owners. Returns (G, s, d) masked sums
    accumulated **in ring order in the wire dtype** — contributions for
    block j added in order owner+1, …, owner+n−1, owner, each gated and
    cast to ``rs_dtype`` first, exactly like the collective ring engine.
    Lets the simulator study bf16-wire convergence without a TPU.

    A quantised ``codec`` re-encodes the running partial between hops
    (per-(g, block) scales over d), replaying the int8-wire transport;
    ``stack`` should then hold the already-decoded (fake-quant) send
    values, exactly like the collective path's contribution source."""
    G, n, s, d = stack.shape
    rs_w = rs_g.astype(rs_dtype)
    quantized = codec is not None and codec.quantized

    def hop(acc, t):
        if quantized:
            # requant(0) = 0, so the t=1 pass-through is exact and every
            # later hop decodes what the wire carried (scales per row)
            acc = codec.decode(*codec.encode(acc, None, lead=1))
        idx = jnp.mod(own + t, n)                          # (s,)
        cols = jnp.arange(s)
        contrib = stack[:, idx, cols, :].astype(rs_dtype) \
            * rs_w[:, idx, cols][..., None]
        return acc + contrib, None

    acc = jnp.zeros((G, s, d), rs_dtype)
    acc, _ = lax.scan(hop, acc, jnp.arange(1, n + 1))
    return acc
