"""Pallas TPU kernel: fused drop-masked renormalised block average.

This is the RPS Reduce-Scatter hot loop (Algorithm 1 line 6): after the
masked contributions for one model block land on the owner, the owner
computes ``sum_i m_i · v_i / sum_i m_i``. Fusing mask-multiply, reduce and
renormalise keeps the traffic at one read of the (n, d) stack + one write of
(d,) — the op is memory-bound, so the fusion is the whole win.

Tiling: one 2-D grid over (block, row tile) — **all** B blocks of an
exchange round (every server block of every plan bucket, DESIGN.md §11) run
as a single ``pallas_call`` dispatch instead of a per-block ``jax.vmap``.

Layout: a block's d payload elements are viewed as (R, 128) rows of the
TPU's 128-lane width (zero-padded to a whole row when d is not a multiple
of 128). Each grid step loads an (n, TILE_ROWS, 128) slab of the n worker
contributions — the worker dim leading, so the reduction over n is plain
vreg adds and every vreg is full whatever n is — and writes a
(TILE_ROWS, 128) slab. A block's last two dims are (TILE_ROWS, 128) with
TILE_ROWS either all R rows or a multiple of 32 (the int8 sublane tile,
so the same rule holds for every payload dtype): Mosaic takes a block
whose last two dims are divisible by its tile or equal the array's own.
The mask rides along as (B, n, 1, 128) f32 rows, one lane-wide row per
worker: Mosaic broadcasts a row over sublanes, not a scalar over both.

Callers that already hold the payload as (…, R, 128) rows (the stacked
global exchange, ``core.rps.rps_exchange_global``) pass it as is: a flat
(…, d) view costs XLA's TPU compiler time and host memory in proportion
to d in the surrounding transposes, at real widths tens of GB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


LANE = 128              # TPU lane width: the row length of the payload view
ROW_TILE = 32           # sublane tile of the narrowest payload (int8)
TILE_BYTES = 2 << 20    # input bytes per grid step (double-buffered)


def pick_tile_rows(rows: int, n: int, itemsize: int) -> int:
    """Rows per grid step for an (n, rows, 128) block stack: all rows when
    the stack fits :data:`TILE_BYTES` (one step per block, the whole dim is
    always a legal block), else the largest multiple of 32 under that
    budget that divides ``rows`` (no ragged last tile), else the budget
    tile with the rows padded up to a multiple of it."""
    cap = max(TILE_BYTES // (n * LANE * itemsize), ROW_TILE)
    if rows <= cap:
        return rows
    cap -= cap % ROW_TILE
    for t in range(cap, ROW_TILE - 1, -ROW_TILE):
        if rows % t == 0:
            return t
    return cap


def _masked_avg_kernel(blocks_ref, mask_ref, out_ref):
    blocks = blocks_ref[0].astype(jnp.float32)           # (n, TR, 128)
    mask = mask_ref[0]                                   # (n, 1, 128) f32
    s = jnp.sum(blocks * mask, axis=0)                   # (TR, 128)
    c = jnp.maximum(jnp.sum(mask, axis=0), 1.0)          # (1, 128)
    out_ref[0] = (s / c).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def masked_avg_grid_pallas(blocks: jax.Array, mask: jax.Array, *,
                           tile_rows: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """Batched renormalised block average: one grid-over-blocks dispatch.

    blocks: (B, n, *tail) — B independent server blocks, n workers each,
            any payload shape (flat (d,) or rows (R, 128));
    mask:   (B, n)        — per-block delivery mask (any dtype).
    Returns (B, *tail) in ``blocks.dtype`` with
    ``out[b] = Σ_i mask[b,i]·blocks[b,i] / max(Σ_i mask[b,i], 1)``
    (accumulated in f32). ``tile_rows=None`` auto-picks the row tile
    (:func:`pick_tile_rows`).
    """
    B, n = blocks.shape[:2]
    tail = blocks.shape[2:]
    if mask.shape != (B, n):
        raise ValueError(f"mask shape {mask.shape} != ({B}, {n})")
    d = math.prod(tail)
    rows = -(-d // LANE)
    if tile_rows is None:
        tile_rows = pick_tile_rows(rows, n, blocks.dtype.itemsize)
    rows_p = -(-rows // tile_rows) * tile_rows
    x = blocks.reshape(B, n, d)
    if rows_p * LANE != d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, rows_p * LANE - d)))
    x = x.reshape(B, n, rows_p, LANE)
    out = pl.pallas_call(
        _masked_avg_kernel,
        grid=(B, rows_p // tile_rows),
        in_specs=[
            pl.BlockSpec((1, n, tile_rows, LANE), lambda b, r: (b, 0, r, 0)),
            pl.BlockSpec((1, n, 1, LANE), lambda b, r: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_rows, LANE), lambda b, r: (b, r, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows_p, LANE), blocks.dtype),
        interpret=interpret,
    )(x, jnp.broadcast_to(mask.astype(jnp.float32)[..., None, None],
                          (B, n, 1, LANE)))
    if rows_p * LANE != d:
        out = out.reshape(B, rows_p * LANE)[:, :d]
    return out.reshape((B,) + tail)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def masked_avg_pallas(blocks: jax.Array, mask: jax.Array, *,
                      tile_rows: int | None = None,
                      interpret: bool = False) -> jax.Array:
    """blocks: (n, d); mask: (n,) -> (d,). Single-block convenience wrapper
    over :func:`masked_avg_grid_pallas` (B = 1)."""
    return masked_avg_grid_pallas(blocks[None], mask.reshape(1, -1),
                                  tile_rows=tile_rows, interpret=interpret)[0]
