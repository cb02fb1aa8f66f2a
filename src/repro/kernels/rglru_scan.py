"""Pallas TPU kernel: RG-LRU gated diagonal linear recurrence (Griffin).

  h_t = a_t ⊙ h_{t-1} + sqrt(1 − a_t²) ⊙ x_t

The recurrence is diagonal (pure VPU, no MXU), so the TPU adaptation is
about memory staging: grid (B, d/TILE_D, nc) streams (C, TILE_D) chunks of
`x`/`a` through VMEM; the running hidden state (1, TILE_D) persists in a
VMEM scratch across the sequential chunk axis. The time loop inside the
kernel is a `fori_loop` over C elementwise steps on VMEM-resident tiles —
no HBM round-trips between steps, which is what the naive XLA scan pays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_ref, o_ref, h_ref):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # (C, TILE_D)
    a = a_ref[0].astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * x
    C = x.shape[0]

    def step(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, axis=0)
        return h, out

    h0 = h_ref[0]
    h_last, out = jax.lax.fori_loop(0, C, step,
                                    (h0, jnp.zeros_like(x)))
    h_ref[0] = h_last
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "tile_d", "interpret"))
def rglru_pallas(x, a, *, chunk: int = 128, tile_d: int = 256,
                 interpret: bool = False):
    """x, a: (B, S, d) -> h: (B, S, d)."""
    B, S, d = x.shape
    pad_s = (-S) % chunk
    pad_d = (-d) % tile_d
    if pad_s or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_d)))
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)),
                    constant_values=1.0)
    Sp, dp = S + pad_s, d + pad_d
    nc = Sp // chunk
    out = pl.pallas_call(
        _rglru_kernel,
        grid=(B, dp // tile_d, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, tile_d), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, chunk, tile_d), lambda b, j, c: (b, c, j)),
        ],
        out_specs=pl.BlockSpec((1, chunk, tile_d), lambda b, j, c: (b, c, j)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, tile_d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(x, a)
    return out[:, :S, :d]
