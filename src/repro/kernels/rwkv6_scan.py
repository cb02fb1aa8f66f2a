"""Pallas TPU kernel: chunked RWKV6 (Finch) linear-attention scan.

TPU adaptation (DESIGN.md §3): the published CUDA kernels stage the
recurrence through shared memory one token at a time; on TPU we rephrase the
data-dependent-decay recurrence as a *chunked* scan so the MXU sees
(C×dk)·(dk×C) and (C×C)·(C×dv) matmuls instead of length-1 outer products:

  within a chunk (all in VMEM, f32):
    la_t   = cumsum(log w)                       (C, dk)
    scores[t,s] = Σ_k r[t,k]·k[s,k]·exp(la_{t-1}[t,k] − la[s,k])   (s < t)
    o_t    = scores @ v + (Σ_k r·u·k)_t · v_t + (r_t·exp(la_{t-1})) @ S
    S'     = S ⊙ exp(la_C) + Σ_s (k_s ⊙ exp(la_C − la_s)) ⊗ v_s

  All exponents are differences with s ≤ t, hence ≤ 0 — no overflow; this is
  why the (C, C, dk) decay tensor is formed *inside* the kernel (VMEM tile,
  C=dk=64 → 1 MiB f32) where fusion is guaranteed, instead of in XLA HLO.

Grid: (B·h, nc) with the chunk axis sequential ("arbitrary"); the running
state S (dk, dv) lives in a VMEM scratch buffer that persists across chunk
steps and is reset at chunk 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    w = w_ref[0].astype(jnp.float32)          # (C, dk)
    u = u_ref[0].astype(jnp.float32)          # (1, dk)
    S = state_ref[...]                        # (dk, dv)

    logw = jnp.log(jnp.clip(w, 1e-30, 1.0))
    la = jnp.cumsum(logw, axis=0)             # inclusive (C, dk)
    la_prev = la - logw                       # exclusive
    C = r.shape[0]

    # pairwise decay tensor, exponent ≤ 0 for s < t
    D = jnp.exp(la_prev[:, None, :] - la[None, :, :])        # (C, C, dk)
    scores = jnp.einsum("tk,sk,tsk->ts", r, k, D)
    tri = jnp.tril(jnp.ones((C, C), jnp.float32), k=-1)
    scores = scores * tri
    o = scores @ v                                            # intra-chunk
    o = o + (jnp.sum(r * u * k, axis=-1, keepdims=True)) * v  # bonus diag
    o = o + (r * jnp.exp(la_prev)) @ S                        # carry-in state

    decay_out = jnp.exp(la[-1][None, :] - la)                 # (C, dk), ≤ 1
    state_ref[...] = S * jnp.exp(la[-1])[:, None] + (k * decay_out).T @ v
    o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def rwkv6_pallas(r, k, v, w, u, *, chunk: int = 64,
                 interpret: bool = False):
    """r,k,w: (B,S,h,dk); v: (B,S,h,dv); u: (h,dk) -> o: (B,S,h,dv)."""
    B, S, h, dk = r.shape
    dv = v.shape[-1]
    pad = (-S) % chunk
    if pad:
        padfn = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = padfn(r), padfn(k), padfn(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    Sp = S + pad
    nc = Sp // chunk
    # (B,S,h,d) -> (B*h, S, d)
    reorder = lambda x: jnp.moveaxis(x, 2, 1).reshape(B * h, Sp, x.shape[-1])
    rr, kk, vv, ww = reorder(r), reorder(k), reorder(v), reorder(w)
    uu = jnp.broadcast_to(u[None], (B, h, dk)).reshape(B * h, 1, dk)

    out = pl.pallas_call(
        _rwkv6_kernel,
        grid=(B * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * h, Sp, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(rr, kk, vv, ww, uu)
    out = out.reshape(B, h, Sp, dv)[:, :, :S]
    return jnp.moveaxis(out, 1, 2)            # (B,S,h,dv)
