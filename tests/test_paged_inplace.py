"""The paged decode updates each kind's stacked KV pool in place
(DESIGN.md §18).

Pins: the layer-indexed ``paged_write`` / ``paged_gather`` on the stacked
pool equal a write / gather on that layer's slice and leave every other
layer untouched; the compiled decode round (grouped scan and faithful
interleaved unroll) makes no pool-sized buffer of its own — no slice of a
layer's pool out of the stack, no stacking back, no copy of the pool after
the layer loop.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import layers as L
from repro.serve import ContinuousEngine

N_LAYERS, N_BLOCKS, PAGE, KVH, HD, B, P = 3, 13, 4, 2, 8, 4, 3
N_SLOT_BLOCKS = 513         # the compiled round's pool: 16.8 MB in float32


def _block_table(rng, layout):
    """(B, P) block table over blocks 1..N_BLOCKS-1 (0 = the null block).

    ``shuffled``: every lane owns a random, non-contiguous set of blocks;
    ``interleaved``: lane b owns blocks b+1, b+1+B, ... (round-robin
    allocation); the last lane is inactive and points at the null block.
    """
    if layout == "shuffled":
        ids = rng.permutation(np.arange(1, N_BLOCKS))[:B * P]
        bt = ids.reshape(B, P)
    else:
        bt = (np.arange(P)[None, :] * B + np.arange(B)[:, None] + 1)
        bt[-1] = 0
    return bt.astype(np.int32)


@pytest.mark.parametrize("layout", ["shuffled", "interleaved"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_layer_indexed_write_gather_match_layer_slice(seed, layout):
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((N_LAYERS, N_BLOCKS * PAGE, KVH, HD),
                               dtype=np.float32)
    new = rng.standard_normal((B, 1, KVH, HD), dtype=np.float32)
    bt = _block_table(rng, layout)
    pos = rng.integers(0, P * PAGE, size=B).astype(np.int32)
    write = jax.jit(L.paged_write, static_argnums=(5,))
    gather = jax.jit(L.paged_gather, static_argnums=(3,))
    for layer in range(N_LAYERS):
        # the former contract: slice the layer out, write, gather from it
        want_l = pool[layer].copy()
        flat = bt[np.arange(B), pos // PAGE] * PAGE + pos % PAGE
        want_l[flat] = new[:, 0]
        slots = (bt[:, :, None] * PAGE + np.arange(PAGE)).reshape(B, -1)
        want_view = want_l[slots]
        for li in (layer, jnp.int32(layer)):      # static and traced index
            got = np.asarray(write(jnp.asarray(pool), li, jnp.asarray(new),
                                   jnp.asarray(bt), jnp.asarray(pos), PAGE))
            np.testing.assert_array_equal(got[layer], want_l)
            others = [i for i in range(N_LAYERS) if i != layer]
            np.testing.assert_array_equal(got[others], pool[others])
            view = np.asarray(gather(jnp.asarray(got), li, jnp.asarray(bt),
                                     PAGE))
            np.testing.assert_array_equal(view, want_view)


def _pool_shapes(pool):
    """HLO shape strings of every kind's stacked K/V pool and one layer's."""
    out = set()
    for kp in pool.values():
        for leaf in ("k", "v"):
            s = kp[leaf].shape
            for dims in (s, s[1:]):
                out.add("f32[%s]" % ",".join(map(str, dims)))
    return out


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "interleaved"])
def test_round_updates_pool_in_place(grouped):
    """Compile the decode round at tiny float32 widths on two interleaved
    kinds (local attn@64 and global attn, alternating). The only ops that
    yield a pool-shaped buffer are parameters, tuple reads of the loop
    state and the scatters of the new token; the round's temporaries stay
    under one pool. The pool is sized above the layer weights, since the
    unrolled path's per-layer weight slices are hoisted out of the round's
    loop as temporaries of their own (about 9 MB here), which this test
    does not pin."""
    cfg = dataclasses.replace(get_config("gemma3-1b").reduced(),
                              n_layers=4, global_every=2)
    model = build_model(cfg, grouped=grouped)
    assert model.kinds == ["attn@64", "attn", "attn@64", "attn"]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    eng = ContinuousEngine(model, params, page=16, n_blocks=N_SLOT_BLOCKS,
                           max_batch=4, chunk=4, max_len=256)
    pool = jax.eval_shape(lambda: model.init_paged(N_SLOT_BLOCKS * 16))
    lanes = jax.ShapeDtypeStruct((4,), jnp.int32)
    compiled = eng._round.lower(
        params, pool, jax.ShapeDtypeStruct((4, eng.max_pages), jnp.int32),
        jax.ShapeDtypeStruct((4, 1), jnp.int32), lanes, lanes,
        jax.random.PRNGKey(0), None).compile()

    shapes = _pool_shapes(pool)
    made = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* (\S+?)\(",
                     line)
        if m is None or m.group(2) not in shapes:
            continue
        name, op = m.group(1), m.group(3)
        in_place = op == "scatter" or (op == "fusion"
                                       and "scatter" in name)
        if op not in ("parameter", "get-tuple-element") and not in_place:
            made.append(f"{name} = {m.group(2)} {op}")
    assert not made, made

    pool_bytes = sum(kp[leaf].size * kp[leaf].dtype.itemsize
                     for kp in pool.values() for leaf in ("k", "v"))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, (temp, pool_bytes)
