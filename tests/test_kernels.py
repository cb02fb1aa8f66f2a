"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.masked_avg import masked_avg_grid_pallas, masked_avg_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.rwkv6_scan import rwkv6_pallas

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [2, 8, 16, 32])
@pytest.mark.parametrize("d", [7, 512, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_avg_sweep(n, d, dtype):
    blocks = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    mask = jnp.asarray(RNG.integers(0, 2, size=n), jnp.float32).at[0].set(1)
    got = masked_avg_pallas(blocks, mask, interpret=True)
    want = ref.masked_avg_ref(blocks, mask)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_masked_avg_all_dropped_but_owner():
    blocks = jnp.asarray(RNG.normal(size=(4, 64)), jnp.float32)
    mask = jnp.zeros((4,)).at[2].set(1.0)
    got = masked_avg_pallas(blocks, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(blocks[2]),
                               rtol=1e-6)


@pytest.mark.parametrize("B", [1, 3, 16, 13])
@pytest.mark.parametrize("n,d", [(2, 7), (8, 512), (16, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_avg_grid_sweep(B, n, d, dtype):
    """The grid-over-blocks dispatch (one pallas_call for B blocks —
    DESIGN.md §11) against the einsum oracle, per block."""
    blocks = jnp.asarray(RNG.normal(size=(B, n, d)), dtype)
    mask = jnp.asarray(RNG.integers(0, 2, size=(B, n)),
                       jnp.float32).at[:, 0].set(1)
    got = masked_avg_grid_pallas(blocks, mask, tile_rows=2, interpret=True)
    f32 = blocks.astype(jnp.float32)
    want = jnp.einsum("bn,bnd->bd", mask, f32) \
        / jnp.maximum(mask.sum(-1), 1.0)[:, None]
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_masked_avg_grid_matches_per_block_vmap():
    """The fused grid call must equal the per-block vmap it replaced."""
    B, n, d = 6, 8, 300
    blocks = jnp.asarray(RNG.normal(size=(B, n, d)), jnp.float32)
    mask = jnp.asarray(RNG.integers(0, 2, size=(B, n)),
                       jnp.float32).at[:, 0].set(1)
    got = masked_avg_grid_pallas(blocks, mask, tile_rows=1, interpret=True)
    want = jax.vmap(lambda b, m: masked_avg_pallas(
        b, m, tile_rows=1, interpret=True))(blocks, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_masked_avg_grid_rejects_bad_mask_shape():
    blocks = jnp.zeros((2, 4, 8))
    with pytest.raises(ValueError):
        masked_avg_grid_pallas(blocks, jnp.zeros((4,)), interpret=True)


def test_masked_avg_tile_d_auto_divisor():
    """tile_rows=None takes every row of a block in one step while the
    (n, rows, 128) stack fits the 2 MiB budget, above it the largest
    multiple of 32 rows under the budget that divides the row count (no
    ragged last tile), falling back to the budget tile with padding:
    Mosaic refuses a tile that is neither the whole dim nor a multiple of
    the sublane tile."""
    from repro.kernels.masked_avg import pick_tile_rows
    assert pick_tile_rows(1, 8, 4) == 1        # tiny model: one exact tile
    assert pick_tile_rows(100, 2, 2) == 100
    assert pick_tile_rows(4096, 2, 2) == 4096  # exactly the budget
    # deepseek-7b embedding, 2 server blocks: 102400·4096/2/128 rows
    assert pick_tile_rows(1638400, 2, 2) == 4096
    assert pick_tile_rows(12320, 2, 2) == 2464  # 12320 = 32·5·7·11
    assert pick_tile_rows(8193, 2, 2) == 4096   # odd: budget + padding
    assert pick_tile_rows(1000, 16, 4) == 256   # f32 n=16 budget; pad
    for rows, n, size in ((1, 2, 4), (12320, 2, 2), (8193, 2, 2),
                          (1638400, 2, 2), (5000, 64, 4)):
        t = pick_tile_rows(rows, n, size)
        assert t == rows or (t % 32 == 0 and n * t * 128 * size <= 2 << 20)


@pytest.mark.parametrize("d", [40, 513, 1000])
def test_masked_avg_auto_tile_matches_explicit(d):
    """The auto tile must be numerically identical to any explicit tiling
    (pure data-layout choice), including raw bool masks (the hoisted
    cast-in-kernel path — no (B, n, 1) f32 mask copy at the caller)."""
    B, n = 3, 8
    blocks = jnp.asarray(RNG.normal(size=(B, n, d)), jnp.float32)
    mask_b = jnp.asarray(RNG.integers(0, 2, size=(B, n)),
                         bool).at[:, 0].set(True)
    got = masked_avg_grid_pallas(blocks, mask_b, interpret=True)
    want = masked_avg_grid_pallas(blocks, mask_b.astype(jnp.float32),
                                  tile_rows=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _rwkv_inputs(B, S, h, dk, dv, dtype=jnp.float32):
    r = jnp.asarray(RNG.normal(size=(B, S, h, dk)) * 0.5, dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, h, dk)) * 0.5, dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, h, dv)) * 0.5, dtype)
    w = jnp.asarray(RNG.uniform(0.05, 0.995, size=(B, S, h, dk)), dtype)
    u = jnp.asarray(RNG.normal(size=(h, dk)) * 0.1, jnp.float32)
    return r, k, v, w, u


@pytest.mark.parametrize("S,chunk", [(1, 16), (16, 16), (33, 16), (130, 32)])
@pytest.mark.parametrize("dk,dv", [(8, 8), (16, 32)])
def test_rwkv6_pallas_sweep(S, chunk, dk, dv):
    r, k, v, w, u = _rwkv_inputs(2, S, 2, dk, dv)
    got = rwkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    want = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("S", [5, 64, 100])
def test_rwkv6_xla_chunked_matches_ref(S):
    r, k, v, w, u = _rwkv_inputs(2, S, 3, 16, 16)
    got = ops.rwkv6(r, k, v, w, u, backend="xla", chunk=16)
    want = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               atol=1e-4, rtol=1e-4)


def test_rwkv6_bf16():
    r, k, v, w, u = _rwkv_inputs(1, 32, 2, 16, 16, jnp.bfloat16)
    got = rwkv6_pallas(r, k, v, w, u, chunk=16, interpret=True)
    want = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.1,
                               rtol=0.1)


def test_rwkv6_step_consistency():
    """Decode one-step recurrence folds to the same as the full scan."""
    B, S, h, dk, dv = 1, 7, 2, 8, 8
    r, k, v, w, u = _rwkv_inputs(B, S, h, dk, dv)
    full = np.asarray(ref.rwkv6_ref(r, k, v, w, u))
    state = jnp.zeros((B, h, dk, dv), jnp.float32)
    outs = []
    for t in range(S):
        o, state = ops.rwkv6_step(r[:, t], k[:, t], v[:, t], w[:, t], u,
                                  state)
        outs.append(np.asarray(o))
    step = np.stack(outs, axis=1).reshape(full.shape)
    np.testing.assert_allclose(step, full, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("S,d,chunk,tile", [(1, 8, 16, 64), (64, 64, 16, 32),
                                            (130, 70, 32, 64)])
def test_rglru_pallas_sweep(S, d, chunk, tile):
    x = jnp.asarray(RNG.normal(size=(2, S, d)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.1, 0.999, size=(2, S, d)), jnp.float32)
    got = rglru_pallas(x, a, chunk=chunk, tile_d=tile, interpret=True)
    want, _ = ref.rglru_ref(x, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


def test_rglru_assoc_matches_ref():
    x = jnp.asarray(RNG.normal(size=(2, 57, 33)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.1, 0.999, size=(2, 57, 33)), jnp.float32)
    got, last = ops.rglru(x, a, backend="xla")
    want, want_last = ref.rglru_ref(x, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(want_last),
                               atol=1e-5, rtol=1e-5)


def test_rglru_step_matches_scan():
    x = jnp.asarray(RNG.normal(size=(2, 9, 16)), jnp.float32)
    a = jnp.asarray(RNG.uniform(0.1, 0.99, size=(2, 9, 16)), jnp.float32)
    want, _ = ref.rglru_ref(x, a)
    h = jnp.zeros((2, 16), jnp.float32)
    for t in range(9):
        h = ops.rglru_step(x[:, t], a[:, t], h)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want[:, t]),
                                   atol=1e-5, rtol=1e-5)
