"""Multi-server RPS (DESIGN.md §10): property-based invariants of the
rectangular (n, s) partition, the s = n bit-identity guarantee, the
collective-vs-global parity matrix (modes × backends × channel families,
including s ≠ n), and the rs_dtype plumbing of the pytree wrapper."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                  # sealed envs: deterministic fallback
    from _hypothesis_compat import given, settings, st

from repro import channels as ch
from repro.core import rps, theory, wmatrix

KEY = jax.random.PRNGKey(7)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _shard_map(f, mesh, in_specs, out_specs, axis_names):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=axis_names,
                         check_vma=False)


def _oracle(V, rs, ag, mode):
    """Numpy reference for one rectangular RPS round on stacked (n, D)."""
    n, s = rs.shape
    D = V.shape[1]
    pad = (-D) % s
    Vp = np.pad(V.astype(np.float64), ((0, 0), (0, pad)))
    blk = (D + pad) // s
    out = np.empty_like(Vp)
    for j in range(s):
        seg = Vp[:, j * blk:(j + 1) * blk]
        summed = (rs[:, j, None] * seg).sum(0)
        tilde = summed / max(rs[:, j].sum(), 1) if mode != "grad" \
            else summed / n
        for i in range(n):
            if ag[i, j]:
                out[i, j * blk:(j + 1) * blk] = tilde
            elif mode == "grad":
                out[i, j * blk:(j + 1) * blk] = 0.0
            else:
                out[i, j * blk:(j + 1) * blk] = seg[i]
    return out[:, :D]


# ---- property: rectangular global exchange vs the numpy oracle -----------

@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(n=st.sampled_from([2, 4, 8]), s=st.sampled_from([1, 3, 4, 8, 13]),
       mode=st.sampled_from(["model", "grad", "grad_renorm"]),
       p=st.floats(0.0, 0.8), seed=st.integers(0, 1000))
def test_global_exchange_matches_rect_oracle(n, s, mode, p, seed):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n, 57)).astype(np.float32)
    key = jax.random.PRNGKey(seed)
    rs_m, ag_m = rps.sample_masks(key, n, p, s)
    got = np.asarray(rps.rps_exchange_global(
        {"x": jnp.asarray(V)}, key, p, n, mode=mode,
        masks=(rs_m, ag_m))["x"])
    want = _oracle(V, np.asarray(rs_m), np.asarray(ag_m), mode)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ---- property: p=0 exchange is the reliable average for every mode -------

@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(n=st.sampled_from([2, 4, 8, 16]), s=st.sampled_from([1, 2, 5, 8, 24]),
       mode=st.sampled_from(["model", "grad", "grad_renorm"]),
       seed=st.integers(0, 1000))
def test_p0_exchange_is_reliable_average(n, s, mode, seed):
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(n, 40)).astype(np.float32)
    out = np.asarray(rps.rps_exchange_global(
        {"x": jnp.asarray(V)}, jax.random.PRNGKey(seed), 0.0, n,
        mode=mode, s=s)["x"])
    np.testing.assert_allclose(out, np.broadcast_to(V.mean(0), V.shape),
                               atol=1e-5, rtol=1e-5)


# ---- property: _blockify/restore roundtrip (incl. model_dim path) --------

@settings(max_examples=15, deadline=None)
@given(s=st.sampled_from([1, 2, 3, 7, 16]),
       shape=st.sampled_from([(5,), (4, 6), (3, 5, 2), (2, 3, 4)]),
       model_dim=st.sampled_from([None, 0, -1]), seed=st.integers(0, 1000))
def test_blockify_restore_roundtrip(s, shape, model_dim, seed):
    if model_dim is not None:
        model_dim = model_dim % len(shape)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                    jnp.float32)
    blocks, restore = rps._blockify(x, s, model_dim)
    assert blocks.shape[0] == s
    np.testing.assert_array_equal(np.asarray(restore(blocks)),
                                  np.asarray(x))


# ---- masks: owner forcing, diagonal where s == n, every family -----------

CHANNEL_SPECS = ["bernoulli:p=0.3", "ge:p_bad=1.0,burst=4,p=0.3",
                 "hetero:n_pods=4,p_cross=0.4",
                 "deadline:deadline_ms=4,straggler_frac=0.3"]


@pytest.mark.parametrize("spec", CHANNEL_SPECS)
@pytest.mark.parametrize("s", [1, 3, 8, 20])
def test_channel_masks_rectangular_and_owner_forced(spec, s):
    n = 8
    c = ch.make_channel(spec, n, s=s)
    state = c.init_state(KEY)
    own = np.arange(s) % n
    for t in range(8):
        rs_m, ag_m, state = c.sample(jax.random.fold_in(KEY, t), state)
        assert rs_m.shape == (n, s) and ag_m.shape == (n, s)
        assert np.asarray(rs_m)[own, np.arange(s)].all(), \
            "owner entries must always be delivered (RS)"
        assert np.asarray(ag_m)[own, np.arange(s)].all(), \
            "owner entries must always be delivered (AG)"


@pytest.mark.parametrize("spec", CHANNEL_SPECS)
def test_channel_masks_diag_forced_where_square(spec):
    n = 8
    c = ch.make_channel(spec, n, s=n)
    rs_m, ag_m, _ = c.sample(KEY, c.init_state(KEY))
    assert np.asarray(rs_m).diagonal().all()
    assert np.asarray(ag_m).diagonal().all()


def test_trace_channel_rectangular_masks():
    up = np.full((2, 4), 0.3, np.float32)
    c = ch.TraceChannel(4, {"up": up, "down": np.zeros_like(up)}, s=7)
    rs_m, ag_m, _ = c.sample(KEY, c.init_state(KEY))
    assert rs_m.shape == (4, 7) and ag_m.shape == (4, 7)
    own = np.arange(7) % 4
    assert np.asarray(rs_m)[own, np.arange(7)].all()


# ---- s = n bit-identity with the pre-PR behaviour ------------------------

def test_sample_masks_square_bit_identical_to_seed_formula():
    for n, p in ((4, 0.0), (8, 0.3), (16, 0.7)):
        for t in range(4):
            key = jax.random.fold_in(KEY, t)
            k1, k2 = jax.random.split(key)
            eye = jnp.eye(n, dtype=bool)
            rs_seed = jax.random.bernoulli(k1, 1.0 - p, (n, n)) | eye
            ag_seed = jax.random.bernoulli(k2, 1.0 - p, (n, n)) | eye
            for s in (None, n):
                rs_m, ag_m = rps.sample_masks(key, n, p, s)
                assert np.array_equal(np.asarray(rs_m), np.asarray(rs_seed))
                assert np.array_equal(np.asarray(ag_m), np.asarray(ag_seed))


@pytest.mark.parametrize("mode", ["model", "grad", "grad_renorm"])
def test_global_exchange_square_s_bit_identical(mode):
    n = 8
    V = {"x": jnp.asarray(
        np.random.default_rng(1).normal(size=(n, 103)).astype(np.float32))}
    a = rps.rps_exchange_global(V, KEY, 0.3, n, mode=mode)
    b = rps.rps_exchange_global(V, KEY, 0.3, n, mode=mode, s=n)
    np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))


@pytest.mark.parametrize("spec", CHANNEL_SPECS)
def test_channels_square_s_bit_identical(spec):
    c0 = ch.make_channel(spec, 8)
    c1 = ch.make_channel(spec, 8, s=8)
    s0, s1 = c0.init_state(KEY), c1.init_state(KEY)
    for t in range(5):
        k = jax.random.fold_in(KEY, t)
        rs0, ag0, s0 = c0.sample(k, s0)
        rs1, ag1, s1 = c1.sample(k, s1)
        assert np.array_equal(np.asarray(rs0), np.asarray(rs1))
        assert np.array_equal(np.asarray(ag0), np.asarray(ag1))


def test_simulator_square_servers_bit_identical():
    """n_servers=n (explicit) reproduces n_servers=None exactly."""
    from repro.train.simulator import SimulatorConfig, run_simulation

    def init_fn(key):
        return {"w": jax.random.normal(key, (6, 4)) * 0.1}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(4, 8, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(4, 8, 4)), jnp.float32)
    outs = []
    for ns in (None, 4):
        h = run_simulation(loss_fn, init_fn, lambda t: (xs, ys),
                           SimulatorConfig(n_workers=4, drop_rate=0.25,
                                           aggregator="rps_model", lr=0.1,
                                           steps=10, eval_every=9,
                                           n_servers=ns))
        outs.append(np.asarray(h["params"]["w"]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_simulator_rectangular_servers_converges():
    from repro.train.simulator import SimulatorConfig, run_simulation

    def init_fn(key):
        return {"w": jax.random.normal(key, (6, 4)) * 0.1}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(4, 16, 6)), jnp.float32)
    w_true = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    ys = xs @ w_true
    for ns in (1, 3, 8):
        h = run_simulation(loss_fn, init_fn, lambda t: (xs, ys),
                           SimulatorConfig(n_workers=4, drop_rate=0.3,
                                           aggregator="rps_model", lr=0.2,
                                           steps=40, eval_every=39,
                                           n_servers=ns))
        assert h["loss"][-1] < h["loss"][0] * 0.5, \
            f"no convergence with n_servers={ns}"
        assert f"s={ns}" in h["channel"]


# ---- rectangular W-matrix oracle properties ------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.sampled_from([2, 4, 8]), s=st.sampled_from([1, 3, 8, 11]),
       p=st.floats(0.0, 0.9), seed=st.integers(0, 100))
def test_rect_w_columns_are_convex_combinations(n, s, p, seed):
    rng = np.random.default_rng(seed)
    owners, rsm, agm = wmatrix.sample_masks(rng, n, p, s=s)
    assert owners.shape == (s,) and rsm.shape == (n, s)
    W = wmatrix.build_w(n, owners, rsm, agm)
    assert W.shape == (s, n, n)
    for j in range(s):
        np.testing.assert_allclose(W[j].sum(axis=0), np.ones(n), atol=1e-9)
        assert (W[j] >= 0).all()


def test_wmatrix_square_draw_bit_identical():
    """The s-generalised numpy oracle draws the seed's square masks
    bit-identically from the same generator state."""
    a = wmatrix.sample_masks(np.random.default_rng(3), 8, 0.3)
    b = wmatrix.sample_masks(np.random.default_rng(3), 8, 0.3, s=8)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---- theory: server-scaling law ------------------------------------------

def test_theory_square_s_is_identity():
    for n, p in ((8, 0.1), (16, 0.3)):
        assert theory.alpha1_bound(n, p) == theory.alpha1_bound(n, p, s=n)
        assert theory.alpha2_bound(n, p) == theory.alpha2_bound(n, p, s=n)
        assert theory.corollary2_rate(n, p, 1000) == \
            theory.corollary2_rate(n, p, 1000, s=n)


def test_theory_alpha2_diminishes_with_servers():
    """Corollary 2's server-count claim at fixed n, p: α₂ strictly shrinks
    as the blocks get finer (fewer packets each)."""
    vals = [theory.alpha2_bound(16, 0.1, s=s) for s in (1, 2, 4, 8, 16)]
    assert all(a > b for a, b in zip(vals, vals[1:])), vals
    # O(p(1-p)/s): doubling s roughly halves the p-induced excess of the
    # bound at small p (the closed form keeps a p-independent
    # (1-p)^(n-1)/n slack floor, so the law shows in the excess)
    floor = theory.alpha2_bound(16, 0.0)
    small = [theory.alpha2_bound(16, 0.01, s=s) - floor for s in (2, 4, 8)]
    for a, b in zip(small, small[1:]):
        assert 1.5 < a / b < 2.5


def test_block_drop_rate():
    assert theory.block_drop_rate(0.1, 1) == pytest.approx(0.1)
    assert theory.block_drop_rate(0.0, 16) == 0.0
    assert theory.block_drop_rate(0.1, 16) == pytest.approx(1 - 0.9 ** 16)
    assert theory.packets_per_block(4, 16) == 4
    assert theory.packets_per_block(3, 16) == 6          # ceil
    assert theory.packets_per_block(32, 16) == 1         # never below 1
    with pytest.raises(ValueError):
        theory.block_drop_rate(1.5, 2)
    with pytest.raises(ValueError):
        theory.packets_per_block(0, 16)


# ---- registry: s plumbing ------------------------------------------------

def test_make_channel_s_plumbing():
    c = ch.make_channel("bernoulli:p=0.2,s=4", 8)
    assert c.s == 4 and c.n == 8
    assert ch.make_channel("ge:p_bad=1.0,burst=4,p=0.1", 8, s=3).s == 3
    # explicit arg must agree with a spec-carried s
    with pytest.raises(ValueError):
        ch.make_channel("bernoulli:p=0.2,s=4", 8, s=2)
    assert ch.make_channel("bernoulli:p=0.2,s=4", 8, s=4).s == 4
    # instance pass-through checks s compatibility
    inst = ch.BernoulliChannel(8, 0.1, s=4)
    assert ch.make_channel(inst, 8, s=4) is inst
    assert ch.make_channel(inst, 8) is inst
    with pytest.raises(ValueError):
        ch.make_channel(inst, 8, s=8)


# ---- rs_dtype reaches the exchange through the pytree wrapper ------------

def test_rps_exchange_wrapper_plumbs_rs_dtype():
    """Regression: the seed wrapper dropped rs_dtype, so bf16 RS
    accumulation was unreachable from the pytree API. One-device mesh:
    the renormalised average must round through bf16 iff requested."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    tree = {"w": jnp.asarray(
        np.random.default_rng(0).normal(size=(37,)).astype(np.float32))}

    def run(rs_dtype):
        f = _shard_map(
            lambda t: rps.rps_exchange(t, KEY, 0.0, "data",
                                       rs_dtype=rs_dtype),
            mesh, (P(),), P(), {"data"})
        return np.asarray(jax.jit(f)(tree)["w"])

    out_f32 = run(jnp.float32)
    out_bf16 = run(jnp.bfloat16)
    want_bf16 = np.asarray(tree["w"]).astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(out_f32, np.asarray(tree["w"]))
    np.testing.assert_array_equal(out_bf16, want_bf16)
    assert not np.array_equal(out_bf16, out_f32), \
        "bf16 RS accumulation must actually round (else the dtype was lost)"


# ---- parity matrix: collective vs global, s ≠ n, all modes/backends ------

def test_parity_matrix_collective_vs_global_8dev():
    """rps_exchange_flat (shard_map collective) ≡ rps_exchange_global
    (stacked) under shared masks: modes × s ∈ {3, 8, 16} × channel
    families, global jnp vs pallas-interpret backends, and bf16 rs_dtype
    through the pytree wrapper. Subprocess with 8 forced host devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro import channels as ch
        from repro.core import rps

        if hasattr(jax, "shard_map"):
            def sm(f, mesh, in_specs, out_specs):
                return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs,
                                     axis_names={"data"})
        else:
            from jax.experimental.shard_map import shard_map as _sm
            def sm(f, mesh, in_specs, out_specs):
                return _sm(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_rep=False)

        n, D = 8, 104
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        V = np.random.default_rng(5).normal(size=(n, D)).astype(np.float32)
        key = jax.random.PRNGKey(11)

        def flat(masks, mode):
            def body(v, k, rs, ag):
                return rps.rps_exchange_flat(
                    v[0], k, 0.0, "data", mode=mode, masks=(rs, ag))[None]
            f = sm(body, mesh, (P("data"), P(), P(), P()), P("data"))
            return np.asarray(jax.jit(f)(jnp.asarray(V), key, *masks))

        def glob(masks, mode, backend="jnp"):
            return np.asarray(rps.rps_exchange_global(
                {"x": jnp.asarray(V)}, key, 0.0, n, mode=mode,
                masks=masks, backend=backend)["x"])

        checks = 0
        specs = ["bernoulli:p=0.3", "ge:p_bad=1.0,burst=4,p=0.3",
                 "hetero:n_pods=4,p_cross=0.4",
                 "deadline:deadline_ms=4,straggler_frac=0.3"]
        for s in (3, 8, 16):
            for spec in specs:
                c = ch.make_channel(spec, n, s=s)
                rs_m, ag_m, _ = c.sample(key, c.init_state(key))
                masks = (rs_m, ag_m)
                for mode in ("model", "grad", "grad_renorm"):
                    a, b = flat(masks, mode), glob(masks, mode)
                    err = np.abs(a - b).max()
                    assert err < 2e-5, (spec, s, mode, err)
                    checks += 1
                for mode in ("model", "grad_renorm"):
                    b = glob(masks, mode, backend="pallas")
                    a = glob(masks, mode)
                    err = np.abs(a - b).max()
                    assert err < 1e-5, ("pallas", spec, s, mode, err)
                    checks += 1

        # wrapper plumbs rs_dtype: bf16 output differs from f32 and equals
        # the flat bf16 path exactly
        rs_m, ag_m = rps.sample_masks(key, n, 0.25)
        def wrap(dt):
            def body(t, k, rs, ag):
                sq = jax.tree.map(lambda x: x[0], t)
                out = rps.rps_exchange(sq, k, 0.0, "data",
                                       masks=(rs, ag), rs_dtype=dt)
                return jax.tree.map(lambda x: x[None], out)
            f = sm(body, mesh, (P("data"), P(), P(), P()), P("data"))
            return np.asarray(jax.jit(f)(
                {"w": jnp.asarray(V)}, key, rs_m, ag_m)["w"])
        def flat_dt(dt):
            def body(v, k, rs, ag):
                return rps.rps_exchange_flat(
                    v[0], k, 0.0, "data", masks=(rs, ag),
                    rs_dtype=dt)[None]
            f = sm(body, mesh, (P("data"), P(), P(), P()), P("data"))
            return np.asarray(jax.jit(f)(jnp.asarray(V), key, rs_m, ag_m))
        w16, w32 = wrap(jnp.bfloat16), wrap(jnp.float32)
        assert np.array_equal(w16, flat_dt(jnp.bfloat16))
        assert np.array_equal(w32, flat_dt(jnp.float32))
        assert not np.array_equal(w16, w32)
        checks += 1
        print("PARITY_OK", checks)
    """) % SRC
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=570)
    assert "PARITY_OK" in r.stdout, r.stdout + r.stderr
