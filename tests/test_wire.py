"""Wire pipeline (DESIGN.md §13): codec/recovery units, the f32+renorm
bit-identity matrix (explicit pipeline args ≡ the legacy default across
modes × s × engines × bucket layouts), EF residual semantics and the
checkpoint round-trip (mid-run save → restore → bitwise continuation),
the bf16-wire rps_exchange_leaf parity (satellite bugfix), the
fused-dispatch claim for every codec (jax.export through Mosaic +
tools.check_hlo), and the theory fold-in.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_state, save_state
from repro.core import plan as plan_lib
from repro.core import rps, theory
from repro.core import wire as wire_lib
from repro.kernels import rps_ring
from repro.train.simulator import SimulatorConfig, run_simulation

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import check_hlo                                    # noqa: E402

KEY = jax.random.PRNGKey(13)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, timeout=570) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


# ---- canon_wire_dtype: one canonicaliser for every spelling ---------------

def test_canon_wire_dtype_spellings():
    for spell in ("f32", "fp32", "float32", jnp.float32,
                  jnp.dtype(jnp.float32), None):
        assert wire_lib.canon_wire_dtype(spell) == jnp.dtype(jnp.float32)
    for spell in ("bf16", "bfloat16", jnp.bfloat16):
        assert wire_lib.canon_wire_dtype(spell) == jnp.dtype(jnp.bfloat16)
    assert wire_lib.canon_wire_dtype("int8") == jnp.dtype(jnp.int8)
    assert wire_lib.canon_wire_dtype(
        wire_lib.make_codec("int8")) == jnp.dtype(jnp.int8)
    assert wire_lib.canon_wire_name("bfloat16") == "bf16"
    assert wire_lib.canon_wire_name(jnp.float32) == "f32"
    with pytest.raises(TypeError):
        wire_lib.canon_wire_dtype("not_a_dtype")


def test_plan_wire_bytes_canon_everywhere():
    """Satellite: plan.wire_bytes accepts every spelling through the one
    canonicaliser — strings, short names and jnp dtypes all agree."""
    tree = {"a": jnp.zeros((24,)), "b": jnp.zeros((8, 2))}
    p = plan_lib.make_plan(tree, 4, n_buckets=2)
    assert p.wire_bytes("bfloat16") == p.wire_bytes("bf16") \
        == p.wire_bytes(jnp.bfloat16)
    assert p.wire_bytes("float32") == p.wire_bytes() == p.wire_bytes("f32")
    # the int8 codec quarters the RS leg exactly (scale side-channel is
    # reported separately, not folded into the headline ratio)
    assert p.rs_leg_bytes("int8") * 4 == p.rs_leg_bytes("f32")
    d8 = plan_lib.make_plan(tree, 4, n_buckets=2, wire="int8").describe()
    assert d8["rs_bytes_ratio"] == 0.25 and d8["scale_bytes"] > 0
    dbf = p.describe("bf16")
    assert dbf["rs_bytes_ratio"] == 0.5 and dbf["scale_bytes"] == 0


def test_plan_carries_pipeline_fields():
    tree = {"a": jnp.zeros((32,))}
    p = plan_lib.make_plan(tree, 4, wire="int8", recovery="ef")
    assert p.wire == "int8" and p.recovery == "ef"
    d = p.describe()
    assert d["wire"] == "int8" and d["recovery"] == "ef"
    assert plan_lib.per_leaf_plan(tree, 4).wire == "f32"
    assert plan_lib.plan_from_config(tree, 4, wire="bfloat16").wire == "bf16"
    with pytest.raises(ValueError):
        plan_lib.make_plan(tree, 4, recovery="retransmit")
    with pytest.raises(TypeError):
        plan_lib.make_plan(tree, 4, wire="int7")


# ---- codec units ----------------------------------------------------------

def test_linear_codecs_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 6)),
                    jnp.float32)
    f32 = wire_lib.make_codec("f32")
    enc, aux = f32.encode(x)
    assert aux is None and np.array_equal(np.asarray(enc), np.asarray(x))
    assert np.array_equal(np.asarray(f32.fake_quant(x)), np.asarray(x))
    bf = wire_lib.make_codec("bf16")
    assert bf.encode(x)[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(bf.fake_quant(x)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    assert bf.accum_dtype == jnp.dtype(jnp.bfloat16)


def test_int8_codec_error_bound_and_grid():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 64)) * 3.0, jnp.float32)
    c = wire_lib.make_codec("int8")
    assert c.quantized and c.accum_dtype == jnp.dtype(jnp.float32)
    q, delta = c.encode(x)                       # RNE without a key
    assert q.dtype == jnp.int8 and delta.shape == (5, 1)
    dec = np.asarray(c.decode(q, delta))
    # per-row grid step bounds the error; RNE is within half a step
    step = np.asarray(delta)
    assert np.all(np.abs(dec - np.asarray(x)) <= 0.5 * step + 1e-7)
    # zero rows survive exactly
    z = c.fake_quant(jnp.zeros((3, 8)))
    assert np.array_equal(np.asarray(z), np.zeros((3, 8), np.float32))


def test_int8_stochastic_rounding_is_unbiased():
    """E[decode(encode(x, key))] = x elementwise — the unbiasedness the
    convergence argument needs from the compression point. (The row max
    itself is always on-grid; the off-grid interior elements are the
    stochastic ones.)"""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 64)), jnp.float32)
    c = wire_lib.make_codec("int8")
    draws = np.stack([
        np.asarray(c.fake_quant(x, jax.random.fold_in(KEY, i)))
        for i in range(600)])
    step = float(np.abs(np.asarray(x)).max() / 127.0)
    bias = np.abs(draws.mean(0) - np.asarray(x)).max()
    assert bias < 0.1 * step, (bias, step)       # mean error << grid step
    assert draws.std(0).max() > 0.1 * step       # actually stochastic


# ---- recovery units -------------------------------------------------------

def test_recovery_construction_and_divisor():
    r = wire_lib.make_recovery("scale", p=0.25)
    assert r.expected_count(8) == 8 * 0.75
    assert wire_lib.make_recovery(None).kind == "renorm"
    assert wire_lib.make_recovery("ef").needs_state
    # p binds only when the instance doesn't carry one
    pre = wire_lib.Recovery("scale", p=0.5)
    assert wire_lib.make_recovery(pre, p=0.1).p == 0.5
    with pytest.raises(ValueError):
        wire_lib.make_recovery("arq")
    with pytest.raises(ValueError):
        wire_lib.Recovery("scale").expected_count(4)
    # clamped at the always-delivered own contribution
    assert wire_lib.Recovery("scale", p=1.0).expected_count(4) == 1.0


def test_scale_recovery_is_unbiased_zero_fill():
    """Monte-Carlo over mask draws: E[exchange(scale)] equals the true
    mean (Weintraub-style unbiased estimation), where renorm's mean is
    conditionally-unbiased but not equal per draw."""
    n, p = 8, 0.3
    rng = np.random.default_rng(2)
    tree = {"w": jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)}
    true_mean = np.asarray(tree["w"]).mean(0)
    acc = np.zeros((n, 40), np.float32)
    reps = 600
    for r in range(reps):
        out = rps.rps_exchange_global(tree, jax.random.fold_in(KEY, r), p,
                                      n, mode="model", recovery="scale")
        acc += np.asarray(out["w"])
    est = acc / reps
    # every worker's expected post-exchange value is the true mean
    # (AG-drops mix in the local param: E = (1-p')·mean + p'·local — the
    # own row is mask-forced, so compare the mean over workers)
    np.testing.assert_allclose(est.mean(0), true_mean, atol=0.05)


# ---- the f32+renorm bit-identity matrix (acceptance) ----------------------

@pytest.mark.slow
def test_default_pipeline_bit_identical_matrix_8dev():
    """wire="f32", recovery="renorm" ≡ the legacy call (no pipeline args)
    across modes × s ∈ {1, n/2, n, 2n} × engines {xla, ring} × layouts
    {single, per_leaf, bucketed-2} × both mask draws — bitwise, and the
    global path agrees likewise."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import plan as plan_lib, rps
        from repro.train.trainer import _shard_map

        n = 8
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(21)
        tree = {"a": jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(n, 33)), jnp.float32),
                "c": jnp.asarray(rng.normal(size=(n, 5, 5)),
                                 jnp.bfloat16)}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        key = jax.random.PRNGKey(3)
        specs = jax.tree.map(lambda _: P("data"), per_worker)

        def run_collective(fn):
            def body(t, k):
                sq = jax.tree.map(lambda x: x[0], t)
                out = fn(sq, k)
                return jax.tree.map(lambda x: x[None], out)
            f = _shard_map(body, mesh, (specs, P()), specs, {"data"})
            return jax.tree.map(np.asarray, jax.jit(f)(tree, key))

        plans = {
            "single": lambda s: plan_lib.single_bucket_plan(per_worker,
                                                            n, s),
            "per_leaf": lambda s: plan_lib.per_leaf_plan(per_worker, n,
                                                         s=s),
            "bucketed2": lambda s: plan_lib.make_plan(per_worker, n, s,
                                                      n_buckets=2)}
        checks = 0
        for s in (1, n // 2, n, 2 * n):
            for pname, mk in plans.items():
                plan = mk(s)
                nb = plan.n_buckets if plan.per_bucket_masks else None
                masks = rps.sample_masks(key, n, 0.3, s, n_buckets=nb)
                for mode in ("model", "grad", "grad_renorm"):
                    for engine in ("xla", "ring"):
                        legacy = run_collective(
                            lambda t, k: rps.rps_exchange_plan(
                                t, k, 0.3, "data", plan=plan, mode=mode,
                                masks=masks, engine=engine))
                        explicit = run_collective(
                            lambda t, k: rps.rps_exchange_plan(
                                t, k, 0.3, "data", plan=plan, mode=mode,
                                masks=masks, engine=engine, wire="f32",
                                recovery="renorm"))
                        for kk in legacy:
                            assert np.array_equal(legacy[kk],
                                                  explicit[kk]), \
                                (s, pname, mode, engine, kk)
                        checks += 1
                        g = jax.tree.map(
                            np.asarray,
                            rps.rps_exchange_global(
                                tree, key, 0.3, n, mode=mode,
                                masks=masks, plan=plan, engine=engine,
                                wire="f32", recovery="renorm"))
                        g0 = jax.tree.map(
                            np.asarray,
                            rps.rps_exchange_global(
                                tree, key, 0.3, n, mode=mode,
                                masks=masks, plan=plan, engine=engine))
                        for kk in legacy:
                            assert np.array_equal(g[kk], g0[kk]), \
                                ("global", s, pname, mode, engine, kk)
                        checks += 1
        print("WIRE_DEFAULT_PARITY_OK", checks)
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_DEFAULT_PARITY_OK 144" in out, out


def test_flat_and_pytree_paths_take_pipeline_args():
    """wire/recovery thread through rps_exchange_flat / rps_exchange; the
    f32 wire defers to rs_dtype (absorption, not override)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import rps
        from repro.train.trainer import _shard_map

        n = 4
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(4)
        v = jnp.asarray(rng.integers(-4, 5, (n, 37)), jnp.float32)
        key = jax.random.PRNGKey(1)
        masks = rps.sample_masks(key, n, 0.4)

        def run(fn):
            f = _shard_map(lambda x, k: fn(x[0], k)[None], mesh,
                           (P("data"), P()), P("data"), {"data"})
            return np.asarray(jax.jit(f)(v, key))

        # explicit bf16 wire == legacy rs_dtype=bf16 (integer data:
        # bitwise)
        a = run(lambda x, k: rps.rps_exchange_flat(
            x, k, 0.4, "data", masks=masks, wire="bf16"))
        b = run(lambda x, k: rps.rps_exchange_flat(
            x, k, 0.4, "data", masks=masks, rs_dtype=jnp.bfloat16))
        assert np.array_equal(a, b)
        # f32 wire + bf16 rs_dtype: rs_dtype wins (the absorbed knob)
        c = run(lambda x, k: rps.rps_exchange_flat(
            x, k, 0.4, "data", masks=masks, wire="f32",
            rs_dtype=jnp.bfloat16))
        assert np.array_equal(b, c)
        # int8 + scale run end-to-end on both engines
        for engine in ("xla", "ring"):
            run(lambda x, k, e=engine: rps.rps_exchange_flat(
                x, k, 0.4, "data", masks=masks, wire="int8",
                recovery="scale", engine=e))
        # ef is plan/global-only on this stateless path
        try:
            run(lambda x, k: rps.rps_exchange_flat(
                x, k, 0.4, "data", masks=masks, recovery="ef"))
            raise SystemExit("expected ValueError")
        except ValueError:
            pass
        print("WIRE_FLAT_OK")
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_FLAT_OK" in out, out


def test_leaf_path_forwards_wire_dtype_bf16_parity():
    """Satellite bugfix: rps_exchange_leaf forwards rs_dtype instead of
    pinning f32 — bf16-wire leaf ≡ bf16-wire flat on integer data
    (bitwise), and the old hard-coded call is what rs_dtype=f32 gives."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import rps
        from repro.train.trainer import _shard_map

        n = 4
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.integers(-4, 5, (n, 3, 8)), jnp.float32)
        key = jax.random.PRNGKey(0)
        masks = rps.sample_masks(key, n, 0.4)

        def leaf(dt):
            f = _shard_map(
                lambda v, r, g: rps.rps_exchange_leaf(
                    v[0], r, g, "data", mode="model",
                    rs_dtype=dt)[None],
                mesh, (P("data"), P(), P()), P("data"), {"data"})
            return np.asarray(jax.jit(f)(x, *masks))

        def flat(dt):
            f = _shard_map(
                lambda v, k: rps.rps_exchange_flat(
                    v[0].reshape(-1), k, 0.4, "data", mode="model",
                    masks=masks, rs_dtype=dt).reshape(1, 3, 8),
                mesh, (P("data"), P()), P("data"), {"data"})
            return np.asarray(jax.jit(f)(x, key))

        for dt in (jnp.float32, jnp.bfloat16):
            assert np.array_equal(leaf(dt), flat(dt)), dt
        # on non-integer data the two wire dtypes genuinely differ —
        # proof the knob reaches the engine (the seed pinned f32)
        x_cont = x + 0.1234567
        fcont = _shard_map(
            lambda v, r, g: rps.rps_exchange_leaf(
                v[0], r, g, "data", mode="model",
                rs_dtype=jnp.bfloat16)[None],
            mesh, (P("data"), P(), P()), P("data"), {"data"})
        f32out = _shard_map(
            lambda v, r, g: rps.rps_exchange_leaf(
                v[0], r, g, "data", mode="model")[None],
            mesh, (P("data"), P(), P()), P("data"), {"data"})
        a = np.asarray(jax.jit(fcont)(x_cont, *masks))
        b = np.asarray(jax.jit(f32out)(x_cont, *masks))
        assert not np.array_equal(a, b)
        assert np.abs(a - b).max() < 0.05          # still the same round
        print("WIRE_LEAF_OK")
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_LEAF_OK" in out, out


# ---- EF recovery ----------------------------------------------------------

def test_ef_f32_is_renorm_and_residual_zero():
    """The f32 codec is exact, so EF's residual stays zero and the
    exchange equals plain renorm."""
    n = 8
    rng = np.random.default_rng(7)
    tree = {"w": jnp.asarray(rng.normal(size=(n, 24)), jnp.float32)}
    ef0 = wire_lib.init_ef_state(tree)
    out_ef, ef1 = rps.rps_exchange_global(tree, KEY, 0.3, n, mode="model",
                                          recovery="ef", ef_state=ef0)
    out = rps.rps_exchange_global(tree, KEY, 0.3, n, mode="model")
    np.testing.assert_array_equal(np.asarray(out_ef["w"]),
                                  np.asarray(out["w"]))
    assert np.all(np.asarray(ef1["w"]) == 0.0)


def test_ef_residual_is_codec_error_and_replays():
    """bf16 wire: round 1 residual == intent − bf16(intent); round 2's
    send is compensated — the two-round *sum* of delivered values tracks
    the exact sum better than uncompensated rounding (telescoping)."""
    n = 4
    rng = np.random.default_rng(8)
    tree = {"w": jnp.asarray(rng.normal(size=(n, 16)), jnp.float32)}
    ones = (jnp.ones((n, n), bool), jnp.ones((n, n), bool))  # no drops
    ef0 = wire_lib.init_ef_state(tree)
    out1, ef1 = rps.rps_exchange_global(tree, KEY, 0.0, n, mode="model",
                                        masks=ones, wire="bf16",
                                        recovery="ef", ef_state=ef0)
    want = np.asarray(tree["w"], np.float32) - np.asarray(
        tree["w"].astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(ef1["w"]), want, rtol=0, atol=0)
    # replay: the compensated send differs from the raw encode next round
    out2, ef2 = rps.rps_exchange_global(tree, KEY, 0.0, n, mode="model",
                                        masks=ones, wire="bf16",
                                        recovery="ef", ef_state=ef1)
    plain = rps.rps_exchange_global(tree, KEY, 0.0, n, mode="model",
                                    masks=ones, wire="bf16")
    exact = np.asarray(tree["w"], np.float32).mean(0, keepdims=True)
    err_ef = np.abs(np.asarray(out1["w"]) + np.asarray(out2["w"])
                    - 2 * exact).max()
    err_plain = np.abs(2 * np.asarray(plain["w"]) - 2 * exact).max()
    assert err_ef <= err_plain + 1e-7


def test_ef_collective_matches_global_int8():
    """The plan path's EF (collective, 8 devices) and the global path's
    EF agree on the xla engine: same stochastic encode keys, same
    residual update."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import plan as plan_lib, rps
        from repro.core import wire as wire_lib
        from repro.train.trainer import _shard_map

        n = 8
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(9)
        tree = {"a": jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(n, 33)), jnp.float32)}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        key = jax.random.PRNGKey(5)
        specs = jax.tree.map(lambda _: P("data"), per_worker)
        plan = plan_lib.make_plan(per_worker, n, n_buckets=2, wire="int8",
                                  recovery="ef")
        masks = rps.sample_masks(key, n, 0.3, None,
                                 n_buckets=plan.n_buckets)
        ef_tree = jax.tree.map(lambda x: jnp.zeros_like(x), tree)

        def body(t, e, k):
            sq = jax.tree.map(lambda x: x[0], t)
            se = jax.tree.map(lambda x: x[0], e)
            out, ne = rps.rps_exchange_plan(sq, k, 0.3, "data", plan=plan,
                                            mode="model", masks=masks,
                                            ef_state=se)
            return (jax.tree.map(lambda x: x[None], out),
                    jax.tree.map(lambda x: x[None], ne))
        f = _shard_map(body, mesh, (specs, specs, P()), (specs, specs),
                       {"data"})
        out_c, ef_c = jax.jit(f)(tree, ef_tree, key)

        out_g, ef_g = rps.rps_exchange_global(
            tree, key, 0.3, n, mode="model", masks=masks, plan=plan,
            ef_state=ef_tree)
        # same pipeline, same masks; stochastic encode keys differ
        # (per-bucket fold vs per-group fold), so compare within the
        # int8 grid step, and residuals must be bounded by it too
        for kk in tree:
            a, b = np.asarray(out_c[kk]), np.asarray(out_g[kk])
            scale = np.abs(np.asarray(tree[kk])).max() / 127.0
            assert np.abs(a - b).max() <= 2 * scale, kk
            r = np.abs(np.asarray(ef_c[kk]))
            assert r.max() <= scale + 1e-6, kk      # |resid| <= one step
        print("WIRE_EF_COLLECTIVE_OK")
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_EF_COLLECTIVE_OK" in out, out


# ---- simulator integration ------------------------------------------------

def _lin_task(n=8, seed=0):
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(n, 16, 6)), jnp.float32)
    w_true = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    ys = xs @ w_true

    def init_fn(key):
        return {"w": jax.random.normal(key, (6, 4)) * 0.1}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    return loss_fn, init_fn, lambda t: (xs, ys)


def test_simulator_wire_recovery_configs_run_and_converge():
    loss_fn, init_fn, batch_fn = _lin_task()
    runs = {}
    for name, kw in (
            ("base", {}),
            # scale is the Weintraub unbiased *gradient* estimation
            # setting — on model averaging the multiplicative count
            # noise hits the iterate itself and compounds (DESIGN §13
            # composition table), so it pairs with rps_grad here
            ("scale", {"recovery": "scale", "aggregator": "rps_grad"}),
            ("bf16_ef", {"wire": "bf16", "recovery": "ef"}),
            ("int8_ef", {"wire": "int8", "recovery": "ef"})):
        h = run_simulation(loss_fn, init_fn, batch_fn,
                           SimulatorConfig(n_workers=8, drop_rate=0.2,
                                           steps=60, lr=0.2, warmup=5,
                                           n_buckets=2,
                                           **{"aggregator": "rps_model",
                                              **kw}))
        runs[name] = h["final_loss"]
        assert np.isfinite(h["final_loss"]), (name, h["final_loss"])
    assert runs["base"] < 0.05, runs
    assert runs["scale"] < 0.1, runs
    assert runs["bf16_ef"] < 0.05, runs
    assert runs["int8_ef"] < 0.1, runs
    # the plan describe in history reports the pipeline
    h = run_simulation(loss_fn, init_fn, batch_fn,
                       SimulatorConfig(n_workers=8, drop_rate=0.2,
                                       aggregator="rps_model", steps=2,
                                       wire="int8", recovery="ef"))
    ep = h["exchange_plan"]
    assert ep["wire"] == "int8" and ep["recovery"] == "ef"
    assert h["ef_state"] is not None


def test_simulator_ef_state_donated():
    """The EF residual is a hot-path carry: donated alongside
    params/opt_state/channel state."""
    from repro import channels as channels_lib
    from repro.optim import make_optimizer
    from repro.train import simulator as sim_lib
    scfg = SimulatorConfig(n_workers=4, drop_rate=0.2,
                           aggregator="rps_model", wire="int8",
                           recovery="ef", n_buckets=2,
                           channel="ge:p_bad=0.5,burst=4,p=0.2")
    n = scfg.n_workers
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(n, 8, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    params = {"w": jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32)}
    opt = make_optimizer(scfg.optimizer)
    channel = channels_lib.make_channel(scfg.channel, n, scfg.drop_rate)
    plan = plan_lib.plan_from_config(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                     params), n, n_buckets=2, wire="int8", recovery="ef")
    step = sim_lib.make_sim_step(loss_fn, scfg, channel, plan, opt)
    key = jax.random.PRNGKey(0)
    ef0 = wire_lib.init_ef_state(params)
    compiled = step.lower(params, opt.init(params), (xs, ys), key,
                          jnp.float32(0.1), channel.init_state(key),
                          ef0).compile()
    assert 6 in compiled.donate_argnums
    ef_in = ef0["w"]
    outs = step(params, opt.init(params), (xs, ys), key, jnp.float32(0.1),
                channel.init_state(key), ef0)
    assert len(outs) == 6
    jax.block_until_ready(outs)
    assert ef_in.is_deleted(), "donated EF residual must be consumed"


def test_checkpoint_roundtrip_ef_and_channel_state():
    """Satellite: save the full mid-run state (params, opt, EF residual,
    GE channel state) through checkpoint/ckpt.py, restore, and continue —
    bitwise identical to the uninterrupted run."""
    import tempfile
    loss_fn, init_fn, batch_fn = _lin_task(seed=3)
    scfg = SimulatorConfig(n_workers=8, drop_rate=0.25,
                           aggregator="rps_model", steps=9, lr=0.2,
                           wire="int8", recovery="ef", n_buckets=2,
                           channel="ge:p_bad=0.6,burst=3,p=0.25",
                           donate=False)
    full = run_simulation(loss_fn, init_fn, batch_fn, scfg)

    half = run_simulation(loss_fn, init_fn, batch_fn,
                          __import__("dataclasses").replace(scfg, steps=5))
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "mid.npz")
        save_state(path, **half["state"])
        like = {k: v for k, v in half["state"].items()}
        restored = load_state(path, **like)
        # bitwise round-trip through the npz container
        for name in like:
            for a, b in zip(jax.tree.leaves(like[name]),
                            jax.tree.leaves(restored[name])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        resumed = run_simulation(loss_fn, init_fn, batch_fn, scfg,
                                 state=restored, start_step=5)
    np.testing.assert_array_equal(np.asarray(full["params"]["w"]),
                                  np.asarray(resumed["params"]["w"]))
    np.testing.assert_array_equal(np.asarray(full["ef_state"]["w"]),
                                  np.asarray(resumed["ef_state"]["w"]))
    for a, b in zip(jax.tree.leaves(full["channel_state"]),
                    jax.tree.leaves(resumed["channel_state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_exchange_table_rejects_ef_without_send():
    """recovery='ef' without a compensated send (e.g. through
    rps_exchange_leaf) must raise, not silently run as renorm."""
    rs_m, ag_m = rps.sample_masks(KEY, 4, 0.2)
    with pytest.raises(ValueError, match="ef"):
        rps._exchange_table(jnp.zeros((4, 8)), rs_m, ag_m,
                            names=("data",), n=4, i=jnp.int32(0),
                            mode="model", recovery="ef")


def test_int8_collective_dither_decorrelated_across_workers():
    """The SR encode key folds in the device index: on identical worker
    data with no drops, the n averaged quantisation draws must cancel
    (~1/√n) instead of collapsing to one worker's (shared-key) error."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import rps
        from repro.core import wire as wire_lib
        from repro.train.trainer import _shard_map

        n = 8
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(3)
        x1 = rng.normal(size=(512,)).astype(np.float32)
        v = jnp.asarray(np.broadcast_to(x1, (n, 512)).copy())
        key = jax.random.PRNGKey(7)
        ones = (jnp.ones((n, n), bool), jnp.ones((n, n), bool))

        f = _shard_map(
            lambda x, k: rps.rps_exchange_flat(
                x[0], k, 0.0, "data", masks=ones, wire="int8")[None],
            mesh, (P("data"), P()), P("data"), {"data"})
        out = np.asarray(jax.jit(f)(v, key))
        # all workers adopt the same average (full AG delivery)
        assert np.abs(out - out[0]).max() == 0.0
        err_avg = np.abs(out[0] - x1)
        # a single worker's SR draw error, for scale
        c = wire_lib.make_codec("int8")
        single = np.abs(np.asarray(
            c.fake_quant(v[:1], jax.random.fold_in(key, 1))[0]) - x1)
        # averaged dither must be well below one draw's dither (shared
        # keys would make err_avg == a single draw's error)
        assert err_avg.mean() < 0.6 * single.mean(), \
            (err_avg.mean(), single.mean())
        print("WIRE_DITHER_OK", err_avg.mean() / single.mean())
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_DITHER_OK" in out, out


def test_trainer_ef_carry_and_donation_hint():
    """The mesh trainer with recovery="ef": train_step carries the
    params-shaped residual (arg 6), publishes init_ef_state and the
    donation hint, the residual is nonzero after a bf16-wire step, and
    the f32 default stays on the seed 3-tuple signature."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding
        from repro.configs import get_config
        from repro.models import build_model
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                                  n_layers=2, shard_acts=False)
        model = build_model(cfg, grouped=True)
        tcfg = TrainConfig(aggregator="rps_model", drop_rate=0.2,
                           wire="bf16", recovery="ef", engine="xla")
        init_state, step, shardings = make_train_setup(
            model, cfg, tcfg, mesh, rps_axes=("data",))
        assert step.donate_argnums == (0, 1, 6), step.donate_argnums
        assert step.plan.wire == "bf16" and step.plan.recovery == "ef"
        params, opt_state = jax.jit(init_state)(jax.random.PRNGKey(0))
        ef0 = step.init_ef_state(params)
        from repro.models.inputs import train_specs
        specs = train_specs(cfg, 8, 16)
        batch = {k: jnp.zeros((4, 2) + tuple(s.shape[1:]), s.dtype)
                 for k, s in specs.items()}
        ctx = jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh
        with ctx:
            out = jax.jit(step)(params, opt_state, batch, jnp.int32(0),
                                jax.random.PRNGKey(1), None, ef0)
        assert len(out) == 4                      # (+ ef_state)
        new_params, _, metrics, ef1 = out
        resid = sum(float(jnp.abs(x).sum())
                    for x in jax.tree.leaves(ef1))
        assert np.isfinite(float(metrics["loss"]))
        assert resid > 0.0                        # bf16 codec error
        # f32 default: seed signature, no residual carry
        _, step0, _ = make_train_setup(model, cfg, TrainConfig(
            aggregator="rps_model", drop_rate=0.2), mesh,
            rps_axes=("data",))
        assert step0.donate_argnums == (0, 1)
        assert step0.init_ef_state is None
        print("WIRE_TRAINER_EF_OK")
    """) % SRC
    out = _run_sub(code)
    assert "WIRE_TRAINER_EF_OK" in out, out


def test_launch_train_cli_wire_flags():
    """--wire/--recovery reach the simulator through the launcher."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "rps-paper-mlp", "--reduced", "--workers", "4", "--steps", "3",
         "--batch-size", "4", "--seq-len", "16", "--drop-rate", "0.2",
         "--buckets", "2", "--wire", "int8", "--recovery", "ef"],
        capture_output=True, text=True, env=env, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "wire=int8/ef" in r.stdout, r.stdout


# ---- lowering claims (acceptance + satellite) -----------------------------

def test_ring_tpu_export_one_dispatch_per_bucket_every_codec():
    """Every codec — f32, bf16 wire, int8 with in-kernel decode + hop
    requantisation, and the EF-compensated linear send — lowers to
    exactly ONE tpu_custom_call per bucket with zero StableHLO
    collectives, through the real Mosaic pipeline from this CPU host."""
    from jax import export
    n, k = 8, 2
    S = k * n

    def one(tbl, qt=None, qs=None, *, rs_dtype, levels, cid):
        pos = jnp.zeros((1,), jnp.int32)
        left = jnp.full((1,), n - 1, jnp.int32)
        right = jnp.ones((1,), jnp.int32)
        rs_row = jnp.ones((S, 1), rs_dtype)
        ag_row = jnp.ones((S, 1), jnp.float32)
        div = jnp.full((S, 1), n, rs_dtype)
        return rps_ring.ring_bucket_fused(
            tbl, rs_row, ag_row, div, pos, left, right, n=n, k=k,
            mode="model", rs_dtype=rs_dtype, qtable=qt, qscale=qs,
            levels=levels, collective_id=cid)

    variants = {
        "f32": lambda: one(jnp.zeros((S, 128), jnp.float32),
                           rs_dtype=jnp.float32, levels=0, cid=0),
        "bf16": lambda: one(jnp.zeros((S, 256), jnp.bfloat16),
                            rs_dtype=jnp.bfloat16, levels=0, cid=1),
        "int8": lambda: one(jnp.zeros((S, 128), jnp.float32),
                            jnp.zeros((S, 128), jnp.int8),
                            jnp.ones((S, 1), jnp.float32),
                            rs_dtype=jnp.float32, levels=127, cid=2),
        "ef_linear": lambda: one(jnp.zeros((S, 128), jnp.float32),
                                 jnp.zeros((S, 128), jnp.bfloat16),
                                 jnp.ones((S, 1), jnp.float32),
                                 rs_dtype=jnp.bfloat16, levels=0, cid=3),
    }

    def round_fn():
        return [v() for v in variants.values()]

    # the kernel's RDMAs need a manual region: one table per device of an
    # n-device ring (an abstract mesh — nothing is placed)
    mesh = jax.sharding.AbstractMesh((n,), ("x",))
    ring = jax.shard_map(round_fn, mesh=mesh, in_specs=(),
                         out_specs=[jax.sharding.PartitionSpec("x")]
                         * len(variants), check_vma=False)
    exp = export.export(jax.jit(ring), platforms=("tpu",))()
    txt = exp.mlir_module()
    # the satellite's loud-failure helper: 1 dispatch per "bucket"
    # (= variant here), zero collectives — codecs add no dispatches
    check_hlo.assert_fused_per_bucket(txt, len(variants))


@pytest.mark.slow
def test_cpu_lowering_codecs_add_no_collectives():
    """On the CPU lowering, int8/bf16 codecs change arithmetic only: the
    xla engine still lowers 2 collectives per bucket, the ring engine
    2(n−1) collective-permutes per bucket — plus 2(n−1) more for the
    int8 scale side-channel — and never an all_reduce/reduce_scatter."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import plan as plan_lib, rps
        from repro.train.trainer import _shard_map
        from tools import check_hlo

        n = 8
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        tree = {"a": jnp.zeros((n, 40)), "b": jnp.zeros((n, 24))}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        specs = jax.tree.map(lambda _: P("data"), per_worker)
        nb = 2
        plan = plan_lib.make_plan(per_worker, n, n_buckets=nb)

        for wire in ("f32", "bf16", "int8"):
            for engine in ("xla", "ring"):
                def body(t, k):
                    sq = jax.tree.map(lambda x: x[0], t)
                    out = rps.rps_exchange_plan(sq, k, 0.2, "data",
                                                plan=plan, engine=engine,
                                                wire=wire)
                    return jax.tree.map(lambda x: x[None], out)
                f = _shard_map(body, mesh, (specs, P()), specs, {"data"})
                txt = jax.jit(f).lower(tree,
                                       jax.random.PRNGKey(0)).as_text()
                got = check_hlo.collective_counts(txt)
                if engine == "xla":
                    want = {"reduce_scatter": nb, "all_gather": nb,
                            "collective_permute": 0}
                else:
                    per_hop = 2 if wire == "int8" else 1
                    want = {"reduce_scatter": 0, "all_gather": 0,
                            "collective_permute":
                                (per_hop + 1) * (n - 1) * nb}
                for op, cnt in want.items():
                    assert got[op] == cnt, (wire, engine, op, got)
                assert got["all_reduce"] == 0, (wire, engine, got)
        print("WIRE_CPU_HLO_OK")
    """) % (SRC, os.path.join(os.path.dirname(__file__), ".."))
    out = _run_sub(code)
    assert "WIRE_CPU_HLO_OK" in out, out


# ---- theory fold-in -------------------------------------------------------

def test_theory_wire_terms_reduce_to_paper_at_default():
    tree = {"a": jnp.zeros((64,))}
    n, p = 16, 0.1
    base = plan_lib.make_plan(tree, n, n_buckets=2)
    a1, a2 = theory.alpha_bounds_plan(base, n, p)
    assert a1 == theory.alpha1_bound(n, p, s=base.s,
                                     model_packets=base.model_packets)
    assert a2 == theory.alpha2_bound(n, p, s=base.s,
                                     model_packets=base.model_packets)
    assert theory.plan_wire_alpha2_extra(base, n, p) == 0.0
    # codec omega ordering: int8 > bf16 > f32, and EF squares it
    w8 = plan_lib.make_plan(tree, n, n_buckets=2, wire="int8")
    wb = plan_lib.make_plan(tree, n, n_buckets=2, wire="bf16")
    e8 = theory.plan_wire_alpha2_extra(w8, n, p)
    eb = theory.plan_wire_alpha2_extra(wb, n, p)
    assert e8 > eb > 0.0
    w8ef = plan_lib.make_plan(tree, n, n_buckets=2, wire="int8",
                              recovery="ef")
    assert 0 < theory.plan_wire_alpha2_extra(w8ef, n, p) < e8
    # scale recovery prices its divisor variance
    ws = plan_lib.make_plan(tree, n, n_buckets=2, recovery="scale")
    assert abs(theory.plan_wire_alpha2_extra(ws, n, p)
               - p / ((1 - p) * n)) < 1e-12
    # rates: wire variance can only slow the predicted rate
    r0 = theory.corollary2_rate_plan(base, n, p, 1000)
    r8 = theory.corollary2_rate_plan(w8, n, p, 1000)
    assert r8 >= r0
    # legacy duck-typed plan-likes (no wire fields) keep working
    class Legacy:
        s, model_packets = n, n
    a1l, a2l = theory.alpha_bounds_plan(Legacy, n, p)
    assert a1l == theory.alpha1_bound(n, p, s=n, model_packets=n)
