"""Ring engine (DESIGN.md §12): interpret-ring ↔ XLA-engine bit-parity
across the full matrix (modes × s × wire dtypes × bucket layouts ×
per-bucket masks), the ring-order global replay, the fused-TPU-dispatch
lowering claim (via ``jax.export`` + ``tools.check_hlo``), hot-path buffer
donation, and the global-path peak-memory regression guard.

Parity is asserted **bitwise** on integer-valued data: every engine
computes the same gated products and divisions on identical operands, and
integer-valued sums are exact in both f32 and bf16 — so any accumulation
order yields identical bits. Continuous data is checked to accumulation-
order tolerance.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import channels as channels_lib
from repro.core import plan as plan_lib
from repro.core import rps
from repro.kernels import rps_ring
from repro.optim import make_optimizer
from repro.train import simulator as sim_lib

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import check_hlo                                    # noqa: E402

KEY = jax.random.PRNGKey(5)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, timeout=570) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


# ---- engine resolution ----------------------------------------------------

def test_resolve_engine():
    assert rps.resolve_engine("xla") == "xla"
    assert rps.resolve_engine("ring") == "ring"
    # this repo's CI host is CPU: auto must pick the XLA collectives
    assert rps.resolve_engine("auto") == \
        ("ring" if jax.default_backend() == "tpu" else "xla")
    assert rps.resolve_engine(None) == rps.resolve_engine("auto")
    with pytest.raises(ValueError):
        rps.resolve_engine("mpi")


def test_plan_carries_engine():
    tree = {"a": jnp.zeros((32,))}
    p = plan_lib.make_plan(tree, 4, n_buckets=1, engine="ring")
    assert p.engine == "ring" and p.describe()["engine"] == "ring"
    assert plan_lib.per_leaf_plan(tree, 4).engine == "xla"
    assert plan_lib.plan_from_config(tree, 4, engine="auto").engine == "auto"


# ---- the parity matrix (subprocess, 8 forced host devices) ----------------

@pytest.mark.slow
def test_ring_engine_bitwise_parity_matrix_8dev():
    """The acceptance matrix: the interpret-mode ring engine is
    bit-identical to the XLA engine over modes {model, grad, grad_renorm}
    × s ∈ {1, n/2, n, 2n} × wire dtypes {f32, bf16} × bucket layouts
    {single-bucket, per-leaf, bucketed-2(per-bucket masks)} on
    integer-valued data — and the ring *global* replay is bit-identical
    to the ring *collective* schedule (same adds, same order)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import plan as plan_lib, rps
        from repro.train.trainer import _shard_map

        def sm(f, mesh, in_specs, out_specs):
            return _shard_map(f, mesh, in_specs, out_specs, {"data"})

        n = 8
        mesh = Mesh(np.array(jax.devices()).reshape(n), ("data",))
        rng = np.random.default_rng(3)
        # integer-valued payloads: sums are exact in f32 AND bf16, so the
        # ring accumulation order must agree with psum_scatter bit for bit
        tree = {"a": jnp.asarray(rng.integers(-4, 5, (n, 6, 4)),
                                 jnp.float32),
                "b": jnp.asarray(rng.integers(-4, 5, (n, 33)), jnp.float32),
                "c": jnp.asarray(rng.integers(-4, 5, (n, 5, 5)),
                                 jnp.bfloat16)}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        key = jax.random.PRNGKey(11)
        specs = jax.tree.map(lambda _: P("data"), per_worker)

        def run_collective(fn):
            def body(t, k):
                sq = jax.tree.map(lambda x: x[0], t)
                out = fn(sq, k)
                return jax.tree.map(lambda x: x[None], out)
            f = sm(body, mesh, (specs, P()), specs)
            return jax.tree.map(np.asarray, jax.jit(f)(tree, key))

        def tree_eq(a, b, tag, exact=True):
            for k in a:
                x = np.asarray(a[k], np.float32)
                y = np.asarray(b[k], np.float32)
                if exact:
                    assert np.array_equal(x, y), (tag, k,
                                                  np.abs(x - y).max())
                else:
                    assert np.abs(x - y).max() < 8e-3, (tag, k,
                                                        np.abs(x - y).max())

        plans = {
            "single": lambda s: plan_lib.single_bucket_plan(per_worker, n,
                                                            s),
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
                    for dt in (jnp.float32, jnp.bfloat16):
                        a = run_collective(
                            lambda t, k: rps.rps_exchange_plan(
                                t, k, 0.3, "data", plan=plan, mode=mode,
                                masks=masks, rs_dtype=dt, engine="ring"))
                        b = run_collective(
                            lambda t, k: rps.rps_exchange_plan(
                                t, k, 0.3, "data", plan=plan, mode=mode,
                                masks=masks, rs_dtype=dt, engine="xla"))
                        tree_eq(a, b, (s, pname, mode, dt.__name__))
                        checks += 1
                        # the single-device ring replay == the ring
                        # collective: bitwise at f32 wire (same adds,
                        # same order); one-bf16-ULP at bf16 wire, where
                        # XLA:CPU float-normalization may elide the
                        # intermediate bf16 rounding differently across
                        # the two program structures
                        g = jax.tree.map(np.asarray,
                                         rps.rps_exchange_global(
                                             tree, key, 0.3, n, mode=mode,
                                             masks=masks, plan=plan,
                                             engine="ring", rs_dtype=dt))
                        tree_eq(a, g, ("global", s, pname, mode,
                                       dt.__name__),
                                exact=dt == jnp.float32)
                        checks += 1
        print("RING_PARITY_OK", checks)
    """) % SRC
    out = _run_sub(code)
    assert "RING_PARITY_OK 144" in out, out


def test_ring_engine_continuous_data_close_8dev():
    """On continuous (non-integer) data the engines may differ only by
    accumulation order: bounded by a few ULPs at n = 8."""
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
        rng = np.random.default_rng(9)
        tree = {"a": jnp.asarray(rng.normal(size=(n, 50)), jnp.float32)}
        per_worker = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree)
        key = jax.random.PRNGKey(2)
        specs = {"a": P("data")}
        plan = plan_lib.make_plan(per_worker, n, n_buckets=1)

        def run(engine):
            def body(t, k):
                sq = jax.tree.map(lambda x: x[0], t)
                out = rps.rps_exchange_plan(sq, k, 0.2, "data", plan=plan,
                                            engine=engine)
                return jax.tree.map(lambda x: x[None], out)
            f = _shard_map(body, mesh, (specs, P()), specs, {"data"})
            return np.asarray(jax.jit(f)(tree, key)["a"])

        a, b = run("ring"), run("xla")
        err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-9)
        assert err < 1e-5, err
        print("RING_CLOSE_OK", err)
    """) % SRC
    out = _run_sub(code)
    assert "RING_CLOSE_OK" in out, out


def test_ring_flat_and_leaf_entry_points():
    """engine= threads through rps_exchange_flat / rps_exchange /
    rps_exchange_leaf (the ppermute ring under a 1-device axis degenerates
    to the local schedule — n=1 means no hops, renorm by own count)."""
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
        rng = np.random.default_rng(1)
        v = jnp.asarray(rng.integers(-4, 5, (n, 37)), jnp.float32)
        key = jax.random.PRNGKey(0)
        masks = rps.sample_masks(key, n, 0.4)

        def run(fn):
            f = _shard_map(lambda x, k: fn(x[0], k)[None], mesh,
                           (P("data"), P()), P("data"), {"data"})
            return np.asarray(jax.jit(f)(v, key))

        for mode in ("model", "grad", "grad_renorm"):
            a = run(lambda x, k: rps.rps_exchange_flat(
                x, k, 0.4, "data", mode=mode, masks=masks, engine="ring"))
            b = run(lambda x, k: rps.rps_exchange_flat(
                x, k, 0.4, "data", mode=mode, masks=masks, engine="xla"))
            assert np.array_equal(a, b), (mode, np.abs(a - b).max())
        # leaf path (partial-manual pins force the ppermute ring)
        x2 = jnp.asarray(rng.integers(-4, 5, (n, 3, 8)), jnp.float32)
        def leaf(engine):
            f = _shard_map(
                lambda x, r, g: rps.rps_exchange_leaf(
                    x[0], r, g, "data", mode="model", engine=engine)[None],
                mesh, (P("data"), P(), P()), P("data"), {"data"})
            return np.asarray(jax.jit(f)(x2, *masks))
        assert np.array_equal(leaf("ring"), leaf("xla"))
        print("RING_ENTRYPOINTS_OK")
    """) % SRC
    out = _run_sub(code)
    assert "RING_ENTRYPOINTS_OK" in out, out


def test_ring_multi_axis_flattened_ring():
    """The ring engine over flattened ("pod", "data") RPS axes: same ring
    order as the flattened single axis, bitwise vs the XLA engine (also a
    regression for _my_index on multi-axis meshes under jax<0.5's missing
    lax.axis_size)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import rps
        from repro.train.trainer import _shard_map

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("pod", "data"))
        rng = np.random.default_rng(0)
        v = jnp.asarray(rng.integers(-4, 5, (8, 24)), jnp.float32)
        key = jax.random.PRNGKey(0)
        masks = rps.sample_masks(key, 8, 0.3)

        def run(engine):
            def body(x, k):
                return rps.rps_exchange_flat(
                    x.reshape(-1), k, 0.3, ("pod", "data"), mode="model",
                    masks=masks, engine=engine)[None]
            f = _shard_map(body, mesh, (P(("pod", "data")), P()),
                           P(("pod", "data")), {"pod", "data"})
            return np.asarray(jax.jit(f)(v, key))

        a, b = run("ring"), run("xla")
        assert np.array_equal(a, b), np.abs(a - b).max()
        print("RING_MULTIAXIS_OK")
    """) % SRC
    out = _run_sub(code)
    assert "RING_MULTIAXIS_OK" in out, out


# ---- lowering claims ------------------------------------------------------

def test_ring_cpu_lowering_is_ppermute_schedule():
    """On CPU the ring engine lowers to exactly 2(n−1) collective-permutes
    per bucket and ZERO reduce-scatters/all-gathers — counted by
    tools/check_hlo (the loud-failure helper)."""
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

        for n_buckets in (1, 2):
            plan = plan_lib.make_plan(per_worker, n, n_buckets=n_buckets)
            for engine, want in (("ring", {"collective_permute":
                                           2 * (n - 1) * plan.n_buckets,
                                           "reduce_scatter": 0,
                                           "all_gather": 0}),
                                 ("xla", {"collective_permute": 0,
                                          "reduce_scatter": plan.n_buckets,
                                          "all_gather": plan.n_buckets})):
                def body(t, k):
                    sq = jax.tree.map(lambda x: x[0], t)
                    out = rps.rps_exchange_plan(sq, k, 0.2, "data",
                                                plan=plan, engine=engine)
                    return jax.tree.map(lambda x: x[None], out)
                f = _shard_map(body, mesh, (specs, P()), specs, {"data"})
                txt = jax.jit(f).lower(tree,
                                       jax.random.PRNGKey(0)).as_text()
                check_hlo.assert_counts(txt, **want)
        print("RING_HLO_OK")
    """) % (SRC, os.path.join(os.path.dirname(__file__), ".."))
    out = _run_sub(code)
    assert "RING_HLO_OK" in out, out


def test_ring_tpu_export_one_fused_dispatch_per_bucket():
    """The tentpole lowering claim, validated from this CPU host through
    the real Mosaic pipeline: ``jax.export`` for platform "tpu" of a
    3-bucket ring round carries exactly 3 ``tpu_custom_call`` fused
    dispatches and ZERO StableHLO collectives (all transport is in-kernel
    RDMA)."""
    n, k = 8, 2
    S = k * n
    buckets = [(128, jnp.float32, jnp.float32),
               (256, jnp.bfloat16, jnp.bfloat16),
               (128, jnp.float32, jnp.bfloat16)]

    def round_fn(*tables):
        pos = jnp.zeros((1,), jnp.int32)
        left = jnp.full((1,), n - 1, jnp.int32)
        right = jnp.ones((1,), jnp.int32)
        outs = []
        for cid, (tbl, (_, _, wire)) in enumerate(zip(tables, buckets)):
            rs_row = jnp.ones((S, 1), wire)
            ag_row = jnp.ones((S, 1), jnp.float32)
            counts = jnp.full((S, 1), n, wire)
            outs.append(rps_ring.ring_bucket_fused(
                tbl, rs_row, ag_row, counts, pos, left, right, n=n, k=k,
                mode="model", rs_dtype=wire, collective_id=cid))
        return outs

    from jax import export
    # the kernel's RDMAs need a manual region: one (S, W) table per device
    # of an n-device ring (an abstract mesh — nothing is placed)
    mesh = jax.sharding.AbstractMesh((n,), ("x",))
    specs = tuple(P("x") for _ in buckets)
    ring = jax.shard_map(round_fn, mesh=mesh, in_specs=specs,
                         out_specs=list(specs), check_vma=False)
    args = [jax.ShapeDtypeStruct((n * S, W), pdt) for (W, pdt, _) in buckets]
    exp = export.export(jax.jit(ring), platforms=("tpu",))(*args)
    txt = exp.mlir_module()
    counts = check_hlo.summarize(txt)
    assert counts["tpu_custom_call"] == len(buckets), counts
    for op in ("reduce_scatter", "all_gather", "collective_permute",
               "all_reduce"):
        assert counts[op] == 0, counts


def test_exchange_table_forwards_raw_pin_to_ring(monkeypatch):
    """Regression: the fused-TPU-kernel gate is ``pin is None`` inside
    rps_ring — _exchange_table must forward the caller's RAW pin (None
    for fully-manual regions), not its normalised identity lambda, or the
    fused dispatch is unreachable from every production path."""
    seen = {}

    def fake_ring(blocks, rs_sc, ag_sc, **kw):
        seen["pin"] = kw.get("pin", "missing")
        return blocks

    monkeypatch.setattr(rps_ring, "ring_exchange_scatter_table", fake_ring)
    n = 4
    rs_m, ag_m = rps.sample_masks(KEY, n, 0.2)
    rps._exchange_table(jnp.zeros((n, 8)), rs_m, ag_m, names=("data",),
                        n=n, i=jnp.int32(0), mode="model", engine="ring")
    assert seen["pin"] is None

    def tp_pin(x):
        return x

    rps._exchange_table(jnp.zeros((n, 8)), rs_m, ag_m, names=("data",),
                        n=n, i=jnp.int32(0), mode="model", engine="ring",
                        pin=tp_pin)
    assert seen["pin"] is tp_pin


def test_auto_engine_is_xla_on_every_backend(monkeypatch):
    """The fused kernel keeps a whole bucket in VMEM, which a real-width
    leaf does not fit: "auto" never picks it, on TPU either."""
    assert rps.resolve_engine("auto") == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rps.resolve_engine("auto") == rps.resolve_engine(None) == "xla"
    assert rps.resolve_engine("ring") == "ring"


def test_ring_with_pin_raises_on_tpu(monkeypatch):
    """A pinned (partial-manual) exchange cannot use the fused kernel: on
    TPU that is an error, not a silent ppermute replay."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 4
    rs_m, ag_m = rps.sample_masks(KEY, n, 0.2)
    with pytest.raises(ValueError, match="fully-manual"):
        rps_ring.ring_exchange_scatter_table(
            jnp.zeros((n, 8)), rs_m, ag_m, names=("data",), n=n,
            i=jnp.int32(0), k=1, mode="model", pin=lambda x: x)


def test_ring_bucket_fused_validates_layout():
    with pytest.raises(ValueError):
        rps_ring.ring_bucket_fused(
            jnp.zeros((7, 128)), jnp.zeros((7, 1)), jnp.zeros((7, 1)),
            jnp.zeros((7, 1)), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            n=4, k=2, mode="model")                       # 7 != k*n
    with pytest.raises(ValueError):
        rps_ring.ring_bucket_fused(
            jnp.zeros((8, 100)), jnp.zeros((8, 1)), jnp.zeros((8, 1)),
            jnp.zeros((8, 1)), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            n=4, k=2, mode="model")                       # W % 128 != 0


def test_logical_ring_ids_multi_axis_mesh():
    """Neighbour logical ids on a ("data", "model") mesh: the ring varies
    the data coord, the model coord stays — computed inside a manual
    region over both axes."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.kernels.rps_ring import logical_ring_ids
        from repro.train.trainer import _shard_map

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                    ("data", "model"))

        def body(x):
            pos, left, right = logical_ring_ids(
                ("data",), mesh_axis_names=mesh.axis_names,
                mesh_shape=dict(mesh.shape))
            return x * 0 + jnp.stack([pos, left, right])   # local (1, 3)

        f = _shard_map(body, mesh, (P(("data", "model")),),
                       P(("data", "model")), {"data", "model"})
        out = np.asarray(jax.jit(f)(jnp.zeros((8, 3), jnp.int32)))
        # device (d, m) has logical id 2d+m; ring neighbours are
        # ((d±1) mod 4, m) -> logical 2((d±1) mod 4)+m
        for d in range(4):
            for m in range(2):
                pos, left, right = out[2 * d + m]
                assert pos == d, (d, m, pos)
                assert left == 2 * ((d - 1) %% 4) + m, (d, m, left)
                assert right == 2 * ((d + 1) %% 4) + m, (d, m, right)
        print("RING_IDS_OK")
    """) % SRC
    out = _run_sub(code)
    assert "RING_IDS_OK" in out, out


# ---- ring_global_sums unit ------------------------------------------------

def test_ring_global_sums_order_and_dtype():
    """Ring-order accumulation in the wire dtype: owner's own contribution
    lands last, every add happens in rs_dtype."""
    n, s, d = 4, 4, 3
    rng = np.random.default_rng(0)
    stack = jnp.asarray(rng.integers(-3, 4, (1, n, s, d)), jnp.float32)
    rs = jnp.ones((1, n, s), jnp.float32)
    own = rps.owners(n, s)
    out = rps_ring.ring_global_sums(stack, rs, own, rs_dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    want = np.asarray(stack).sum(1)                       # exact: integers
    np.testing.assert_array_equal(np.asarray(out, np.float32), want)
    # masked: dropped contributions never accumulate
    rs0 = rs.at[0, 2, :].set(0.0)
    out2 = rps_ring.ring_global_sums(stack, rs0, own)
    want2 = np.einsum("gns,gnsd->gsd", np.asarray(rs0), np.asarray(stack))
    np.testing.assert_allclose(np.asarray(out2), want2, rtol=1e-6)


# ---- donation -------------------------------------------------------------

def _tiny_sim_setup(scfg):
    n = scfg.n_workers
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(n, 8, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    params = {"w": jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32)}
    opt = make_optimizer(scfg.optimizer)
    channel = channels_lib.make_channel(scfg.channel, n, scfg.drop_rate,
                                        s=scfg.n_servers)
    plan = plan_lib.plan_from_config(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                     params),
        n, scfg.n_servers, bucket_mb=scfg.bucket_mb,
        n_buckets=scfg.n_buckets)
    step = sim_lib.make_sim_step(loss_fn, scfg, channel, plan, opt)
    return step, params, opt.init(params), (xs, ys), channel


def test_simulator_step_donates_hot_buffers():
    """The simulator step must reuse the params/opt_state/channel-state
    input buffers: donated at compile level (compiled.donate_argnums,
    alias bytes > 0) and actually consumed at run time (input deleted)."""
    scfg = sim_lib.SimulatorConfig(n_workers=4, drop_rate=0.2,
                                   aggregator="rps_model",
                                   channel="ge:p_bad=0.5,burst=4,p=0.2")
    step, params, opt_state, batch, channel = _tiny_sim_setup(scfg)
    key = jax.random.PRNGKey(0)
    ch_state = channel.init_state(key)
    lr = jnp.float32(0.1)
    compiled = step.lower(params, opt_state, batch, key, lr,
                          ch_state).compile()
    assert len(compiled.donate_argnums) > 0
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes > 0
    w_in = params["w"]
    out = step(params, opt_state, batch, key, lr, ch_state)
    jax.block_until_ready(out)
    assert w_in.is_deleted(), \
        "donated params input must be consumed by the step"

    # the A/B knob: donate=False keeps the seed copying behaviour
    scfg_off = dataclasses.replace(scfg, donate=False)
    step2, params2, opt2, batch2, channel2 = _tiny_sim_setup(scfg_off)
    c2 = step2.lower(params2, opt2, batch2, key, lr,
                     channel2.init_state(key)).compile()
    assert len(c2.donate_argnums) == 0
    w2 = params2["w"]
    out2 = step2(params2, opt2, batch2, key, lr, channel2.init_state(key))
    jax.block_until_ready(out2)
    assert not w2.is_deleted()


def test_simulator_run_bitidentical_with_and_without_donation():
    """Donation is a pure memory optimisation — the training trajectory
    must not move by a single bit."""
    from repro.train.simulator import SimulatorConfig, run_simulation
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(4, 8, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(4, 8, 4)), jnp.float32)

    def init_fn(key):
        return {"w": jax.random.normal(key, (6, 4)) * 0.1}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    outs = []
    for donate in (True, False):
        h = run_simulation(loss_fn, init_fn, lambda t: (xs, ys),
                           SimulatorConfig(n_workers=4, drop_rate=0.3,
                                           aggregator="rps_model",
                                           steps=4, lr=0.1, n_buckets=2,
                                           donate=donate))
        outs.append(np.asarray(h["params"]["w"]))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_trainer_exposes_donation_hint():
    """make_train_setup publishes donate_argnums for jit callers: params +
    opt_state always, the channel-state carry when stateful."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                                  n_layers=2, shard_acts=False)
        model = build_model(cfg, grouped=True)
        _, step, _ = make_train_setup(model, cfg, TrainConfig(
            aggregator="rps_model", drop_rate=0.1), mesh,
            rps_axes=("data",))
        assert step.donate_argnums == (0, 1), step.donate_argnums
        _, step2, _ = make_train_setup(model, cfg, TrainConfig(
            aggregator="rps_model", drop_rate=0.1,
            channel="ge:p_bad=0.5,burst=4,p=0.1"), mesh,
            rps_axes=("data",))
        assert step2.donate_argnums == (0, 1, 5), step2.donate_argnums
        print("DONATE_HINT_OK")
    """) % SRC
    out = _run_sub(code)
    assert "DONATE_HINT_OK" in out, out


# ---- peak-memory regression guard (satellite #1) --------------------------

def test_global_exchange_peak_memory_budget():
    """Regression guard on the compiled global path: temp bytes stay at
    the measured post-fix level (stack + out, ≈2× payload for
    model/renorm; ≈1.1× for grad, whose fallback is a mask multiply).
    A reintroduced materialised f32 copy or fallback buffer pushes the
    ratio past the bound and fails loudly."""
    n = 16
    rng = np.random.default_rng(0)
    tree = {f"p{i}": jnp.asarray(rng.normal(size=(n, 128, 64)),
                                 jnp.float32) for i in range(4)}
    payload = sum(x.size * x.dtype.itemsize for x in tree.values())
    key = jax.random.PRNGKey(0)
    for mode, bound in (("model", 2.25), ("grad_renorm", 2.25),
                        ("grad", 1.35)):
        c = jax.jit(lambda t, k, m=mode: rps.rps_exchange_global(
            t, k, 0.1, n, mode=m)).lower(tree, key).compile()
        temp = c.memory_analysis().temp_size_in_bytes
        assert temp <= bound * payload, \
            (mode, temp / payload, "expected <=", bound)


# ---- simulator engine knobs ----------------------------------------------

def test_simulator_ring_engine_bf16_wire_converges():
    """engine="ring" + exchange_dtype=bfloat16 in the simulator: the
    wire-accurate bf16 replay must train to the same tolerance as the f32
    path (the acceptance's unchanged-convergence claim, CPU-sized)."""
    from repro.train.simulator import SimulatorConfig, run_simulation
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(8, 16, 6)), jnp.float32)
    w_true = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    ys = xs @ w_true

    def init_fn(key):
        return {"w": jax.random.normal(key, (6, 4)) * 0.1}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    runs = {}
    for name, kw in (("f32", {}),
                     ("ring_f32", {"engine": "ring"}),
                     ("ring_bf16", {"engine": "ring",
                                    "exchange_dtype": "bfloat16"})):
        h = run_simulation(loss_fn, init_fn, lambda t: (xs, ys),
                           SimulatorConfig(n_workers=8, drop_rate=0.1,
                                           aggregator="rps_model",
                                           steps=60, lr=0.2, warmup=5,
                                           n_buckets=2, **kw))
        runs[name] = h["final_loss"]
    assert runs["f32"] < 0.05, runs
    # ring f32 replay: same math to accumulation order
    assert abs(runs["ring_f32"] - runs["f32"]) < 1e-4, runs
    # bf16 wire: converges to the same tolerance class
    assert runs["ring_bf16"] < 0.05, runs
