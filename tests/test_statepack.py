"""Quantized trainer state (DESIGN.md §16): the shared quant core is
op-for-op the wire codec's grid (bit-identity), the f32 StatePack is a
literal identity (packed optimizers ≡ the pre-§16 formulas bitwise, sgd
invariant under every pack), SR keeps the packed EMA unbiased where RNE
stalls, packed state donates and checkpoints bitwise, the dryrun-side
state-bytes breakdown works on AOT shapes and shows the ≥2x Adam
reduction, and the §16 host-perf launcher (launch/env.py) + the
--compute-ms=auto measured-readiness path behave.
"""
import dataclasses
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_state, save_state
from repro.core import plan as plan_lib
from repro.core import quant as quant_lib
from repro.core import wire as wire_lib
from repro.launch import env as env_lib
from repro.optim import make_optimizer
from repro.optim import statepack as statepack_lib
from repro.optim.statepack import (I8_LEVELS, canon_pack, is_packed_i8,
                                   make_state_pack, pack_tree,
                                   state_bytes_breakdown, tree_bytes,
                                   unpack_tree)
from repro.train.simulator import (SimulatorConfig, make_sim_step,
                                   measure_bucket_ready_ms, run_simulation,
                                   wants_measured_ready)

KEY = jax.random.PRNGKey(21)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


def _mlp_task(n=4, seed=0):
    """Two-leaf model so the plan has two buckets to time."""
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(n, 16, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(n, 16, 4)), jnp.float32)

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (6, 8)) * 0.3,
                "w2": jax.random.normal(k2, (8, 4)) * 0.3}

    def loss_fn(p, b):
        x, y = b
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    return loss_fn, init_fn, lambda t: (xs, ys)


# ---- the shared quant core is the wire codec's grid -----------------------

def test_quant_core_matches_wire_codec_bitwise():
    """One quantization library, two consumers: quant.quantize at the
    codec's level count reproduces WireCodec.encode bit-for-bit, RNE and
    SR alike, and fake_quant composes the same ops."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 64)) * 3.0, jnp.float32)
    c = wire_lib.make_codec("int8")
    for key in (None, KEY):
        qw, dw = c.encode(x, key=key)
        qq, dq = quant_lib.quantize(x, I8_LEVELS, jnp.int8, key=key,
                                    lead=0)
        np.testing.assert_array_equal(np.asarray(qw), np.asarray(qq))
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(dq))
        np.testing.assert_array_equal(
            np.asarray(c.fake_quant(x, key=key)),
            np.asarray(quant_lib.fake_quant(x, I8_LEVELS, jnp.int8,
                                            key=key, lead=0)))
    np.testing.assert_array_equal(
        np.asarray(c.decode(qw, dw)),
        np.asarray(quant_lib.dequantize(qw, dw)))


def test_row_lead_and_block_delta_shapes():
    assert quant_lib.row_lead(1) == -1
    assert quant_lib.row_lead(2) == 0
    assert quant_lib.row_lead(3) == 1
    x3 = jnp.ones((4, 6, 8))
    d3 = quant_lib.block_delta(x3, I8_LEVELS, lead=quant_lib.row_lead(3))
    assert d3.shape == (4, 6, 1)
    x1 = jnp.ones((8,))
    d1 = quant_lib.block_delta(x1, I8_LEVELS, lead=quant_lib.row_lead(1))
    assert d1.shape == (1,)
    # zero blocks get a guard delta, and quantize maps them to exact zero
    z = jnp.zeros((2, 8))
    q, d = quant_lib.quantize(z, I8_LEVELS, jnp.int8)
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(d) > 0)


# ---- StatePack registry and round-trips -----------------------------------

def test_state_pack_registry_and_aliases():
    assert canon_pack(None) == "f32" == canon_pack("none") \
        == canon_pack("float32") == canon_pack("F32")
    assert canon_pack("int8") == "i8" and canon_pack("bfloat16") == "bf16"
    pk = make_state_pack("i8")
    assert (pk.m_format, pk.v_format, pk.ef_format) == ("bf16", "i8", "i8")
    assert not pk.is_identity and make_state_pack().is_identity
    assert "i8" in pk.describe()
    with pytest.raises(ValueError, match="unknown state pack"):
        canon_pack("fp4")


def test_pack_tree_f32_is_a_literal_identity():
    """The bit-identity contract: the same tree object passes through —
    no cast, no copy, nothing for XLA to even see."""
    t = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((4,))}
    assert pack_tree(t, "f32") is t
    assert unpack_tree(t, "f32") is t


def test_pack_tree_bf16_and_i8_roundtrip():
    rng = np.random.default_rng(7)
    t = {"a": jnp.asarray(rng.normal(size=(4, 32)) * 2.0, jnp.float32),
         "b": jnp.asarray(rng.normal(size=(3, 5, 16)), jnp.float32)}
    pb = pack_tree(t, "bf16")
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(pb))
    ub = unpack_tree(pb, "bf16")
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(ub)):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(b))
    pi = pack_tree(t, "i8", key=KEY)
    assert is_packed_i8(pi) and not is_packed_i8(t)
    assert jax.tree.structure(pi["q"]) == jax.tree.structure(t)
    assert pi["q"]["a"].dtype == jnp.int8
    assert pi["scale"]["a"].shape == (4, 1)        # per-row, keepdims
    assert pi["scale"]["b"].shape == (3, 5, 1)
    ui = unpack_tree(pi, "i8")
    # SR error is bounded by one grid step per element
    for name in t:
        err = np.abs(np.asarray(ui[name]) - np.asarray(t[name]))
        step = np.broadcast_to(np.asarray(pi["scale"][name]),
                               t[name].shape)
        assert np.all(err <= step + 1e-7)
    # zeros pack exactly: the packed EF start is still the zero residual
    z = {"a": jnp.zeros((4, 32)), "b": jnp.zeros((3, 5, 16))}
    uz = unpack_tree(pack_tree(z, "i8", key=KEY), "i8")
    assert all(np.all(np.asarray(x) == 0.0) for x in jax.tree.leaves(uz))


# ---- f32-pack bit-identity of the packed optimizers -----------------------

def test_packed_optimizers_f32_bit_identical_to_formulas():
    """The packed decode->update->encode path under the f32 identity pack
    reproduces the textbook update bit-for-bit, key threaded or not."""
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(6, 4)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.normal(size=(6, 4)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    lr = jnp.float32(0.07)

    # momentum
    opt = make_optimizer("momentum", state_pack="f32")
    st = opt.init(params)
    p, st = opt.update(grads, st, params, lr, key=KEY)
    p, st = opt.update(grads, st, p, lr)          # key optional
    m_ref = jax.tree.map(jnp.zeros_like, params)
    p_ref = params
    for _ in range(2):
        m_ref = jax.tree.map(lambda m, g: 0.9 * m + g, m_ref, grads)
        p_ref = jax.tree.map(lambda q, m: q - lr * m, p_ref, m_ref)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(m_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # adam
    b1, b2, eps = 0.9, 0.999, 1e-8
    opt = make_optimizer("adam", state_pack="f32")
    st = opt.init(params)
    p = params
    m_ref = jax.tree.map(jnp.zeros_like, params)
    v_ref = jax.tree.map(jnp.zeros_like, params)
    p_ref = params
    for t in (1, 2, 3):
        p, st = opt.update(grads, st, p, lr, key=jax.random.fold_in(KEY, t))
        m_ref = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                             m_ref, grads)
        v_ref = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g), v_ref, grads)
        bc1 = 1 - b1 ** jnp.float32(t)
        bc2 = 1 - b2 ** jnp.float32(t)
        p_ref = jax.tree.map(
            lambda q, m, v: q - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
            p_ref, m_ref, v_ref)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(st["m"]), jax.tree.leaves(m_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(st["t"]) == 3


def test_adam_init_distinct_buffers_under_identity_pack():
    """The f32 pack is an identity, so m and v must come from two distinct
    zero trees — shared buffers would double-donate in the jitted step."""
    params = {"w": jnp.ones((3, 4))}
    st = make_optimizer("adam").init(params)
    assert st["m"]["w"] is not st["v"]["w"]


def test_sgd_invariant_under_every_pack():
    """sgd carries no state: packing must not perturb a single bit of the
    training trajectory, whatever the pack."""
    loss_fn, init_fn, batch_fn = _lin_task()
    base = dict(n_workers=8, drop_rate=0.2, steps=8, lr=0.2, warmup=2,
                aggregator="rps_model", wire="int8", recovery="renorm",
                eval_every=4)
    runs = {pk: run_simulation(loss_fn, init_fn, batch_fn,
                               SimulatorConfig(**base, state_pack=pk))
            for pk in ("f32", "bf16", "i8")}
    for pk in ("bf16", "i8"):
        np.testing.assert_array_equal(
            np.asarray(runs["f32"]["params"]["w"]),
            np.asarray(runs[pk]["params"]["w"]))


def test_simulator_f32_pack_alias_parity_matrix():
    """Every f32 spelling (default, "none", "float32") is the same run,
    bitwise, across stateful-optimizer x EF configurations."""
    loss_fn, init_fn, batch_fn = _lin_task(n=4, seed=1)
    for opt_name, wire in (("momentum", "f32"), ("adam", "int8")):
        base = dict(n_workers=4, drop_rate=0.25, steps=6, lr=0.1,
                    warmup=2, aggregator="rps_model", optimizer=opt_name,
                    wire=wire, recovery="ef", n_buckets=2, eval_every=3)
        ref = run_simulation(loss_fn, init_fn, batch_fn,
                             SimulatorConfig(**base))
        for spell in ("f32", "none", "float32"):
            h = run_simulation(loss_fn, init_fn, batch_fn,
                               SimulatorConfig(**base, state_pack=spell))
            np.testing.assert_array_equal(np.asarray(ref["params"]["w"]),
                                          np.asarray(h["params"]["w"]))
            for a, b in zip(jax.tree.leaves(ref["state"]["opt_state"]),
                            jax.tree.leaves(h["state"]["opt_state"])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


# ---- SR keeps the packed EMA unbiased where RNE stalls --------------------

def test_sr_packed_ema_unbiased_where_rne_stalls():
    """An EMA increment below half the int8 grid step vanishes under
    round-to-nearest (the packed EMA stalls); stochastic rounding keeps
    the expected packed value on the true EMA — the §16 property the
    Adam second moments rely on."""
    step = 2.0 / I8_LEVELS                        # grid set by the row max
    # row: a pinned max element (2.0, always on-grid) + interior elements
    # sitting exactly on grid points, so pack(m) == m under RNE
    m = jnp.concatenate([jnp.full((1, 1), 2.0),
                         jnp.full((1, 7), 64 * step)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(unpack_tree(pack_tree(m, "i8"), "i8")), np.asarray(m))
    inc = 1e-3                                    # << step/2 ~ 7.9e-3
    bump = jnp.concatenate([jnp.zeros((1, 1)),
                            jnp.full((1, 7), inc)], axis=1)
    target = m + bump
    # RNE: the sub-half-step write is absorbed — the packed EMA stalls
    rne = unpack_tree(pack_tree(target, "i8"), "i8")
    np.testing.assert_array_equal(np.asarray(rne), np.asarray(m))

    @jax.jit
    def draw(key):
        return unpack_tree(pack_tree(target, "i8", key=key), "i8")

    keys = jax.random.split(jax.random.PRNGKey(11), 4096)
    draws = np.asarray(jax.vmap(draw)(keys))      # (4096, 1, 8)
    mean = draws.mean(axis=0)
    # MC std of the mean: step*sqrt(p(1-p))/sqrt(K) ~ 6e-5; 5 sigma
    np.testing.assert_allclose(mean, np.asarray(target), atol=3e-4)
    assert np.abs(mean - np.asarray(m))[0, 1:].min() > 5e-4, \
        "SR mean must move off the stalled RNE value"


# ---- bytes accounting (the dryrun report's state_bytes) -------------------

def test_state_bytes_breakdown_adam_i8_at_least_2x():
    """The headline §16 claim, on AOT shapes exactly as the dryrun
    computes it: packed Adam state (m bf16, v int8 + f32 row scales)
    is >= 2x smaller than unpacked f32 m/v."""
    params = {"emb": jax.ShapeDtypeStruct((512, 256), jnp.float32),
              "mlp": jax.ShapeDtypeStruct((4, 256, 512), jnp.float32)}
    shapes = {}
    for pk in ("f32", "i8"):
        opt = make_optimizer("adam", state_pack=pk)
        st = jax.eval_shape(opt.init, params)
        shapes[pk] = state_bytes_breakdown(params=params, opt_state=st)
    f32, i8 = shapes["f32"], shapes["i8"]
    pbytes = tree_bytes(params)
    assert f32["params"] == i8["params"] == pbytes
    opt_f32 = f32["opt_m"] + f32["opt_v"] + f32["opt_t"]
    opt_i8 = (i8["opt_m"] + i8["opt_v"] + i8["opt_v_scales"]
              + i8["opt_t"])
    assert opt_f32 == 2 * pbytes + 4
    assert opt_f32 >= 2 * opt_i8, (opt_f32, opt_i8)
    assert i8["opt_m"] == pbytes // 2             # bf16 momentum
    assert i8["opt_v"] == pbytes // 4             # int8 payload
    assert 0 < i8["opt_v_scales"] < i8["opt_v"]   # per-row f32 scales
    assert i8["total"] == sum(v for k, v in i8.items() if k != "total")


def test_state_bytes_breakdown_ef_and_plain_trees():
    ef = {"w": jnp.zeros((8, 16))}
    out = state_bytes_breakdown(ef_state=pack_tree(ef, "i8"))
    assert out["ef"] == 8 * 16 and out["ef_scales"] == 8 * 4
    out = state_bytes_breakdown(ef_state=ef)
    assert out["ef"] == 8 * 16 * 4
    # momentum's bare packed tree (no adam bundle)
    st = make_optimizer("momentum", state_pack="i8").init(ef)
    out = state_bytes_breakdown(opt_state=st)
    assert out["opt_m"] == 8 * 16 * 2             # bf16


def test_simulator_history_reports_state_bytes():
    loss_fn, init_fn, batch_fn = _lin_task(n=4)
    h = run_simulation(loss_fn, init_fn, batch_fn, SimulatorConfig(
        n_workers=4, drop_rate=0.2, steps=3, lr=0.1,
        aggregator="rps_model", optimizer="adam", state_pack="i8",
        wire="int8", recovery="ef", n_buckets=2))
    sb = h["state_bytes"]
    assert sb["opt_m"] > 0 and sb["opt_v_scales"] > 0 and sb["ef"] > 0
    assert sb["total"] == sum(v for k, v in sb.items() if k != "total")
    # and the carried state really is packed at rest
    assert h["state"]["opt_state"]["m"]["w"].dtype == jnp.bfloat16
    assert h["state"]["opt_state"]["v"]["q"]["w"].dtype == jnp.int8
    assert h["ef_state"]["q"]["w"].dtype == jnp.int8


# ---- donation survives packing --------------------------------------------

def test_sim_donation_intact_with_i8_pack():
    """Packed buffers are what gets donated: with adam+i8+EF the packed
    opt state and packed residual are consumed in place."""
    from repro import channels as channels_lib
    scfg = SimulatorConfig(n_workers=4, drop_rate=0.2,
                           aggregator="rps_model", wire="int8",
                           recovery="ef", n_buckets=2, optimizer="adam",
                           state_pack="i8",
                           channel="ge:p_bad=0.5,burst=4,p=0.2")
    n = scfg.n_workers
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(n, 8, 6)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(n, 8, 4)), jnp.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    params = {"w": jnp.asarray(rng.normal(size=(n, 6, 4)), jnp.float32)}
    opt = make_optimizer(scfg.optimizer, state_pack=scfg.state_pack)
    channel = channels_lib.make_channel(scfg.channel, n, scfg.drop_rate)
    plan = plan_lib.plan_from_config(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                     params), n, n_buckets=2, wire="int8", recovery="ef")
    step = make_sim_step(loss_fn, scfg, channel, plan, opt)
    key = jax.random.PRNGKey(0)
    opt_state = opt.init(params)
    ef0 = pack_tree(jax.tree.map(jnp.zeros_like, params), "i8")
    compiled = step.lower(params, opt_state, (xs, ys), key,
                          jnp.float32(0.1), channel.init_state(key),
                          ef0).compile()
    # compiled reports donation in flattened-arg space: every leaf of
    # params + packed opt state + channel state + packed EF is donated
    n_donated = (len(jax.tree.leaves(params))
                 + len(jax.tree.leaves(opt_state))
                 + len(jax.tree.leaves(channel.init_state(key)))
                 + len(jax.tree.leaves(ef0)))
    assert len(compiled.donate_argnums) == n_donated, \
        (compiled.donate_argnums, n_donated)
    m_in = opt_state["m"]["w"]
    v_in, ef_in = opt_state["v"]["q"]["w"], ef0["q"]["w"]
    outs = step(params, opt_state, (xs, ys), key, jnp.float32(0.1),
                channel.init_state(key), ef0)
    jax.block_until_ready(outs)
    assert m_in.is_deleted(), "donated bf16 momentum must be consumed"
    assert v_in.is_deleted(), "donated packed opt state must be consumed"
    assert ef_in.is_deleted(), "donated packed EF residual must be consumed"


# ---- bitwise checkpoint round-trip of packed state ------------------------

def test_checkpoint_roundtrip_packed_state_bitwise():
    """Mid-run save -> restore -> continue under adam+i8+EF: the packed
    bundle (bf16 m via the tagged-uint16 npz path, int8 payloads, f32
    scales) round-trips bitwise and the resumed run matches the
    uninterrupted one."""
    loss_fn, init_fn, batch_fn = _lin_task(seed=3)
    scfg = SimulatorConfig(n_workers=8, drop_rate=0.25,
                           aggregator="rps_model", steps=9, lr=0.2,
                           wire="int8", recovery="ef", n_buckets=2,
                           optimizer="adam", state_pack="i8",
                           channel="ge:p_bad=0.6,burst=3,p=0.25",
                           donate=False)
    full = run_simulation(loss_fn, init_fn, batch_fn, scfg)
    half = run_simulation(loss_fn, init_fn, batch_fn,
                          dataclasses.replace(scfg, steps=5))
    assert half["state"]["opt_state"]["m"]["w"].dtype == jnp.bfloat16
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "mid.npz")
        save_state(path, **half["state"])
        restored = load_state(path, **half["state"])
        for name in half["state"]:
            for a, b in zip(jax.tree.leaves(half["state"][name]),
                            jax.tree.leaves(restored[name])):
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        resumed = run_simulation(loss_fn, init_fn, batch_fn, scfg,
                                 state=restored, start_step=5)
    np.testing.assert_array_equal(np.asarray(full["params"]["w"]),
                                  np.asarray(resumed["params"]["w"]))
    for name in ("opt_state", "ef_state"):
        for a, b in zip(jax.tree.leaves(full["state"][name]),
                        jax.tree.leaves(resumed["state"][name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- telemetry quant-error counters ---------------------------------------

def test_telemetry_quant_error_counters():
    """With a collector installed, every packed write reports its
    quantization-error norm; the f32 identity pack adds no counters (and
    no ops) at all."""
    loss_fn, init_fn, batch_fn = _lin_task(n=4)
    base = dict(n_workers=4, drop_rate=0.2, steps=3, lr=0.1,
                aggregator="rps_model", optimizer="adam", wire="int8",
                recovery="ef", n_buckets=2, telemetry=True)
    h8 = run_simulation(loss_fn, init_fn, batch_fn,
                        SimulatorConfig(**base, state_pack="i8"))
    rec = h8.records[0]
    for k in ("quant_err_opt_m", "quant_err_opt_v", "quant_err_ef"):
        assert k in rec and np.isfinite(rec[k]), (k, rec.keys())
    assert rec["quant_err_opt_v"] >= 0.0
    h32 = run_simulation(loss_fn, init_fn, batch_fn,
                         SimulatorConfig(**base, state_pack="f32"))
    assert not any(k.startswith("quant_err_opt") for k in h32.records[0])


# ---- launcher hygiene: launch/env.py --------------------------------------

def test_env_merge_xla_flag_replaces_and_appends():
    out = env_lib.merge_xla_flag("", "--a=1")
    assert out == "--a=1"
    out = env_lib.merge_xla_flag("--a=1 --b=2", "--a=9")
    assert out.split() == ["--b=2", "--a=9"]       # replaced, not stacked
    # idempotent
    assert env_lib.merge_xla_flag(out, "--a=9") == out


def test_env_workers_from_argv():
    assert env_lib.workers_from_argv(
        ["python", "-m", "x", "--workers", "12"]) == 12
    assert env_lib.workers_from_argv(["x", "--workers=7"]) == 7
    assert env_lib.workers_from_argv(["x", "--workers", "lots"]) is None
    assert env_lib.workers_from_argv(["x", "--steps", "3"]) is None


def test_env_host_env_pure_and_validating():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2 "
                         "--other=keep"}
    env = env_lib.host_env(workers=8, tcmalloc=False, base=base)
    flags = env["XLA_FLAGS"].split()
    assert "--xla_force_host_platform_device_count=8" in flags
    assert "--other=keep" in flags                 # merged, not clobbered
    assert flags.count("--xla_force_host_platform_device_count=8") == 1
    assert env_lib.STEP_MARKER_FLAG in flags
    assert "LD_PRELOAD" not in env                 # tcmalloc off
    # explicit devices beats workers
    env = env_lib.host_env(workers=4, devices=16, tcmalloc=False, base={})
    assert "--xla_force_host_platform_device_count=16" in env["XLA_FLAGS"]
    with pytest.raises(ValueError):
        env_lib.host_env(workers=0, tcmalloc=False, base={})
    assert base["XLA_FLAGS"].startswith("--xla_force")   # input untouched


def test_env_apply_sizes_host_devices_subprocess():
    """env.apply() before the first jax import forces the device count —
    the in-process leg of run.sh's preamble."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from repro.launch import env as env_lib\n"
        "set_ = env_lib.apply(workers=6)\n"
        "assert 'XLA_FLAGS' in set_ and 'LD_PRELOAD' not in set_\n"
        "import jax\n"
        "assert jax.device_count() == 6, jax.device_count()\n"
        "print('ENV_APPLY_OK')\n" % SRC)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ENV_APPLY_OK" in r.stdout


def test_env_cli_emits_eval_able_preamble():
    """`python -m repro.launch.env -- cmd --workers N` prints export
    lines run.sh can eval."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.env", "--no-tcmalloc", "--",
         "python", "-m", "repro.launch.train", "--workers", "5"],
        capture_output=True, text=True,
        env={**env, "PYTHONPATH": SRC}, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "export XLA_FLAGS=" in r.stdout
    assert "--xla_force_host_platform_device_count=5" in r.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_env_use_compile_cache(monkeypatch, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset,
    the cache sits at the fixed <checkout>/.jax_cache (the path is part
    of the cache key: a moving directory never hits)."""
    old = jax.config.jax_compilation_cache_dir
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
            assert env_lib.use_compile_cache() == "cache-from-env"
            assert jax.config.jax_compilation_cache_dir == old
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = env_lib.use_compile_cache()
            assert path == os.path.join(checkout, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert env_lib.use_compile_cache() == path     # fixed, stable
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# ---- --compute-ms=auto: measured bucket readiness -------------------------

def test_with_ready_ms_validation():
    tree = {"a": jnp.zeros((24,)), "b": jnp.zeros((8, 2))}
    sync = plan_lib.make_plan(tree, 4, n_buckets=2)
    with pytest.raises(ValueError, match="async"):
        sync.with_ready_ms([1.0, 2.0])
    p = plan_lib.make_plan(tree, 4, n_buckets=2, schedule="async",
                           compute_ms=4.0)
    with pytest.raises(ValueError, match="readiness times"):
        p.with_ready_ms([1.0])
    with pytest.raises(ValueError, match="negative"):
        p.with_ready_ms([1.0, -2.0])
    p2 = p.with_ready_ms([3.5, 1.25])
    assert p2.ready_ms == (3.5, 1.25)
    assert p.ready_ms != p2.ready_ms               # replace, not mutate


def test_wants_measured_ready_gating():
    base = dict(n_workers=4, aggregator="rps_model", n_buckets=2)
    assert wants_measured_ready(SimulatorConfig(
        **base, schedule="async", compute_ms="auto"))
    assert not wants_measured_ready(SimulatorConfig(
        **base, schedule="async", compute_ms=5.0))
    assert not wants_measured_ready(SimulatorConfig(
        **base, compute_ms="auto"))                # sync: nothing to time


def test_measure_bucket_ready_ms_monotone():
    loss_fn, init_fn, batch_fn = _mlp_task()
    n = 4
    p1 = init_fn(jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), p1)
    plan = plan_lib.plan_from_config(p1, n, n_buckets=2, schedule="async",
                                     compute_ms=1.0)
    ready = measure_bucket_ready_ms(loss_fn, params, batch_fn(0), plan,
                                    reps=1)
    assert len(ready) == plan.n_buckets
    assert all(r > 0 for r in ready)
    # suffix b contains suffix b+1: readiness non-increasing in plan order
    assert all(a >= b for a, b in zip(ready, ready[1:]))
    assert plan.with_ready_ms(ready).ready_ms == tuple(ready)


def test_simulator_compute_ms_auto_end_to_end():
    """compute_ms='auto' measures the real backward, feeds the plan, and
    the async run completes with the staleness axis populated."""
    loss_fn, init_fn, batch_fn = _mlp_task()
    h = run_simulation(loss_fn, init_fn, batch_fn, SimulatorConfig(
        n_workers=4, aggregator="rps_model", steps=3, eval_every=1,
        lr=0.1, n_buckets=2, schedule="async", compute_ms="auto",
        channel="deadline:deadline_ms=10,base_ms=1,jitter_ms=3,"
                "straggler_frac=0.3,straggler_mult=4"))
    assert len(h["staleness"]) == 3
    assert np.isfinite(h["final_loss"])


# ---- launch CLI -----------------------------------------------------------

def test_launch_train_cli_state_pack_flag():
    """--state-pack/--optimizer reach the simulator; the state-bytes
    report line shows up for packed runs."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "rps-paper-mlp", "--reduced", "--workers", "4", "--steps", "3",
         "--batch-size", "4", "--seq-len", "16", "--drop-rate", "0.2",
         "--buckets", "2", "--wire", "int8", "--recovery", "ef",
         "--optimizer", "adam", "--state-pack", "int8"],
        capture_output=True, text=True, env=env, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "state bytes [int8]" in r.stdout, r.stdout
    assert "opt_v_scales=" in r.stdout, r.stdout
