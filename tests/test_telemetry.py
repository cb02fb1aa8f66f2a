"""Exchange telemetry (DESIGN.md §14): bit-identity of instrumented runs,
per-link estimator convergence against every channel family, the drift
monitor, Chrome-trace schema validity, and the tap/timer utilities."""
import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import telemetry as telemetry_lib
from repro.channels import make_channel
from repro.core import rps as rps_lib
from repro.data.synthetic import TeacherTask, make_worker_streams
from repro.telemetry import counters, taps
from repro.telemetry.estimator import LinkRateEstimator
from repro.telemetry.timing import time_fn, wallclock
from repro.telemetry.trace import TraceBuffer, validate_chrome_trace
from repro.train.simulator import SimulatorConfig, run_simulation

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _problem(n):
    task = TeacherTask(d_in=24, n_classes=8, hetero=0.3, seed=0)

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (24, 48)) * 0.1,
                "w2": jax.random.normal(k2, (48, 8)) * 0.1}

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"])
        logits = h @ p["w2"]
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
        return jnp.mean(logz - gold)

    return loss_fn, init_fn, make_worker_streams(task, n, 16)


# ---------------------------------------------------------------------------
# bit-identity: telemetry must be observationally free
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["xla", "ring"])
def test_simulator_telemetry_bit_identical(engine):
    loss_fn, init_fn, batch_fn = _problem(4)
    base = dict(n_workers=4, drop_rate=0.2, aggregator="rps_model",
                lr=0.2, warmup=2, steps=12, n_buckets=2, engine=engine)
    h0 = run_simulation(loss_fn, init_fn, batch_fn,
                        SimulatorConfig(**base))
    h1 = run_simulation(loss_fn, init_fn, batch_fn,
                        SimulatorConfig(telemetry=True, **base))
    assert h0["loss"] == h1["loss"]
    for a, b in zip(jax.tree.leaves(h0["params"]),
                    jax.tree.leaves(h1["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "telemetry changed the trained parameters"
    assert len(h1.records) == base["steps"]
    assert {"rs_link_delivered", "ag_link_delivered", "link_offered",
            "loss", "grad_norm"} <= set(h1.records[0])


def test_simulator_telemetry_counts_match_configured_p():
    # sanity on the magnitudes: realized drop rate near the configured p
    loss_fn, init_fn, batch_fn = _problem(8)
    h = run_simulation(loss_fn, init_fn, batch_fn,
                       SimulatorConfig(n_workers=8, drop_rate=0.3,
                                       aggregator="rps_model", lr=0.2,
                                       warmup=2, steps=60, telemetry=True))
    rates = [r["rs_drop_rate"] for r in h.records]
    assert abs(np.mean(rates) - 0.3) < 0.05, np.mean(rates)
    offered = np.asarray(h.records[0]["link_offered"])
    assert offered.shape == (8,) and (offered == 7).all()


# ---------------------------------------------------------------------------
# per-link estimator convergence, every channel family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,slack", [
    ("bernoulli:p=0.3", 0.02),
    ("ge:p_bad=0.6,burst=8", 0.08),      # burst autocorrelation → wide band
    ("hetero:n_pods=2,p_cross=0.4", 0.03),
])
def test_per_link_estimate_converges(spec, slack):
    n = 8
    channel = make_channel(spec, n, 0.1)
    loss_fn, init_fn, batch_fn = _problem(n)
    reg = telemetry_lib.Telemetry()
    run_simulation(loss_fn, init_fn, batch_fn,
                   SimulatorConfig(n_workers=n, aggregator="rps_model",
                                   lr=0.2, warmup=2, steps=300,
                                   channel=channel),
                   telemetry=reg)
    expected = channel.expected_link_p()
    rep = reg.rs_est.drift(expected, z=4.0, slack=slack)
    assert not rep["any_drift"], rep
    assert rep["max_abs_dev"] < 4 * rep["stderr"][0] + slack, rep
    # the estimator really resolves per-link structure, not just the mean
    assert reg.rs_est.packets.sum() >= 300 * (n - 1) * n * 0.9


def test_drift_monitor_fires_on_mismatch():
    n = 4
    rng = np.random.default_rng(0)
    est = LinkRateEstimator(n)
    offered = np.full(n, 3)
    for _ in range(500):
        est.update(rng.binomial(3, 0.7, size=n), offered)   # true p = 0.3
    ok = est.drift(np.full(n, 0.3))
    bad = est.drift(np.full(n, 0.15))
    assert not ok["any_drift"], ok
    assert bad["any_drift"] and all(bad["drifted"]), bad


def test_estimator_math():
    est = LinkRateEstimator(2)
    est.update([2, 4], [4, 4])          # drop x = [0.5, 0.0]
    est.update([4, 2], [4, 4])          # drop x = [0.0, 0.5]
    assert np.allclose(est.est, [0.25, 0.25])
    assert np.array_equal(est.packets, [8, 8])
    # EWMA: first update seeds, later ones decay geometrically
    ew = LinkRateEstimator(1, alpha=0.5)
    ew.update([0], [2])                 # x = 1.0 → est 1.0
    ew.update([2], [2])                 # x = 0.0 → est 0.5
    assert np.allclose(ew.est, [0.5])
    assert ew.ess()[0] == pytest.approx(2 * (2 - 0.5) / 0.5)
    with pytest.raises(ValueError):
        LinkRateEstimator(2, alpha=1.5)
    with pytest.raises(ValueError):
        est.update([1, 2, 3], [3, 3, 3])


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_link_counters_exclude_owner():
    n = s = 4
    full = jnp.ones((n, s), bool)
    assert np.array_equal(np.asarray(counters.link_delivered(full)),
                          [3, 3, 3, 3])
    assert np.array_equal(counters.link_offered(n, s), [3, 3, 3, 3])
    # owner-only delivery = zero wire events
    own = jnp.asarray(counters._np_owner_mask(n, s))
    assert np.asarray(counters.link_delivered(own)).sum() == 0
    # per-bucket masks sum over the bucket dim
    per_bucket = jnp.stack([full, own])
    assert np.array_equal(np.asarray(counters.link_delivered(per_bucket)),
                          [3, 3, 3, 3])
    assert np.array_equal(counters.link_offered(n, s, n_buckets=2),
                          [6, 6, 6, 6])


def test_mask_step_stats_drop_rate():
    n = s = 4
    rs = jnp.asarray(counters._np_owner_mask(n, s))   # all wire drops
    ag = jnp.ones((n, s), bool)                       # no drops
    stats = counters.mask_step_stats(rs, ag)
    assert float(stats["rs_drop_rate"]) == pytest.approx(1.0)
    assert float(stats["ag_drop_rate"]) == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# taps
# ---------------------------------------------------------------------------

def test_taps_noop_without_collector():
    assert taps.active() is None
    taps.emit("x", jnp.ones(3))          # must not raise, must not record
    with taps.tap_collector() as t:
        assert taps.active() is t
        taps.emit("x", jnp.ones(3))
        taps.emit("x", jnp.zeros(3))     # repeat → list
        taps.annotate("meta", {"k": 1})
    assert taps.active() is None
    tree = t.tree()
    assert isinstance(tree["x"], list) and len(tree["x"]) == 2
    assert t.meta["meta"] == {"k": 1}


def test_exchange_taps_emit_counters():
    tree = {"w": jnp.ones((4, 8, 8))}
    key = jax.random.PRNGKey(0)
    with taps.tap_collector() as t:
        rps_lib.rps_exchange_global(tree, key, 0.3, 4, mode="model")
    got = t.tree()
    assert "rs_link_delivered" in got and "ag_link_delivered" in got
    assert np.asarray(got["rs_link_delivered"]).shape == (4,)
    assert t.meta["exchange"]["n"] == 4


# ---------------------------------------------------------------------------
# chrome trace
# ---------------------------------------------------------------------------

def test_trace_buffer_emits_valid_chrome_trace(tmp_path):
    tb = TraceBuffer()
    with tb.span("phase.outer", detail="x"):
        with tb.span("phase.inner"):
            pass
    tb.instant("marker")
    tb.counter("packets", {"value": 7})
    obj = tb.to_chrome()
    assert validate_chrome_trace(obj) == []
    path = tmp_path / "trace.json"
    tb.write(str(path))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    names = [e["name"] for e in obj["traceEvents"]]
    assert {"phase.outer", "phase.inner", "marker"} <= set(names)


def test_trace_buffer_span_reaches_profiler_on_its_clock(tmp_path):
    """A span's args (those given at entry and those set at its end) reach
    the profiler's annotation as numbers, and its ``ts`` is wall-clock µs
    since the epoch, the clock of the profiler's host events."""
    from jax.profiler import ProfileData
    tb = TraceBuffer()
    t_wall = time.time_ns() / 1e3
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tb.span("phase.args", rid=7, wait_ms=1.5, program="jit_f") as sp:
            sp.set_metadata(finished=2)
    finally:
        jax.profiler.stop_trace()
    ev = tb.events[-1]
    assert ev["args"] == {"rid": 7, "wait_ms": 1.5, "program": "jit_f",
                          "finished": 2}
    assert abs(ev["ts"] - t_wall) < 60e6           # epoch µs, not run µs
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    start, = [dict(pl.stats)["profile_start_time"] for pl in pd.planes
              if pl.name == "Task Environment"]
    hits = [e for pl in pd.planes if pl.name == "/host:CPU"
            for ln in pl.lines for e in ln.events if e.name == "phase.args"]
    assert len(hits) == 1
    assert dict(hits[0].stats) == ev["args"]
    assert abs((start + hits[0].start_ns) / 1e3 - ev["ts"]) < 1e3   # 1 ms


def test_trace_validator_rejects_malformed():
    assert validate_chrome_trace({"no_events": []})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})  # no name
    assert validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "ts": "soon"}]})
    assert validate_chrome_trace([{"name": "a", "ph": "X", "ts": 0.0,
                                   "dur": 1.0, "pid": 1, "tid": 1}]) == []


def test_trace_validate_cli(tmp_path):
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    tb = TraceBuffer()
    with tb.span("s"):
        pass
    tb.write(str(good))
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    env = dict(os.environ, PYTHONPATH=SRC)
    ok = subprocess.run([sys.executable, "-m", "repro.telemetry.trace",
                         "--validate", str(good)], env=env,
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    ko = subprocess.run([sys.executable, "-m", "repro.telemetry.trace",
                         "--validate", str(bad)], env=env,
                        capture_output=True, text=True)
    assert ko.returncode == 1, ko.stdout + ko.stderr


# ---------------------------------------------------------------------------
# registry + artifacts + renderer
# ---------------------------------------------------------------------------

def test_registry_writes_artifacts(tmp_path):
    out = tmp_path / "tel"
    n = 6
    channel = make_channel("bernoulli:p=0.25", n, 0.25)
    loss_fn, init_fn, batch_fn = _problem(n)
    reg = telemetry_lib.Telemetry(out_dir=str(out))
    run_simulation(loss_fn, init_fn, batch_fn,
                   SimulatorConfig(n_workers=n, aggregator="rps_model",
                                   lr=0.2, warmup=2, steps=40,
                                   channel=channel),
                   telemetry=reg)
    summ = reg.finalize()
    for fname in ("summary.json", "trace.json", "telemetry.jsonl"):
        assert (out / fname).exists(), fname
    with open(out / "trace.json") as f:
        assert validate_chrome_trace(json.load(f)) == []
    with open(out / "summary.json") as f:
        ondisk = json.load(f)
    assert ondisk["steps"] == 40
    assert ondisk["meta"]["alpha_bounds"]["alpha2"] > 0
    assert len(ondisk["link_p"]["rs"]["observed_p"]) == n
    assert summ["meta"]["n"] == n
    with open(out / "telemetry.jsonl") as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == 40 and recs[0]["step"] == 0
    # the HTML renderer consumes exactly these artifacts
    sys.path.insert(0, os.path.join(SRC, "..", "tools"))
    try:
        import render_experiments
        html_doc = render_experiments.render_telemetry_html(str(out))
    finally:
        sys.path.pop(0)
    assert "Per-link delivery" in html_doc and "svg" in html_doc


# ---------------------------------------------------------------------------
# trainer path (subprocess: 8 forced host devices)
# ---------------------------------------------------------------------------

def test_trainer_telemetry_bit_identical():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.inputs import make_batch
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("gemma3-1b").reduced(),
                                  n_layers=2, shard_acts=True)
        model = build_model(cfg, grouped=True)

        def run(tel):
            tcfg = TrainConfig(optimizer="sgd", lr=0.3, drop_rate=0.2,
                               aggregator="rps_model", microbatch=2,
                               telemetry=tel)
            init_state, train_step, _ = make_train_setup(
                model, cfg, tcfg, mesh, rps_axes=("data",))
            params, opt_state = init_state(jax.random.PRNGKey(0))
            with jax.set_mesh(mesh):
                step = jax.jit(train_step)
                batch = jax.tree.map(
                    lambda x: x.reshape((4, -1) + x.shape[1:]),
                    make_batch(cfg, 8, 32, seed=0))
                for t in range(3):
                    params, opt_state, m = step(params, opt_state, batch,
                                                jnp.int32(t),
                                                jax.random.PRNGKey(t))
            return params, m

        p_off, m_off = run(False)
        p_on, m_on = run(True)
        for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \\
                "trainer telemetry changed the trained parameters"
        assert "telemetry" not in m_off
        tel = m_on["telemetry"]
        rs = np.asarray(tel["rs_link_delivered"])
        off = np.asarray(tel["link_offered"])
        assert rs.shape == off.shape and (rs <= off).all()
        drop = float(tel["rs_drop_rate"])
        assert 0.0 <= drop <= 1.0, drop
        print("TRAINER_TEL_OK", drop)
    """) % SRC
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TRAINER_TEL_OK" in r.stdout


# ---------------------------------------------------------------------------
# timer
# ---------------------------------------------------------------------------

def test_time_fn_and_wallclock():
    f = jax.jit(lambda x: x * 2.0)
    sec = time_fn(f, jnp.ones(16), reps=2, iters=2)
    assert 0 < sec < 1.0
    with wallclock("test.block") as w:
        np.ones(10).sum()
    assert w.s >= 0 and w.us == pytest.approx(w.s * 1e6)
    # an active registry collects labelled timings
    reg = telemetry_lib.Telemetry()
    with telemetry_lib.enabled(reg):
        with wallclock("test.labelled"):
            pass
    assert "test.labelled" in reg.timings


@pytest.mark.parametrize("spec,slack", [
    # one straggle coin per iteration correlates whole rounds → wide band
    ("deadline:deadline_ms=10,base_ms=1,jitter_ms=3,"
     "straggler_frac=0.3,straggler_mult=4", 0.06),
])
def test_per_link_estimate_converges_deadline(spec, slack):
    """Satellite regression: the drift monitor must hold on the deadline
    family too — its marginal is uniform across links (the straggle draw
    multiplies every link's latency in lockstep), so expected_link_p()
    is the right target for both legs."""
    n = 8
    channel = make_channel(spec, n, 0.1)
    loss_fn, init_fn, batch_fn = _problem(n)
    reg = telemetry_lib.Telemetry()
    run_simulation(loss_fn, init_fn, batch_fn,
                   SimulatorConfig(n_workers=n, aggregator="rps_model",
                                   lr=0.2, warmup=2, steps=300,
                                   channel=channel),
                   telemetry=reg)
    rep = reg.drift_report(slack=slack)
    assert not rep["rs"]["any_drift"], rep["rs"]
    assert not rep["ag"]["any_drift"], rep["ag"]
    np.testing.assert_allclose(channel.expected_link_p(),
                               channel.expected_link_p_ag())


def test_per_link_drift_trace_family_is_per_leg():
    """Satellite regression: TraceChannel's AG draw uses the transposed
    link matrix, so with asymmetric up/down loss the RS and AG marginals
    differ per worker. The monitor must compare each estimator to its
    own leg — checking the AG leg against the RS expectation (the
    pre-fix behaviour) false-flags drift on exactly this family."""
    from repro import channels as ch
    n = 8
    # senders 0..n-1 run increasingly lossy uplinks; downlinks the reverse
    up = np.tile(np.linspace(0.05, 0.55, n, dtype=np.float32), (2, 1))
    down = np.tile(np.linspace(0.3, 0.0, n, dtype=np.float32), (2, 1))
    channel = ch.TraceChannel(n, {"up": up, "down": down})
    exp_rs = channel.expected_link_p()
    exp_ag = channel.expected_link_p_ag()
    assert np.abs(exp_rs - exp_ag).max() > 0.08, \
        "trace not asymmetric enough to exercise the per-leg split"
    loss_fn, init_fn, batch_fn = _problem(n)
    reg = telemetry_lib.Telemetry()
    run_simulation(loss_fn, init_fn, batch_fn,
                   SimulatorConfig(n_workers=n, aggregator="rps_model",
                                   lr=0.2, warmup=2, steps=400,
                                   channel=channel),
                   telemetry=reg)
    rep = reg.drift_report(slack=0.04)
    assert not rep["rs"]["any_drift"], rep["rs"]
    assert not rep["ag"]["any_drift"], rep["ag"]
    wrong = reg.ag_est.drift(exp_rs, z=4.0, slack=0.04)
    assert wrong["any_drift"], \
        "cross-leg comparison should drift on an asymmetric trace"


def test_trace_schema_covers_async_lateness(tmp_path):
    """CI trace gate: an async run's lateness counters land in a
    schema-valid Chrome trace and the step records carry the staleness
    fields (DESIGN.md §15)."""
    loss_fn, init_fn, batch_fn = _problem(4)
    reg = telemetry_lib.Telemetry(out_dir=str(tmp_path))
    h = run_simulation(loss_fn, init_fn, batch_fn, SimulatorConfig(
        n_workers=4, aggregator="rps_model", lr=0.2, warmup=2, steps=8,
        eval_every=1, n_buckets=2, schedule="async",
        channel="deadline:deadline_ms=10,base_ms=1,jitter_ms=3,"
                "straggler_frac=0.3,straggler_mult=4"), telemetry=reg)
    assert {"rs_link_late", "ag_link_late", "late_frac",
            "staleness"} <= set(h.records[0])
    reg.finalize()
    path = os.path.join(str(tmp_path), "trace.json")
    with open(path) as f:
        obj = json.load(f)
    assert validate_chrome_trace(obj) == []
    lat = [e for e in obj["traceEvents"] if e.get("name") == "lateness"]
    assert len(lat) == 8
    assert all(e["ph"] == "C" and "late_frac" in e["args"] for e in lat)


def test_trace_schema_covers_corruption_counters(tmp_path):
    """CI trace gate: a corrupted run's contamination counters land in a
    schema-valid Chrome trace and the step records carry the §17 fields
    — and the drift monitor keeps binding the *inner* channel's delivery
    expectations (corruption changes what arrives wrong, never what
    arrives), so a corrupted run never false-flags delivery drift."""
    loss_fn, init_fn, batch_fn = _problem(4)
    reg = telemetry_lib.Telemetry(out_dir=str(tmp_path))
    h = run_simulation(loss_fn, init_fn, batch_fn, SimulatorConfig(
        n_workers=4, aggregator="rps_model", lr=0.2, warmup=2, steps=8,
        eval_every=1, n_buckets=2, drop_rate=0.2, byzantine_frac=0.25,
        recovery="median"), telemetry=reg)
    assert {"rs_link_corrupt", "corrupt_frac"} <= set(h.records[0])
    # one colluder (worker 0) of 4, every offered packet corrupted
    assert h.records[0]["rs_link_corrupt"][1:] == [0, 0, 0]
    reg.finalize()
    path = os.path.join(str(tmp_path), "trace.json")
    with open(path) as f:
        obj = json.load(f)
    assert validate_chrome_trace(obj) == []
    cor = [e for e in obj["traceEvents"] if e.get("name") == "corruption"]
    assert len(cor) == 8
    assert all(e["ph"] == "C" and "corrupt_frac" in e["args"] for e in cor)
    # drift monitor: the wrapped channel exposes the inner expectations
    assert reg.meta["p"] == pytest.approx(0.2)


def test_async_drift_monitor_uses_async_marginal():
    """bind() must shift the expected per-link p to the mean per-bucket
    async rate for a deadline-arbitrated async plan: the estimators see
    drops *plus* lateness write-offs, so comparing them to the sync
    stationary p would false-flag drift on every async run."""
    n = 8
    channel = make_channel("deadline:deadline_ms=10,base_ms=1,jitter_ms=3,"
                           "straggler_frac=0.3,straggler_mult=4", n, 0.1)
    loss_fn, init_fn, batch_fn = _problem(n)
    reg = telemetry_lib.Telemetry()
    run_simulation(loss_fn, init_fn, batch_fn,
                   SimulatorConfig(n_workers=n, aggregator="rps_model",
                                   lr=0.2, warmup=2, steps=200,
                                   n_buckets=4, schedule="async",
                                   channel=channel),
                   telemetry=reg)
    rep = reg.drift_report(slack=0.06)
    assert not rep["rs"]["any_drift"], rep["rs"]
    assert not rep["ag"]["any_drift"], rep["ag"]
    # the shift really happened: sync marginal recorded, async one bound
    assert reg.meta["p_sync"] == pytest.approx(channel.effective_p())
    assert reg.meta["p"] > reg.meta["p_sync"] + 0.1
    from repro.core import theory
    assert reg.meta["alpha_bounds"]["alpha2"] >= 0.0
