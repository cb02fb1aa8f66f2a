"""The training cell ``train-sim-ds7b-p10`` at a size a test run holds, on
the CPU: its data, a whole run through the harness (sound: correct; with
the timed step broken underneath, once for each fault a training step
can have: not correct), the float8 control against the cell's limits,
the attribution of a compiled program's ops to the code that built them,
and the training readers on the recorded trace and on hand-made ones.

    python -m pytest -q bench/tests
"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import hlo_ops  # noqa: E402
import markov  # noqa: E402
import trace_reduce as tr  # noqa: E402
import train_work  # noqa: E402

SEED = 3_000_000_001
TRAIN = "train-sim-ds7b-p10"
DEV = "/device:TPU:0"
PEAKS = {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e15}


#: the limits at this size, set as the cell's were, from readings on the
#: CPU at these widths over seven seeds (this file's among them): the
#: program's loss_gap 3.6e-5 to 1.2e-4, change1_gap and change_gap under
#: 2.8e-3; the float8 control's loss_gap 4.7e-4 to 9.8e-4; each fault's
#: larger gap 0.011 or more (on the CPU)
TINY_LIMITS = {"loss_gap": 2.5e-4, "change1_gap": 0.012, "change_gap": 0.006,
               "nonfinite": 0.0}


def tiny(cell):
    c = dict(cell.config, hidden_size=128, intermediate_size=256,
             num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
             num_hidden_layers=2)
    t = dict(cell.traffic, seq_len=32, limits=TINY_LIMITS,
             task=dict(cell.traffic["task"], vocab=256, batches=8))
    return dataclasses.replace(cell, config=c, traffic=t)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def run_cell(capsys, seconds=1.0, trace=0):
    rc = harness.run(["--workload", TRAIN, "--seed", str(SEED),
                      "--seconds", str(seconds), "--trace", str(trace)],
                     require_chip=False, cell_override=tiny)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0
    out = json.loads(line)
    assert list(out)[-1] == "checks"
    return out


# ---------------------------------------------------------------------------
# the data
# ---------------------------------------------------------------------------

def test_markov_batches_come_from_the_seed():
    task = {"vocab": 64, "order_temp": 1.0, "batches": 3}
    tok, lab = markov.batches(7, task, 2, 4, 16)
    assert tok.shape == lab.shape == (3, 2, 4, 16)
    assert tok.dtype == np.int32 and 0 <= tok.min() and tok.max() < 64
    assert (tok[..., 1:] == lab[..., :-1]).all()
    again = markov.batches(7, task, 2, 4, 16)
    assert (again[0] == tok).all() and (again[1] == lab).all()
    assert (markov.batches(8, task, 2, 4, 16)[0] != tok).any()
    rows = tok.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_step_work_counts_from_shapes():
    hf = {"hidden_size": 4096, "num_attention_heads": 32,
          "num_key_value_heads": 32, "intermediate_size": 11008,
          "vocab_size": 102400, "num_hidden_layers": 2}
    flops = train_work.step_flops(hf, 2048, 256)
    matmul = 2 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400
    assert flops == pytest.approx(6 * matmul * 2048 + 3 * 8 * 4
                                  * (256 * 257 / 2) * 32 * 128 * 2)
    assert train_work.masked_avg_bytes([10, 6], 2, 2, 2) == 3 * 16 * 2 + 8


# ---------------------------------------------------------------------------
# what decides correct
# ---------------------------------------------------------------------------

def test_train_sound_run_is_correct(capsys):
    out = run_cell(capsys)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 3
    assert set(out["checks"]) == {"loss_gap", "change1_gap", "change_gap",
                                  "nonfinite"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def _unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp
    from repro.train import simulator
    orig = simulator.make_sim_step

    def make(*a, **k):
        step = orig(*a, **k)

        def still(params, *rest, **kw):
            outs = step(jax.tree.map(jnp.copy, params), *rest, **kw)
            return (params,) + tuple(outs[1:])
        return still
    monkeypatch.setattr(simulator, "make_sim_step", make)


def _half_batch(monkeypatch):
    """Half of each worker's batch left out, the mean over the rest."""
    import repro.models
    orig = repro.models.build_model

    def build(*a, **k):
        model = orig(*a, **k)
        full = model.loss

        def loss(params, batch):
            return full(params, {k: v[: v.shape[0] // 2]
                                 for k, v in batch.items()})
        return dataclasses.replace(model, loss=loss)
    monkeypatch.setattr(repro.models, "build_model", build)


def _no_exchange(monkeypatch):
    """The exchange between the workers left out."""
    from repro.train import simulator
    monkeypatch.setattr(simulator, "_exchange", lambda tree, *a, **k: tree)


def _other_masks(monkeypatch):
    """The exchange run on the masks of another key (a tag under which
    the drops of this test's seed differ)."""
    import jax
    from repro.core import rps
    orig = rps.rps_exchange_global

    def exchange(tree, key, p, n, **kw):
        kw["masks"] = rps.sample_masks(jax.random.fold_in(key, 3), n, p)
        return orig(tree, key, p, n, **kw)
    monkeypatch.setattr(rps, "rps_exchange_global", exchange)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange,
                                   _other_masks],
                         ids=lambda f: f.__name__.strip("_"))
def test_train_fault_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = run_cell(capsys)
    assert out["correct"] is False, out["checks"]


def test_train_control_fails_the_limits():
    """The float8 control, put in the program's place, fails the cell's
    limits; the reference against itself reads 0."""
    import jax
    import jax.numpy as jnp
    import calibrate
    from refs import train as ref_train
    cell = tiny(harness.load_cell(TRAIN))
    hf, trf = cell.config, cell.traffic
    s31 = harness.seed32(SEED)
    tok, lab = markov.batches(s31, trf["task"], 2, 4, trf["seq_len"])
    _, key = jax.random.split(jax.random.PRNGKey(s31))
    masks = [tuple(np.asarray(m) for m in
                   jax.random.bernoulli(jax.random.fold_in(key, t), 0.9,
                                        (2, 2, 2)) | np.eye(2, dtype=bool))
             for t in range(3)]
    args = (s31, tok[:3], lab[:3], masks, 0.05, 3)
    truth = ref_train.Reference(hf).run(*args)
    same = ref_train.gaps(truth, truth)
    assert same["loss_gap"] == same["change1_gap"] == same["change_gap"] == 0
    ctrl = ref_train.gaps(ref_train.Reference(
        hf, lowp=jnp.float8_e4m3fn).run(*args), truth)
    numbers = ("loss_gap", "change1_gap", "change_gap")
    assert not calibrate.verdict({k: ctrl[k] for k in numbers},
                                 {k: trf["limits"][k] for k in numbers}), ctrl


def test_backward_only_product_rounds_the_backward_alone():
    """``lowp_mm(forward=False)``: the forward product is the float32
    one, and the gradients are those of the product on float8 inputs."""
    import jax
    import jax.numpy as jnp
    from refs import train as ref_train
    ka, kb, kc = jax.random.split(jax.random.PRNGKey(5), 3)
    a = jax.random.normal(ka, (8, 16))
    b = jax.random.normal(kb, (16, 4))
    ct = jax.random.normal(kc, (8, 4))
    f8 = jnp.float8_e4m3fn
    both, back = ref_train.lowp_mm(f8), ref_train.lowp_mm(f8, forward=False)
    eq = "ij,jk->ik"
    assert (back(eq, a, b) == ref_train.mm32(eq, a, b)).all()
    assert not (both(eq, a, b) == ref_train.mm32(eq, a, b)).all()

    def grads(mm):
        return jax.vjp(lambda x, y: mm(eq, x, y), a, b)[1](ct)

    for g_back, g_both, g32 in zip(grads(back), grads(both),
                                   grads(ref_train.mm32)):
        assert (g_back == g_both).all()
        assert not (g_back == g32).all()


# ---------------------------------------------------------------------------
# ops traced back to the code that built them
# ---------------------------------------------------------------------------

def _outer(x):
    return _inner(x) * 3.0


def _inner(x):
    return x.sum(axis=0) + 1.0


def test_compiled_ops_traced_to_their_function():
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return _outer(x) @ y, jnp.tanh(y).sum()

    x = jnp.ones((4, 8, 8))
    text = jax.jit(f).lower(x, x[0]).compile().as_text()
    prog = hlo_ops.Program(text)
    inner = prog.ops_where(hlo_ops.called_from("_inner", "test_train.py"))
    assert inner and set(inner) <= set(prog.device_ops)
    for name in inner:
        stack = [fn for _, fn in prog.stack(prog.frame_of(name))]
        assert stack[:2] == ["_inner", "_outer"], stack
    tanh = prog.ops_where(lambda fr: not any(fn in ("_inner", "_outer")
                                             for _, fn in fr))
    assert tanh and not set(tanh) & set(inner)
    assert prog.ops_where(hlo_ops.called_from("_inner", "other.py")) == []


def test_frame_tables_and_fusion_roots():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "",
        "FileNames",
        '1 "/src/a.py"',
        '2 "/src/b.py"',
        "",
        "FunctionNames",
        '1 "outer"',
        '2 "inner"',
        "",
        "FileLocations",
        "1 {file_name_id=1 function_name_id=1 line=3 end_line=3}",
        "2 {file_name_id=2 function_name_id=2 line=9 end_line=9}",
        "",
        "StackFrames",
        "1 {file_location_id=1 parent_frame_id=1}",
        "2 {file_location_id=2 parent_frame_id=2}",
        "",
        "%fused_computation (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        "  ROOT %m = f32[4]{0} multiply(%p, %p), "
        "metadata={op_name=\"x\" stack_frame_id=2}",
        "}",
        "",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation, metadata={op_name=\"y\"}",
        "  ROOT %add.2 = f32[4]{0} add(%fusion.1, %a), "
        "metadata={op_name=\"z\" stack_frame_id=1}",
        "}",
    ])
    prog = hlo_ops.Program(text)
    assert sorted(prog.device_ops) == ["a", "add.2", "fusion.1"]
    # the fusion has no frame of its own: its root's, inner <- outer
    assert prog.stack(prog.frame_of("fusion.1")) == [
        ("/src/b.py", "inner"), ("/src/a.py", "outer")]
    assert prog.ops_where(hlo_ops.called_from("inner", "b.py")) == \
        ["fusion.1"]
    assert prog.ops_where(hlo_ops.called_from("outer", "a.py")) == \
        ["add.2", "fusion.1"]


# ---------------------------------------------------------------------------
# the training readers
# ---------------------------------------------------------------------------

def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def _trace(ops=(), modules=(), window=None):
    planes = [{"name": DEV, "lines": [
        {"name": tr.OPS_LINE, "events": [[n, s, d, {}] for n, s, d in ops]},
        {"name": tr.MODULES_LINE,
         "events": [[n, s, d, {}] for n, s, d in modules]}]}]
    return tr.Trace({"planes": planes}, window=window)


INFO = {"step_module": "jit_step_fn", "kernel": "masked_avg_grid_pallas",
        "step_flops": 1e6, "masked_avg_bytes": 3000,
        "exchange_ops": ["fusion.7", "masked_avg_grid_pallas.2"]}


def _hand_made():
    """Two step runs inside the window (0-100, 100-200 ns) and one that
    crosses its end; the window 0-250."""
    ops = []
    for base in (0, 100, 200):
        ops += [("%fusion.1 = ...", base + 0, 40),             # matmul
                ("%fusion.7 = ...", base + 40, 20),            # exchange
                ("%masked_avg_grid_pallas.2 = ...", base + 60, 10),
                ("%copy.3 = ...", base + 70, 20)]
    mods = [("jit_step_fn(11)", b, 95) for b in (0, 100, 200)]
    return _trace(ops, mods, window=(0, 250))


def test_train_readers_on_hand_made_trace():
    t = _hand_made()
    # busy inside the two whole runs: 2 x 90 ns
    assert _reader("mfu.train").read(t, INFO, PEAKS) == pytest.approx(
        100 * 1e6 * 2 / (180e-9 * 1e15))
    assert _reader("exchange_share.train").read(t, INFO, PEAKS) == \
        pytest.approx(100 * 60 / 180)
    assert _reader("masked_avg_roofline").read(t, INFO, PEAKS) == \
        pytest.approx(100 * 3000 * 2 / (20e-9 * 1e12))
    # busy 0-90, 100-190, 200-250 of 250 ns
    assert _reader("idle_share.train").read(t, INFO, PEAKS) == \
        pytest.approx(100 * (1 - 230 / 250))


def test_train_readers_return_nothing_without_their_inputs():
    t = _hand_made()
    for name in ("mfu.train", "exchange_share.train",
                 "masked_avg_roofline"):
        assert _reader(name).read(t, {}, PEAKS) is None
        assert _reader(name).read(
            t, dict(INFO, step_module="jit_other"), PEAKS) is None
    assert _reader("exchange_share.train").read(
        t, dict(INFO, exchange_ops=[]), PEAKS) is None
    assert _reader("masked_avg_roofline").read(
        t, dict(INFO, kernel="other_kernel"), PEAKS) is None
    assert _reader("idle_share.train").read(_trace(), {}, PEAKS) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "tpu_v5e_train_step.json")) as f:
        data = json.load(f)
    return tr.Trace(data, window=tuple(data["window"]))


def test_train_readers_on_recorded_trace(recorded):
    """The recorded v5e step (an earlier build of this cell's step: 2
    layers, 2 workers, 2048 tokens a step): one step run wholly in the
    window, 243.4 ms."""
    import peaks
    v5e = peaks.peaks("TPU v5 lite")
    hf = {"hidden_size": 4096, "num_attention_heads": 32,
          "num_key_value_heads": 32, "intermediate_size": 11008,
          "vocab_size": 102400, "num_hidden_layers": 2}
    sizes = [4096, 4096 * 102400, 102400 * 4096] \
        + [2 * 4096 * 4096] * 4 + [2 * 4096] * 2 + [2 * 4096 * 11008] * 3
    info = {"step_module": "jit_step_fn", "kernel": "masked_avg_grid_pallas",
            "step_flops": train_work.step_flops(hf, 2048, 256),
            "masked_avg_bytes": train_work.masked_avg_bytes(sizes, 2, 2, 2),
            "exchange_ops": sorted({
                tr.op_short_name(e[0]) for e in recorded.ops(DEV)
                if "masked_avg" in e[0]})}
    runs = recorded.module_runs(lambda m: m == "jit_step_fn")
    assert len(runs) == 1
    mfu = _reader("mfu.train").read(recorded, info, v5e)
    busy = recorded.module_op_seconds(lambda m: m == "jit_step_fn")
    assert mfu == pytest.approx(100 * info["step_flops"]
                                / (busy * 197e12))
    kern = recorded.op_seconds(lambda s: s.startswith("masked_avg_grid"),
                               within=runs)
    roof = _reader("masked_avg_roofline").read(recorded, info, v5e)
    assert roof == pytest.approx(100 * info["masked_avg_bytes"]
                                 / (kern * 819e9))
    assert 0 < roof < 100
    ex = _reader("exchange_share.train").read(recorded, info, v5e)
    assert ex == pytest.approx(100 * kern / busy)
    idle = _reader("idle_share.train").read(recorded, info, v5e)
    assert idle == pytest.approx(100 * recorded.idle_share())
