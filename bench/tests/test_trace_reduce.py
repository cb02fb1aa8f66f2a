"""The reduction from trace to metrics, on a small recorded trace of the
simulator step on one TPU v5e (every masked-average kernel op and every
fifth other op of one step) and on hand-made traces.

    python -m pytest -q bench/tests
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"


def _trace(ops, spans=(), modules=(), window=None, device=DEV):
    planes = [{"name": device, "lines": [
        {"name": tr.OPS_LINE, "events": [[n, s, d, {}] for n, s, d in ops]},
        {"name": tr.MODULES_LINE,
         "events": [[n, s, d, {}] for n, s, d in modules]}]},
        {"name": tr.HOST_PLANE, "lines": [
            {"name": "python3",
             "events": [[n, s, d, {}] for n, s, d in spans]}]}]
    return tr.Trace({"planes": planes}, window=window)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "tpu_v5e_train_step.json")) as f:
        data = json.load(f)
    return data, tr.Trace(data, window=tuple(data["window"]))


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (4, 4)]) == [(0, 3), (5, 7)]
    assert tr.length(tr.union([(0, 10), (2, 3), (9, 12)])) == 12


def test_subtract_leaves_uncovered_parts():
    assert tr.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_busy_is_union_of_ops_clipped_to_window():
    t = _trace([("%a.1 = f32[] add()", 0, 10), ("%b = f32[] mul()", 5, 10),
                ("%c.2 = f32[] add()", 30, 10)], window=(0, 50))
    assert t.busy_s(DEV) == pytest.approx(25e-9)
    assert t.idle_share() == pytest.approx(0.5)
    t = _trace([("%a = x", 0, 10), ("%c = x", 30, 30)], window=(5, 40))
    assert t.busy_s(DEV) == pytest.approx(15e-9)


def test_busy_and_idle_of_recorded_trace(recorded):
    data, t = recorded
    w0, w1 = data["window"]
    ops = [e for pl in data["planes"] if pl["name"] == DEV
           for ln in pl["lines"] if ln["name"] == tr.OPS_LINE
           for e in ln["events"]]
    # by hand: sort, merge, clip
    ivs = sorted((max(e[1], w0), min(e[1] + e[2], w1)) for e in ops
                 if e[1] + e[2] > w0 and e[1] < w1)
    busy, end = 0.0, -1.0
    for s, e in ivs:
        s = max(s, end)
        if e > s:
            busy += e - s
            end = e
    assert t.busy_s(DEV) == pytest.approx(busy / 1e9)
    assert t.idle_share() == pytest.approx(1 - busy / (w1 - w0))
    assert 0.0 < t.idle_share() < 1.0


def test_op_time_by_name_and_inside_program_runs(recorded):
    data, t = recorded
    kern = sum(e[2] for pl in data["planes"] if pl["name"] == DEV
               for ln in pl["lines"] if ln["name"] == tr.OPS_LINE
               for e in ln["events"] if "masked_avg_grid_pallas" in e[0])
    assert kern > 0
    got = t.op_seconds(lambda s: s.startswith("masked_avg_grid_pallas"))
    assert got == pytest.approx(kern / 1e9)
    runs = t.module_runs(lambda m: m == "jit_step_fn")
    assert len(runs) == 1
    inside = t.op_seconds(lambda s: s.startswith("masked_avg"), within=runs)
    assert 0 < inside <= got
    assert t.top_ops(1)[0][0] == "masked_avg_grid_pallas.6"


def test_op_names_reduce_to_instruction_names():
    assert tr.op_short_name("%fusion.4 = bf16[2,4] fusion(...)") == "fusion.4"
    assert tr.op_kind("masked_avg_grid_pallas.6") == "masked_avg_grid_pallas"
    assert tr.module_name("jit_step_fn(3342876)") == "jit_step_fn"


def test_module_op_seconds_counts_busy_time_inside_runs():
    t = _trace([("%a = x", 0, 10), ("%b = x", 20, 10), ("%c = x", 60, 10)],
               modules=[("jit_prefill(1)", 0, 35), ("jit_round(2)", 50, 30)],
               window=(0, 100))
    assert t.module_op_seconds(lambda m: m == "jit_prefill") == \
        pytest.approx(20e-9)
    assert t.module_op_seconds(lambda m: m == "jit_round") == \
        pytest.approx(10e-9)


def test_gap_named_by_innermost_host_span():
    t = _trace([("%a = x", 0, 10), ("%b = x", 50, 10), ("%c = x", 70, 5)],
               spans=[("serve.request", 0, 100), ("serve.prefill", 12, 36)],
               window=(0, 80))
    gaps = t.idle_gaps(3)
    assert gaps[0] == ["serve.prefill", pytest.approx(40e-9)]
    assert gaps[1][0] == "serve.request"          # (60, 70)
    # a gap no span covers for more than half
    t = _trace([("%a = x", 0, 10), ("%b = x", 50, 10)],
               spans=[("x", 0, 15)], window=(0, 60))
    assert t.idle_gaps(1) == [["no host span", pytest.approx(40e-9)]]


def test_peaks_known_and_unknown_device():
    row = peaks.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")
