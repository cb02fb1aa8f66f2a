"""The per-layer metrics that read the serving engine's own spans
(``queue_wait_share.serve``, ``host_idle_share.serve``,
``hbm_share.round``), on hand-made traces: each span is ``[name,
start_ns, dur_ns, args]`` as ``trace_reduce.load_xplane`` keeps it.

    python -m pytest -q bench/tests
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
PEAKS = {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e15}


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "test_metric_" + name.replace(".", "_"))


def _trace(ops=(), modules=(), spans=(), window=None):
    planes = [{"name": DEV, "lines": [
        {"name": tr.OPS_LINE, "events": [[n, s, d, {}] for n, s, d in ops]},
        {"name": tr.MODULES_LINE,
         "events": [[n, s, d, {}] for n, s, d in modules]}]},
        {"name": tr.HOST_PLANE, "lines": [
            {"name": "python3",
             "events": [[n, s, d, dict(a)] for n, s, d, a in spans]}]}]
    return tr.Trace({"planes": planes}, window=window)


# ---------------------------------------------------------------------------
# queue_wait_share.serve
# ---------------------------------------------------------------------------

def test_queue_wait_share_over_first_prefills_in_window():
    ms = 1e6
    spans = [("serve.prefill", 10 * ms, 10 * ms, {"first": 1,
                                                  "wait_ms": 30.0}),
             ("serve.prefill", 40 * ms, 10 * ms, {"first": 1,
                                                  "wait_ms": 10.0}),
             # a re-prefill after preemption: its wait is not admission
             ("serve.prefill", 60 * ms, 10 * ms, {"first": 0,
                                                  "wait_ms": 500.0}),
             # a first prefill outside the window
             ("serve.prefill", 200 * ms, 10 * ms, {"first": 1,
                                                   "wait_ms": 900.0})]
    t = _trace(spans=spans, window=(0, 100 * ms))
    got = _reader("queue_wait_share.serve").read(t, {}, PEAKS)
    assert got == pytest.approx(100.0 * 40 / 60)


def test_queue_wait_share_silent_without_the_args():
    """A program whose prefill spans carry no ``first``/``wait_ms`` (the
    parent's ``rid`` and ``tokens`` only) reads nothing, and raises
    nothing."""
    t = _trace(spans=[("serve.prefill", 10, 10, {"rid": 1, "tokens": 8})],
               window=(0, 100))
    assert _reader("queue_wait_share.serve").read(t, {}, PEAKS) is None
    assert _reader("queue_wait_share.serve").read(_trace(window=(0, 100)),
                                                  {}, PEAKS) is None


# ---------------------------------------------------------------------------
# host_idle_share.serve
# ---------------------------------------------------------------------------

def _idle_trace(spans):
    # busy [0,10] [20,30] [60,100]: idle [10,20] and [30,60]
    return _trace(ops=[("%a = x", 0, 10), ("%b = x", 20, 10),
                       ("%c = x", 60, 40)], spans=spans, window=(0, 100))


def test_host_idle_share_counts_gaps_under_engine_spans():
    """The gap under ``serve.batch`` (inside ``serve.step``) counts; the
    part of a gap under ``serve.wait`` alone does not."""
    spans = [("serve.step", 0, 50, {}), ("serve.batch", 12, 6, {}),
             ("serve.wait", 50, 50, {})]
    got = _reader("host_idle_share.serve").read(_idle_trace(spans), {},
                                                PEAKS)
    assert got == pytest.approx(30.0)          # [10,20] + [30,50]
    idle = _idle_trace(spans).idle_share() * 100
    assert got <= idle == pytest.approx(40.0)


def test_host_idle_share_gap_under_wait_is_not_host_work():
    spans = [("serve.step", 0, 25, {}), ("serve.wait", 25, 75, {})]
    got = _reader("host_idle_share.serve").read(_idle_trace(spans), {},
                                                PEAKS)
    assert got == pytest.approx(10.0)          # [10,20] only


def test_host_idle_share_silent_without_step_spans():
    spans = [("serve.prefill", 0, 50, {"rid": 0, "tokens": 8}),
             ("bench.window", 0, 100, {})]
    assert _reader("host_idle_share.serve").read(_idle_trace(spans), {},
                                                 PEAKS) is None


# ---------------------------------------------------------------------------
# hbm_share.round
# ---------------------------------------------------------------------------

W, KV = 1000.0, 10.0
ROUND = {"program": "jit_round_fn", "steps": 2, "tokens": 3, "kv_reads": 21}


def _round_trace(round_args=ROUND):
    """One round span [100,200) in the window [0,300): the round program
    runs [110,150) inside it, and a scatter program [160,180) too; another
    round's span and run lie past the window."""
    ops = [("%fusion.1 = x", 110, 40), ("%scatter.2 = x", 160, 20),
           ("%fusion.1 = x", 410, 40)]
    modules = [("jit_round_fn(1)", 110, 40), ("jit_write(2)", 160, 20),
               ("jit_round_fn(1)", 410, 40)]
    spans = [("serve.round", 100, 100, round_args),
             ("serve.round", 400, 100, round_args)]
    return _trace(ops, modules, spans, window=(0, 300))


def test_hbm_share_round_bytes_over_its_programs_busy_time():
    """The scatter run inside the span is not the span's program and is
    not counted; the round past the window is not either."""
    got = _reader("hbm_share.round").read(
        _round_trace(), {"weight_bytes": W, "kv_bytes_per_token": KV}, PEAKS)
    nbytes = 2 * W + KV * (21 + 3)
    assert got == pytest.approx(100.0 * nbytes / (40e-9 * 1e12))


def test_hbm_share_round_agrees_with_hbm_share_decode():
    """Counts from the engine's span give the reading that the lane
    positions logged by ``RoundLog`` give (pos 5, 7 and an idle lane; 2,
    1 and 0 tokens left; chunk 4: 2 steps, 3 tokens, 6 + 8 + 7 = 21
    reads)."""
    info = {"weight_bytes": W, "kv_bytes_per_token": KV, "chunk": 4,
            "decode_module": "jit_round_fn", "matmul_params": 1.0,
            "attn_flops_per_ctx_token": 1.0,
            "rounds": [([5, 7, 0], [2, 1, 0])]}
    t = _round_trace()
    assert _reader("hbm_share.round").read(t, info, PEAKS) == pytest.approx(
        _reader("hbm_share.decode").read(t, info, PEAKS))


def test_hbm_share_round_takes_a_run_that_leads_its_span():
    """On a TPU profile a round's run starts up to a millisecond before
    the host dispatches it, so before its span's start: the run still
    belongs to the span whose start lies nearest."""
    ops = [("%fusion.1 = x", 95, 100)]
    modules = [("jit_round_fn(1)", 95, 100)]
    t = _trace(ops, modules, [("serve.round", 100, 100, ROUND)],
               window=(0, 300))
    got = _reader("hbm_share.round").read(
        t, {"weight_bytes": W, "kv_bytes_per_token": KV}, PEAKS)
    nbytes = 2 * W + KV * (21 + 3)
    assert got == pytest.approx(100.0 * nbytes / (100e-9 * 1e12))


def test_hbm_share_round_silent_without_counters():
    info = {"weight_bytes": W, "kv_bytes_per_token": KV}
    t = _round_trace({})
    assert _reader("hbm_share.round").read(t, info, PEAKS) is None
    t = _trace(window=(0, 300))
    assert _reader("hbm_share.round").read(t, info, PEAKS) is None
