"""The traffic generator: the same seed gives the same inputs; another
seed gives the same work with other tokens."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import traffic_gen  # noqa: E402

CHAT = dict(rate_per_s=5.0,
            prompt={"median": 512, "sigma": 0.7, "lo": 128, "hi": 2048,
                    "multiple": 128},
            output={"median": 96, "sigma": 0.8, "lo": 16, "hi": 512},
            vocab=32000)


def test_same_seed_same_open_loop_trace():
    a = traffic_gen.open_loop(5, 20.0, **CHAT)
    b = traffic_gen.open_loop(5, 20.0, **CHAT)
    assert len(a) == len(b) == 100
    for (ta, pa, ma), (tb, pb, mb) in zip(a, b):
        assert ta == tb and ma == mb
        np.testing.assert_array_equal(pa, pb)


def test_other_seed_same_work_other_tokens():
    a = traffic_gen.open_loop(5, 20.0, **CHAT)
    b = traffic_gen.open_loop(3_000_000_017, 20.0, **CHAT)
    assert [(t, len(p), m) for t, p, m in a] == \
        [(t, len(p), m) for t, p, m in b]
    assert not all(np.array_equal(pa, pb) for (_, pa, _), (_, pb, _)
                   in zip(a, b))


def test_lengths_lie_on_the_grid_and_arrivals_fill_the_window():
    trace = traffic_gen.open_loop(1, 20.0, **CHAT)
    grid = set(traffic_gen.prompt_grid(CHAT["prompt"]))
    assert len(grid) == 16
    assert {len(p) for _, p, _ in trace} <= grid
    outs = [m for _, _, m in trace]
    assert 16 <= min(outs) and max(outs) <= 512
    assert np.median(outs) == 96 or abs(np.median(outs) - 96) <= 2
    times = [t for t, _, _ in trace]
    assert times == sorted(times) and times[0] == 0.0
    assert 15e3 < times[-1] < 25e3
    assert all(0 <= p.min() and p.max() < 32000 for _, p, _ in trace)
