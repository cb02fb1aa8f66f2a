"""The one generator of every traffic mix: inputs made from the seed and
the mix's parameters (``bench/traffic/<name>.json``).

The sizes of the work, and for open-loop serving the gaps between
arrivals, are a fixed set drawn at stratified quantiles of their
distributions, in a fixed order; the seed fills in the tokens. So two
seeds give the same work, and a run's spread is the system's, not the
draw's.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# serving: open-loop Poisson arrivals, log-normal lengths
# ---------------------------------------------------------------------------

def _norm_ppf(q: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (Acklam's rational
    approximation, relative error < 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    q = np.asarray(q, np.float64)
    out = np.empty_like(q)
    lo, hi = q < 0.02425, q > 1 - 0.02425
    mid = ~(lo | hi)
    r = q[mid] - 0.5
    s = r * r
    out[mid] = ((((((a[0] * s + a[1]) * s + a[2]) * s + a[3]) * s + a[4]) * s
                 + a[5]) * r) / (((((b[0] * s + b[1]) * s + b[2]) * s + b[3])
                                  * s + b[4]) * s + 1)
    for m, sign, qq in ((lo, 1.0, q[lo]), (hi, -1.0, 1 - q[hi])):
        t = np.sqrt(-2 * np.log(qq))
        out[m] = sign * (((((c[0] * t + c[1]) * t + c[2]) * t + c[3]) * t
                          + c[4]) * t + c[5]) / ((((d[0] * t + d[1]) * t
                                                   + d[2]) * t + d[3]) * t + 1)
    return out


def lognormal_lengths(n: int, *, median: float, sigma: float, lo: int,
                      hi: int, multiple: int = 1) -> np.ndarray:
    """``n`` lengths at the stratified quantiles (i + 1/2)/n of a
    log-normal, clipped to [lo, hi] and rounded up to ``multiple``."""
    q = (np.arange(n) + 0.5) / n
    x = median * np.exp(sigma * _norm_ppf(q))
    x = np.clip(np.ceil(x), lo, hi)
    x = np.ceil(x / multiple) * multiple
    return np.minimum(x, hi).astype(np.int64)


def poisson_gaps_ms(n: int, rate_per_s: float) -> np.ndarray:
    """``n`` gaps at the stratified quantiles of an exponential law of
    mean 1/rate: a Poisson process's inter-arrival times."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate_per_s * 1e3


def open_loop(seed: int, seconds: float, *, rate_per_s: float,
              prompt: dict, output: dict, vocab: int
              ) -> List[Tuple[float, np.ndarray, int]]:
    """The requests that arrive in a window of ``seconds``: a list of
    (arrival_ms, prompt token ids, max_new), sorted by arrival."""
    n = max(int(round(rate_per_s * seconds)), 1)
    # one fixed order of sizes and gaps for every seed: when the longest
    # request arrives sets how long the window drains, so an order drawn
    # from the seed changed the work (on a TPU v5e, serve_tokens_per_s
    # read 94 to 124 across three seeds and within 1% for one seed)
    order = np.random.default_rng(0)
    gaps = order.permutation(poisson_gaps_ms(n, rate_per_s))
    arrivals = np.cumsum(gaps) - gaps[0]
    plens = order.permutation(lognormal_lengths(n, **prompt))
    outs = order.permutation(lognormal_lengths(n, **output))
    rng = np.random.default_rng(seed)
    return [(float(a), rng.integers(0, vocab, int(p)).astype(np.int32),
             int(o)) for a, p, o in zip(arrivals, plens, outs)]


def prompt_grid(prompt: dict) -> List[int]:
    """Every prompt length the mix can draw (its rounding grid)."""
    m = prompt.get("multiple", 1)
    lo = int(math.ceil(prompt["lo"] / m) * m)
    return list(range(lo, prompt["hi"] + 1, m))
