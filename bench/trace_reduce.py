"""Reduction from a profiler trace to the numbers the per-layer metrics read.

A trace is kept as plain data, so that the reduction can be tested on a
small recorded trace without a chip::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, {stat: value}], ...]}]}]}

``load_xplane`` builds it from the ``.xplane.pb`` file that
``jax.profiler`` writes. On a TPU plane the line "XLA Ops" holds one event
per HLO instruction executed, named by the instruction's text
(``%fusion.4 = bf16[...] fusion(...)``), and "XLA Modules" one event per
program run, named ``jit_<fn>(<fingerprint>)``. Host spans (the program's
``TraceAnnotation``s and the benchmark's own) sit on the host plane's
Python thread lines. Device and host events share the profile's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]          # (start_ns, end_ns)


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _host_line_kept(name: str) -> bool:
    return name.startswith("python")


def load_xplane(path: str) -> dict:
    """The plain-data trace of one ``.xplane.pb``: every device plane's op
    and module lines, and the host's Python-thread spans (Python frames,
    named ``$file:line fn``, are left out)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        if pl.name.startswith(DEVICE_PREFIX):
            keep = (OPS_LINE, MODULES_LINE)
            lines = [{"name": ln.name,
                      "events": [[ev.name, float(ev.start_ns),
                                  float(ev.duration_ns), {}]
                                 for ev in ln.events]}
                     for ln in pl.lines if ln.name in keep]
        elif pl.name == HOST_PLANE:
            lines = []
            for ln in pl.lines:
                if not _host_line_kept(ln.name):
                    continue
                evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns),
                        {k: v if isinstance(v, (int, float)) else str(v)
                         for k, v in ev.stats}]
                       for ev in ln.events if not ev.name.startswith("$")]
                lines.append({"name": ln.name, "events": evs})
        else:
            continue
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------

def op_short_name(event_name: str) -> str:
    """``%fusion.4 = bf16[...] fusion(...)`` -> ``fusion.4``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_kind(short: str) -> str:
    """``masked_avg_grid_pallas.6`` -> ``masked_avg_grid_pallas``."""
    return re.sub(r"\.\d+$", "", short)


def module_name(event_name: str) -> str:
    """``jit_step_fn(3342876)`` -> ``jit_step_fn``."""
    return event_name.split("(", 1)[0]


class Trace:
    """Device ops, program runs and host spans of one traced window."""

    def __init__(self, data: dict, window: Optional[Interval] = None):
        self.data = data
        self._lines: Dict[Tuple[str, str], list] = {}
        for pl in data["planes"]:
            for ln in pl["lines"]:
                self._lines.setdefault((pl["name"], ln["name"]),
                                       []).extend(ln["events"])
        self.devices = sorted(
            {pl["name"] for pl in data["planes"]
             if pl["name"].startswith(DEVICE_PREFIX)},
            key=lambda s: int(s[len(DEVICE_PREFIX):]))
        self.window = window if window is not None else self._extent()

    def _extent(self) -> Interval:
        evs = [e for d in self.devices for e in self.ops(d)]
        if not evs:
            return (0.0, 0.0)
        return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # -- raw access --------------------------------------------------------
    def ops(self, device: str) -> list:
        return self._lines.get((device, OPS_LINE), [])

    def modules(self, device: str) -> list:
        return self._lines.get((device, MODULES_LINE), [])

    def host_spans(self, name: Optional[str] = None) -> list:
        out = [e for (pl, _), evs in self._lines.items() if pl == HOST_PLANE
               for e in evs]
        if name is not None:
            out = [e for e in out if e[0] == name]
        return sorted(out, key=lambda e: e[1])

    def _in_window(self, events) -> list:
        w0, w1 = self.window
        return [e for e in events if e[1] + e[2] > w0 and e[1] < w1]

    # -- busy and idle -----------------------------------------------------
    def busy_intervals(self, device: str) -> List[Interval]:
        return clip(union((e[1], e[1] + e[2]) for e in self.ops(device)),
                    self.window)

    def busy_s(self, device: str) -> float:
        return length(self.busy_intervals(device)) / 1e9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> Optional[float]:
        """1 - (union of device op intervals) / window, averaged over the
        chips; None where the window is empty."""
        if self.window_s <= 0 or not self.devices:
            return None
        return 1.0 - self.mean_busy_s() / self.window_s

    # -- attribution -------------------------------------------------------
    def op_seconds(self, pred: Callable[[str], bool],
                   device: Optional[str] = None,
                   within: Optional[Sequence[Interval]] = None) -> float:
        """Summed device time of the ops whose short name satisfies
        ``pred``, inside the window, or only inside the intervals
        ``within`` (mean over chips when ``device`` is None)."""
        devs = [device] if device is not None else self.devices
        tot = 0.0
        for d in devs:
            ivs = clip(((ev[1], ev[1] + ev[2]) for ev in self.ops(d)
                        if pred(op_short_name(ev[0]))), self.window)
            if within is not None:
                ivs = subtract(union(ivs), subtract(union(ivs),
                                                    union(within)))
            tot += length(ivs)
        return tot / 1e9 / max(len(devs), 1)

    def module_runs(self, pred: Callable[[str], bool],
                    device: Optional[str] = None) -> List[Interval]:
        """Intervals of the program runs whose module name satisfies
        ``pred`` and that lie wholly inside the window."""
        if device is None and not self.devices:
            return []
        d = device if device is not None else self.devices[0]
        w0, w1 = self.window
        return [(e[1], e[1] + e[2]) for e in self.modules(d)
                if pred(module_name(e[0])) and e[1] >= w0
                and e[1] + e[2] <= w1]

    def module_op_seconds(self, pred: Callable[[str], bool],
                          device: Optional[str] = None) -> float:
        """Device busy time inside the runs of the matching programs
        (mean over chips when ``device`` is None)."""
        devs = [device] if device is not None else self.devices
        tot = 0.0
        for d in devs:
            runs = union(self.module_runs(pred, d))
            busy = self.busy_intervals(d)
            tot += length(subtract(busy, subtract(busy, runs)))
        return tot / 1e9 / max(len(devs), 1)

    # -- the breakdown -----------------------------------------------------
    def top_ops(self, k: int = 10) -> List[list]:
        """The op kinds (instruction names without their number) that took
        most device time in the window, mean over chips."""
        agg: Dict[str, float] = {}
        for d in self.devices:
            for ev in self._in_window(self.ops(d)):
                short = op_short_name(ev[0])
                agg[short] = agg.get(short, 0.0) + ev[2]
        n = max(len(self.devices), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9 / n] for name, ns in top]

    def idle_gaps(self, k: int = 10, device: Optional[str] = None
                  ) -> List[list]:
        """The ``k`` longest idle gaps of one chip in the window, each named
        by the innermost host span open over most of it ("no host span"
        where none is)."""
        if device is None and not self.devices:
            return []
        d = device if device is not None else self.devices[0]
        busy = self.busy_intervals(d)
        gaps = subtract([self.window], busy)
        spans = [(e[1], e[1] + e[2], e[0]) for e in self.host_spans()]
        starts = [s[0] for s in spans]
        out = []
        for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            best, best_key = "no host span", None
            hi = bisect.bisect_right(starts, g1)
            for s0, s1, name in spans[:hi]:
                cover = min(s1, g1) - max(s0, g0)
                if cover <= 0.5 * (g1 - g0):
                    continue
                key = (s1 - s0)             # innermost: the shortest
                if best_key is None or key < best_key:
                    best, best_key = name, key
            out.append([best, (g1 - g0) / 1e9])
        return out
