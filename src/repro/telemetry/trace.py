"""Chrome-trace/Perfetto span buffer + schema validation.

Spans cover the host-side phases of a run — plan build, mask/gather,
collective dispatch, decode, recovery, bench sections — as complete
("ph": "X") events in the Trace Event Format that chrome://tracing and
https://ui.perfetto.dev load directly. Device-side phase attribution
rides on ``jax.named_scope`` inside the jitted step (``core/rps.py``):
those names land in XLA's own profiler timeline on TPU; this buffer is
the host view that works everywhere, no profiler needed.

Events are stamped on the wall clock (µs since the epoch), the clock of
the JAX profiler's host events, and every span is also opened as a
``jax.profiler.TraceAnnotation`` with the same args: a buffer's Chrome
trace and an ``.xplane.pb`` of the same run overlay.

``python -m repro.telemetry.trace --validate FILE`` exits non-zero on a
malformed trace — the CI schema gate.
"""
from __future__ import annotations

import contextlib
import json
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional


class SpanArgs(dict):
    """The args of an open span. ``set_metadata(**more)`` adds args known
    only as the span ends, in the Chrome event and in the profiler's
    annotation alike (the name is TraceAnnotation's own method, so code
    that records spans can hold either)."""

    def __init__(self, args: Dict[str, Any], ann=None):
        super().__init__(args)
        self._ann = ann

    def set_metadata(self, **more) -> None:
        self.update(more)
        if self._ann is not None:
            self._ann.set_metadata(**more)


class _NoArgs:
    def set_metadata(self, **more) -> None:
        pass


#: the span of a run that records none: one shared no-op context manager
NULL_SPAN = contextlib.nullcontext(_NoArgs())


class TraceBuffer:
    """Accumulates Trace Event Format events (timestamps in µs)."""

    def __init__(self, pid: int = 0):
        self.pid = pid
        self.events: List[Dict[str, Any]] = []

    def _now_us(self) -> float:
        return time.time_ns() / 1e3

    def now_us(self) -> float:
        """Buffer-clock timestamp (wall clock, µs since the epoch) for
        callers that record a span's start and emit it later via
        :meth:`complete` (e.g. per-request serving spans that straddle
        many decode rounds)."""
        return self._now_us()

    def complete(self, name: str, ts_us: float, dur_us: float,
                 tid: int = 0, **args) -> None:
        """Append a complete ("X") event with explicit start/duration —
        the non-contextmanager form of :meth:`span`, for intervals whose
        endpoints are separate host events (per-request serving latency:
        admit → finish spans interleave across requests, so no ``with``
        block can bracket one)."""
        ev = {"name": name, "ph": "X", "ts": float(ts_us),
              "dur": max(float(dur_us), 0.0), "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = dict(args)
        self.events.append(ev)

    @contextmanager
    def span(self, name: str, tid: int = 0, **args):
        """Time a host-side phase; also opens a JAX profiler
        TraceAnnotation with the same name and args, so device timelines
        line up when a profiler session is active. Yields the span's
        :class:`SpanArgs`."""
        t0 = self._now_us()
        ann = _profiler_annotation(name, args)
        held = SpanArgs(args, ann)
        try:
            yield held
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            ev = {"name": name, "ph": "X", "ts": t0,
                  "dur": self._now_us() - t0, "pid": self.pid, "tid": tid}
            if held:
                ev["args"] = dict(held)
            self.events.append(ev)

    def instant(self, name: str, tid: int = 0, **args) -> None:
        ev = {"name": name, "ph": "i", "ts": self._now_us(),
              "pid": self.pid, "tid": tid, "s": "g"}
        if args:
            ev["args"] = dict(args)
        self.events.append(ev)

    def counter(self, name: str, values: Dict[str, float],
                tid: int = 0) -> None:
        self.events.append({"name": name, "ph": "C", "ts": self._now_us(),
                            "pid": self.pid, "tid": tid,
                            "args": {k: float(v) for k, v in values.items()}})

    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


def _profiler_annotation(name: str, args: Dict[str, Any]):
    """Enter a jax.profiler.TraceAnnotation when available (it is on
    every jax we target, but keep the host path profiler-optional). The
    profiler splits an arg's value at commas: join lists otherwise."""
    try:
        import jax.profiler as _prof
        ann = _prof.TraceAnnotation(name, **args)
        ann.__enter__()
        return ann
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Schema validation (the CI gate)
# ---------------------------------------------------------------------------

_PHASES = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s", "t", "f"}


def validate_chrome_trace(obj: Any) -> List[str]:
    """Structural check of a Trace Event Format object; returns a list of
    problems (empty = valid). Covers what chrome://tracing actually
    requires: a traceEvents array of dicts, each with a string name, a
    known phase, numeric ts (and numeric non-negative dur on "X"), and
    JSON-serialisable args."""
    errs: List[str] = []
    if isinstance(obj, list):
        events = obj                       # the bare-array variant is legal
    elif isinstance(obj, dict):
        events = obj.get("traceEvents")
        if not isinstance(events, list):
            return ["top-level object lacks a 'traceEvents' array"]
    else:
        return [f"trace must be an object or array, got {type(obj).__name__}"]
    for k, ev in enumerate(events):
        where = f"event[{k}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        if not isinstance(ev.get("name"), str) and ev.get("ph") != "M":
            errs.append(f"{where}: missing string 'name'")
        ph = ev.get("ph")
        if ph not in _PHASES:
            errs.append(f"{where}: unknown phase {ph!r}")
        if not isinstance(ev.get("ts"), (int, float)):
            errs.append(f"{where}: missing numeric 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: 'X' event needs numeric dur >= 0")
        args = ev.get("args")
        if args is not None:
            try:
                json.dumps(args)
            except (TypeError, ValueError):
                errs.append(f"{where}: args not JSON-serialisable")
    return errs


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Validate a Chrome-trace JSON file")
    ap.add_argument("--validate", metavar="FILE", required=True)
    ns = ap.parse_args(argv)
    try:
        with open(ns.validate) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"INVALID {ns.validate}: {e}")
        return 1
    errs = validate_chrome_trace(obj)
    if errs:
        print(f"INVALID {ns.validate}:")
        for e in errs[:20]:
            print(f"  - {e}")
        return 1
    n = len(obj["traceEvents"]) if isinstance(obj, dict) else len(obj)
    print(f"OK {ns.validate}: {n} events")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
