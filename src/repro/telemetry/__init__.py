"""Exchange telemetry: counters, tracing, estimation, reports (DESIGN §14).

Layers, bottom up:

  ``taps``       trace-time collector — on-device counters out of the
                 jitted step as extra outputs (donation/bit-identity safe)
  ``counters``   mask-derived delivery counts, divisor stats, norms
  ``estimator``  per-link effective-p EWMA + theory-drift monitor
  ``trace``      Chrome-trace span buffer + schema validation
  ``sinks``      JSONL / in-memory ring record sinks
  ``record``     JSON-ready step records + the RunHistory container
  ``registry``   the per-run Telemetry object tying it all together
  ``timing``     the unified bench timer (time_fn / wallclock)
"""
from repro.telemetry.record import RunHistory, make_step_record, to_jsonable
from repro.telemetry.registry import Telemetry, enabled, get_current, \
    set_current
from repro.telemetry.taps import TapCollector, annotate, emit, tap_collector
from repro.telemetry.timing import time_fn, wallclock
from repro.telemetry.trace import TraceBuffer, validate_chrome_trace

__all__ = [
    "RunHistory", "make_step_record", "to_jsonable",
    "Telemetry", "enabled", "get_current", "set_current",
    "TapCollector", "annotate", "emit", "tap_collector",
    "time_fn", "wallclock",
    "TraceBuffer", "validate_chrome_trace",
]
