"""Device idle time under the serving engine's host work, in % of the
traced window: the idle intervals (no op running) that lie under the
engine's ``serve.step`` spans (one working loop iteration, which encloses
every other engine span but ``serve.wait``, the sleep while no request
is due), mean over the chips. Nothing where the engine records no
``serve.step`` spans."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce as tr  # noqa: E402


def read(trace, info, peaks):
    steps = trace.host_spans("serve.step")
    if not steps or not trace.devices or trace.window_s <= 0:
        return None
    under = tr.union(tr.clip(((e[1], e[1] + e[2]) for e in steps),
                             trace.window))
    idle_ns = 0.0
    for d in trace.devices:
        idle = tr.subtract([trace.window], trace.busy_intervals(d))
        idle_ns += tr.length(tr.subtract(idle, tr.subtract(idle, under)))
    return 100.0 * idle_ns / len(trace.devices) / (trace.window_s * 1e9)
