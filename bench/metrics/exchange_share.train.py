"""Share of the training step's device time spent in the RPS exchange, in
%: the device time of the step's exchange ops (the ops its compiled text
traces back to the simulator's exchange, ``info["exchange_ops"]``, the
masked-average kernel among them) inside the step runs wholly inside the
traced window, over the device busy time inside those runs."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _train_step  # noqa: E402


def read(trace, info, peaks):
    names = set(info.get("exchange_ops") or ())
    runs = _train_step.runs(trace, info)
    busy = _train_step.busy_s(trace, info)
    if not names or not runs or busy <= 0:
        return None
    secs = trace.op_seconds(lambda s: s in names, within=runs)
    return 100.0 * secs / busy if secs > 0 else None
