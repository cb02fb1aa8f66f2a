"""Model FLOP utilisation of the training step, in %: the model FLOPs of
a step counted from shapes (``train_work.step_flops``: 6 x the matrix
product parameters x tokens, plus the causal attention) times the step
runs wholly inside the traced window, over the device busy time inside
those runs and the bf16 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _train_step  # noqa: E402


def read(trace, info, peaks):
    n = len(_train_step.runs(trace, info))
    secs = _train_step.busy_s(trace, info)
    if "step_flops" not in info or n == 0 or secs <= 0:
        return None
    return 100.0 * info["step_flops"] * n / (secs * peaks["bf16_flops_per_s"])
