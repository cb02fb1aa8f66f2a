"""The masked-average kernel's share of its roofline, in %: the HBM bytes
one exchange round's masked average needs by the algorithm
(``train_work.masked_avg_bytes``: the n stacked copies read once, the
delivery flags, the averages written once) times the step runs wholly
inside the traced window, over the kernel's device time inside those runs
and the HBM peak. Memory bound: a multiply-add per element read is about
1.3 FLOPs a byte of bf16, where the peaks of a v5e cross at 240."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _train_step  # noqa: E402
import trace_reduce as tr  # noqa: E402


def read(trace, info, peaks):
    kernel = info.get("kernel")
    runs = _train_step.runs(trace, info)
    if kernel is None or "masked_avg_bytes" not in info or not runs:
        return None
    secs = trace.op_seconds(lambda s: tr.op_kind(s) == kernel, within=runs)
    if secs <= 0:
        return None
    return 100.0 * info["masked_avg_bytes"] * len(runs) \
        / (secs * peaks["hbm_bytes_per_s"])
