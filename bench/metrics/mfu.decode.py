"""Model FLOP utilisation of the paged decode rounds, in %: the model
FLOPs of a round's live tokens (``_decode_rounds``) times the rounds in
the traced window, over the rounds' device time and the bf16 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _decode_rounds  # noqa: E402


def read(trace, info, peaks):
    if "decode_module" not in info:
        return None
    per = _decode_rounds.need(info)
    n, secs = _decode_rounds.device_time(trace, info)
    if per is None or n == 0 or secs <= 0:
        return None
    return 100.0 * per[1] * n / (secs * peaks["bf16_flops_per_s"])
