"""HBM bandwidth share of the paged decode rounds, in %: the bytes a round
needs (weights once per token step, the K/V of the live positions, the
pool writes; ``_decode_rounds``) times the rounds in the traced window,
over the rounds' device time and the HBM peak."""
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
    return 100.0 * per[0] * n / (secs * peaks["hbm_bytes_per_s"])
