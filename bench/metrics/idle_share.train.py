"""Device idle share while training: 1 - (union of device op
intervals) / traced window, mean over the chips, in %."""


def read(trace, info, peaks):
    v = trace.idle_share()
    return None if v is None else 100.0 * v
