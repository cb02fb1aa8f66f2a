"""Share of the device's busy time spent in prefill, in %: the busy time
inside runs of the prefill and prefill-scatter programs, over all busy
time in the traced window."""


def read(trace, info, peaks):
    mods = tuple(info.get("prefill_modules", ()))
    busy = trace.mean_busy_s()
    if not mods or busy <= 0:
        return None
    return 100.0 * trace.module_op_seconds(lambda m: m in mods) / busy
