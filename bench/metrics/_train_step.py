"""What the training readers share: the runs of the step program that lie
wholly inside the traced window, and the device busy time inside them."""


def runs(trace, info):
    mod = info.get("step_module")
    if mod is None:
        return []
    return trace.module_runs(lambda m: m == mod)


def busy_s(trace, info) -> float:
    mod = info.get("step_module")
    if mod is None:
        return 0.0
    return trace.module_op_seconds(lambda m: m == mod)
