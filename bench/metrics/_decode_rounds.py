"""What the paged decode rounds of a traced window needed, from the lanes'
positions and tokens left that the driver logged per round: per token
step, the weights once, the K/V of every live position and one K/V write
per live lane (bytes); the matmuls of every live lane and causal
attention over its live positions (FLOPs)."""


def need(info):
    rounds = info.get("rounds") or []
    if not rounds:
        return None
    kv = info["kv_bytes_per_token"]
    nbytes = flops = 0.0
    for pos, n_left in rounds:
        for s in range(info["chunk"]):
            live = [p + s for p, k in zip(pos, n_left) if k > s]
            if not live:
                continue
            ctx = sum(p + 1 for p in live)
            nbytes += info["weight_bytes"] + kv * (ctx + len(live))
            flops += (2.0 * info["matmul_params"] * len(live)
                      + info["attn_flops_per_ctx_token"] * ctx)
    return nbytes / len(rounds), flops / len(rounds)


def device_time(trace, info):
    """Runs of the round program wholly inside the window, and the device
    busy time inside them."""
    mod = info["decode_module"]
    runs = trace.module_runs(lambda m: m == mod)
    return len(runs), trace.module_op_seconds(lambda m: m == mod)
