"""Share of the time to first token spent waiting for admission, in %:
over the requests whose first prefill ran in the traced window (the
engine's ``serve.prefill`` spans with ``first`` 1), the summed wait from
the request's due time to its prefill's start (``wait_ms``), over that
wait plus the prefill span (dispatch, scatter and the first token's
read). Re-prefills after preemption are left out."""


def read(trace, info, peaks):
    w0, w1 = trace.window
    wait_ms = total_ms = 0.0
    for e in trace.host_spans("serve.prefill"):
        args = e[3]
        if "wait_ms" not in args or float(args.get("first", 0)) != 1:
            continue
        if e[1] < w0 or e[1] + e[2] > w1:
            continue
        wait_ms += float(args["wait_ms"])
        total_ms += float(args["wait_ms"]) + e[2] / 1e6
    return 100.0 * wait_ms / total_ms if total_ms > 0 else None
