"""HBM bandwidth share of the paged decode rounds, in %, from the
engine's own ``serve.round`` spans: each span's counters give the bytes
its round needs (``steps`` x the weights, and the K/V of ``kv_reads``
positions read and ``tokens`` written), over the device busy time inside
the runs of the span's ``program`` in the window, each run taken by the
span whose start lies nearest its own, and the HBM peak. Not the span a
run starts in: on TPU v5e profiles a round's run started up to 0.96 ms
before the host dispatched it (the alignment of the device clock to the
host's moved by about 1 ms between profiles), so before its span's start
wherever less host work than that precedes the dispatch. The byte
model is ``hbm_share.decode``'s; no program is matched by a fixed
name."""
import bisect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce as tr  # noqa: E402

COUNTERS = ("program", "steps", "tokens", "kv_reads")


def _nearest(starts, t):
    i = bisect.bisect_left(starts, t)
    near = [j for j in (i - 1, i) if 0 <= j < len(starts)]
    return min(near, key=lambda j: abs(starts[j] - t))


def read(trace, info, peaks):
    w, kv = info.get("weight_bytes"), info.get("kv_bytes_per_token")
    spans = [e for e in trace.host_spans("serve.round")
             if all(k in e[3] for k in COUNTERS)]
    if w is None or kv is None or not spans or not trace.devices:
        return None
    by_prog = {}
    for e in spans:
        by_prog.setdefault(str(e[3]["program"]), []).append(e)
    nbytes = busy_s = 0.0
    for d in trace.devices:
        picked, taken = [], set()
        for prog, evs in by_prog.items():
            starts = [e[1] for e in evs]
            for r in trace.module_runs(lambda m, p=prog: m == p, d):
                j = _nearest(starts, r[0])
                picked.append(r)
                if (prog, j) not in taken:
                    taken.add((prog, j))
                    a = evs[j][3]
                    nbytes += (float(a["steps"]) * w + kv
                               * (float(a["kv_reads"]) + float(a["tokens"])))
        busy = trace.busy_intervals(d)
        busy_s += tr.length(tr.subtract(busy, tr.subtract(
            busy, tr.union(picked)))) / 1e9
    if busy_s <= 0:
        return None
    return 100.0 * nbytes / (busy_s * peaks["hbm_bytes_per_s"])
