"""The benchmark's frame: find a cell by name, check the chip, run the
cell's driver, read the per-layer metrics and print the result line.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration's file, the traffic mix
``bench/traffic/<traffic>.json`` (whose ``driver`` names the module under
``bench/drivers/``) and one reader ``bench/metrics/<metric>.py`` per
per-layer metric. Adding a cell, a configuration, a mix or a metric adds
files and entries; nothing here changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: traces and other run output, inside the checkout
OUT_DIR = os.path.join(ROOT, ".bench_out")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed32(seed: int) -> int:
    """A 31-bit seed for JAX and NumPy from any whole number (the driver's
    seeds pass 32 bits)."""
    import numpy as np
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               >> 1)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic mix's parameters
    end_to_end: List[dict]  # the cell's end-to-end metrics
    per_layer: List[dict]   # the cell's per-layer metrics


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per)


# ---------------------------------------------------------------------------
# what a driver gets and gives
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Result:
    metrics: Dict[str, float]           # end-to-end, by name
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    info: Dict[str, Any]                # what the per-layer readers read
    trace: Any = None                   # trace_reduce.Trace of --trace 1
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) and
    persistent-cache misses, separately before and inside the window."""

    def __init__(self):
        import jax
        self.window = False
        self.counts = {"setup_compiles": 0, "setup_cache_misses": 0,
                       "window_compiles": 0, "window_cache_misses": 0}

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.counts[("window" if self.window else "setup")
                            + "_compiles"] += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.counts[("window" if self.window else "setup")
                            + "_cache_misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                           # process start, time.time()
    devices: list
    compiles: CompileCounter
    out_dir: str
    peaks: Optional[dict]

    def mark_window(self) -> float:
        """Call where the measured window starts; returns setup_s."""
        self.compiles.window = True
        self.t_window = time.time()
        return self.t_window - self.t0


def profile_start(ctx: Context) -> str:
    import jax
    d = os.path.join(ctx.out_dir, "trace")
    if os.path.isdir(d):
        import shutil
        shutil.rmtree(d)
    jax.profiler.start_trace(d)
    return d


def profile_stop(ctx: Context, log_dir: str, window_span: str):
    """Stop the profiler and reduce its trace, the window being the host
    span ``window_span`` that the driver opened around the traced work."""
    import jax
    import trace_reduce as tr
    jax.profiler.stop_trace()
    data = tr.load_xplane(tr.find_xplane(log_dir))
    spans = [e for pl in data["planes"] if pl["name"] == tr.HOST_PLANE
             for ln in pl["lines"] for e in ln["events"]
             if e[0] == window_span]
    if not spans:
        raise RuntimeError(f"the trace holds no {window_span!r} span")
    s = spans[-1]
    return tr.Trace(data, window=(s[1], s[1] + s[2]))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _number(x: float):
    return x if isinstance(x, int) else float(x)


def run(argv: Optional[List[str]] = None, *, t0: Optional[float] = None,
        require_chip: bool = True, root: str = ROOT,
        cell_override: Optional[Callable[[Cell], Cell]] = None) -> int:
    """The command. ``require_chip=False`` and ``cell_override`` exist for
    the benchmark's own tests, which drive a cell at a small size on the
    CPU."""
    t0 = time.time() if t0 is None else t0
    ap = argparse.ArgumentParser(description="chip benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, root)
    if cell_override is not None:
        cell = cell_override(cell)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        log(f"bench: no program under {root}/src; nothing was run")
        return 2
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    # the TPU runtime's logs go inside the checkout, not to /tmp
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT_DIR, "tpu_logs"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    platform = devices[0].platform
    if require_chip and platform != "tpu":
        log(f"bench: no TPU (JAX found {platform}); nothing was run")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}; nothing was run")
        return 2
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(root, "src"))
    import peaks as peaks_lib
    kind = devices[0].device_kind
    peak = peaks_lib.peaks(kind) if require_chip else None
    os.makedirs(OUT_DIR, exist_ok=True)
    ctx = Context(cell=cell, seed=int(args.seed), seconds=args.seconds,
                  trace=bool(args.trace), t0=t0,
                  devices=devices[:cell.chips], compiles=CompileCounter(),
                  out_dir=OUT_DIR, peaks=peak)
    driver = load_module(os.path.join(BENCH, "drivers",
                                      cell.traffic["driver"] + ".py"),
                         "bench_driver_" + cell.traffic["driver"])
    res: Result = driver.run(ctx)

    log("compiles: " + json.dumps(ctx.compiles.counts))
    for k, v in res.notes.items():
        log(f"{k}: {json.dumps(v, default=str)}")
    device = {"platform": platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": res.memory_peak_bytes}
    out: Dict[str, Any] = {"correct": res.correct,
                           "attempted": int(res.attempted),
                           "failed": int(res.failed)}
    metrics: Dict[str, Any] = {}
    if not args.trace:
        for m in cell.end_to_end:
            if m["name"] in res.metrics:
                metrics[m["name"]] = {"value": _number(res.metrics[m["name"]]),
                                      "unit": m["unit"]}
    else:
        tr = res.trace
        device["busy_s"] = tr.mean_busy_s()
        device["window_s"] = tr.window_s
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(tr, res.info, ctx.peaks)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_gaps(10)}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {c.name: {"value": _number(c.value),
                              "limit": _number(c.limit)}
                     for c in res.checks}
    for c in res.checks:
        log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return 0
