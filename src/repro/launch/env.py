"""Host-perf environment preamble (DESIGN.md §16, SNIPPETS exemplars).

Multi-host-on-CPU parity tests, benches and dry-runs need the same three
pieces of host hygiene every launch used to hand-set (or forget):

  * ``--xla_force_host_platform_device_count=N`` — one XLA host device
    per simulated worker, derived from ``--workers`` instead of copied by
    hand (stale counts silently serialise the mesh);
  * step-marker flags so host profiles attribute time to training steps;
  * tcmalloc: ``LD_PRELOAD`` when the library is present (glibc malloc
    fragments badly under XLA's large transient allocations) plus a
    large-alloc report threshold high enough to keep it quiet.

This module must stay importable *before* jax — XLA_FLAGS are read once
at backend init — so it imports nothing heavy. It also places JAX's
persistent compilation cache (:func:`use_compile_cache`). Two entry
points for the host vars:

  * :func:`apply` — in-process: merge the computed vars into
    ``os.environ`` (call before the first jax import; ``LD_PRELOAD``
    cannot take effect in-process and is left to the shell wrapper);
  * ``python -m repro.launch.env -- <cmd …>`` — emit ``export K=V``
    lines for ``run.sh`` to eval before exec'ing the real command (this
    path does preload tcmalloc).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence

# keep tcmalloc quiet about XLA's perfectly-normal giant buffers
# (exemplar value: reports only above 60 GB)
TCMALLOC_REPORT_THRESHOLD = "60000000000"

# host-profile step attribution: mark step boundaries at the entry of the
# top-level jitted computation
STEP_MARKER_FLAG = "--xla_step_marker_location=STEP_MARK_AT_ENTRY"

_TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib64/libtcmalloc*.so*",
    "/usr/lib/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
)


#: the checkout root (this file is src/repro/launch/env.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — a fixed path, since the path is part of
    the cache key and a directory that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def find_tcmalloc() -> Optional[str]:
    """Path of an installed tcmalloc shared library, or None. Prefers the
    minimal variant (no heap profiler hooks) like the exemplar run.sh."""
    hits: List[str] = []
    for pat in _TCMALLOC_GLOBS:
        hits.extend(glob.glob(pat))
    if not hits:
        return None
    hits.sort(key=lambda p: ("minimal" not in p, len(p)))
    return hits[0]


def merge_xla_flag(flags: str, flag: str) -> str:
    """``flag`` ("--name=value") merged into an XLA_FLAGS string: replaces
    an existing ``--name=…`` entry, appends otherwise — idempotent, and
    never stacks duplicate definitions (XLA takes the last one, which
    makes stale hand-set values win silently)."""
    name = flag.split("=", 1)[0]
    kept = [f for f in flags.split() if f.split("=", 1)[0] != name]
    return " ".join(kept + [flag])


def workers_from_argv(argv: Sequence[str]) -> Optional[int]:
    """The ``--workers N`` / ``--workers=N`` value from a command line, or
    None — how ``run.sh`` derives the host device count from the command
    it is about to exec without understanding it."""
    argv = list(argv)
    for i, a in enumerate(argv):
        if a == "--workers" and i + 1 < len(argv):
            try:
                return int(argv[i + 1])
            except ValueError:
                return None
        if a.startswith("--workers="):
            try:
                return int(a.split("=", 1)[1])
            except ValueError:
                return None
    return None


def host_env(workers: Optional[int] = None,
             devices: Optional[int] = None,
             tcmalloc: bool = True,
             step_markers: bool = True,
             base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The host-perf environment as a dict (pure — nothing is mutated).

    ``devices`` (or, when unset, ``workers``) sizes
    ``--xla_force_host_platform_device_count``; flags merge into
    ``base``'s existing XLA_FLAGS (default ``os.environ``) rather than
    clobbering them. ``tcmalloc=True`` adds LD_PRELOAD + the report
    threshold when the library exists — meaningful only when a shell
    exports the result before process start."""
    base = dict(os.environ if base is None else base)
    out: Dict[str, str] = {}
    xla = base.get("XLA_FLAGS", "")
    n = devices if devices is not None else workers
    if n is not None:
        if int(n) < 1:
            raise ValueError(f"need >= 1 host devices, got {n}")
        xla = merge_xla_flag(
            xla, f"--xla_force_host_platform_device_count={int(n)}")
    if step_markers:
        xla = merge_xla_flag(xla, STEP_MARKER_FLAG)
    if xla:
        out["XLA_FLAGS"] = xla
    if tcmalloc:
        lib = find_tcmalloc()
        if lib is not None:
            pre = base.get("LD_PRELOAD", "")
            if lib not in pre.split(":"):
                out["LD_PRELOAD"] = f"{pre}:{lib}".strip(":")
            out["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = \
                TCMALLOC_REPORT_THRESHOLD
    return out


def apply(workers: Optional[int] = None, devices: Optional[int] = None,
          step_markers: bool = True) -> Dict[str, str]:
    """Merge the host-perf vars into ``os.environ`` for this process.
    Call BEFORE the first jax import (XLA reads XLA_FLAGS once at backend
    init). LD_PRELOAD is skipped — the loader resolved symbols long ago;
    preloading is ``run.sh``'s job. Returns what was set."""
    env = host_env(workers=workers, devices=devices, tcmalloc=False,
                   step_markers=step_markers)
    os.environ.update(env)
    return env


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        description="emit `export K=V` host-perf preamble lines for "
                    "run.sh to eval (everything after `--` is the "
                    "command about to run; its --workers sizes the host "
                    "device count)")
    ap.add_argument("--workers", type=int, default=None,
                    help="host device count (overrides the command's "
                         "own --workers)")
    ap.add_argument("--no-tcmalloc", action="store_true")
    ap.add_argument("cmd", nargs="*", help="the command run.sh will exec")
    args = ap.parse_args(argv)
    n = args.workers if args.workers is not None \
        else workers_from_argv(args.cmd)
    env = host_env(workers=n, tcmalloc=not args.no_tcmalloc)
    for k, v in sorted(env.items()):
        sys.stdout.write(f"export {k}={v!r}\n")


if __name__ == "__main__":
    main()
