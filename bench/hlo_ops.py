"""Which device ops of a compiled program came from which Python code.

A compiled program's text (``jax.stages.Compiled.as_text()``) names every
instruction as the profiler's device trace names its op (``fusion.336``,
``masked_avg_grid_pallas.6``), and each instruction's metadata may carry
a ``stack_frame_id`` into the tables at the top of the text::

    FileNames          1 "/path/src/repro/core/rps.py"
    FunctionNames      1 "rps_exchange_global"
    FileLocations      1 {file_name_id=1 function_name_id=1 line=.. ...}
    StackFrames        1 {file_location_id=1 parent_frame_id=..}

so an op can be traced back to the functions that built it. A fusion
whose own metadata has no frame is taken by the root of the computation
it calls. Only instructions of computations that run as device ops are
returned (the bodies of fusions are not: the trace shows the fusion).
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

Frame = Tuple[str, str]                 # (file name, function name)

_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_TABLE = re.compile(r"^(\d+) (.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_OPCODE = re.compile(r"^[^(]*?\s([a-z][\w\-]*)\(")


def _tables(lines: List[str]) -> Dict[str, Dict[int, str]]:
    """The four tables at the top of the text, each ``{id: entry}``; a
    table ends at the first blank line."""
    out: Dict[str, Dict[int, str]] = {}
    cur = None
    for ln in lines:
        if ln in _TABLES:
            cur = out.setdefault(ln, {})
        elif not ln.strip():
            cur = None
        elif cur is not None:
            m = _TABLE.match(ln)
            if m is not None:
                cur[int(m.group(1))] = m.group(2)
    return out


class Program:
    """The instructions of one compiled program and their call stacks."""

    def __init__(self, text: str):
        lines = text.splitlines()
        t = _tables(lines)
        files = {k: v.strip('"') for k, v in t.get("FileNames", {}).items()}
        funcs = {k: v.strip('"')
                 for k, v in t.get("FunctionNames", {}).items()}
        locs = {k: dict((a, int(b)) for a, b in _FIELD.findall(v))
                for k, v in t.get("FileLocations", {}).items()}
        self._frames = {k: dict((a, int(b)) for a, b in _FIELD.findall(v))
                        for k, v in t.get("StackFrames", {}).items()}
        self._loc = {k: (files.get(v.get("file_name_id"), ""),
                         funcs.get(v.get("function_name_id"), ""))
                     for k, v in locs.items()}
        # jax 0.9 prints a frame's parent as the parent's id + 1 (so an
        # outermost frame k shows parent k, and 1 shows 1): where any frame
        # names itself, that is the convention; 0 or less is no parent
        self._parent_shift = int(any(
            fr.get("parent_frame_id") == k for k, fr in self._frames.items()))
        # computation -> [(name, opcode, frame id, callee)], root name
        self.computations: Dict[str, list] = {}
        self.roots: Dict[str, str] = {}
        cur = None
        for ln in lines:
            m = _COMP.match(ln)
            if m is not None and not ln.startswith(" "):
                cur = m.group(1)
                self.computations[cur] = []
                continue
            if cur is None:
                continue
            if ln.startswith("}"):
                cur = None
                continue
            m = _INST.match(ln)
            if m is None:
                continue
            root, name, rest = m.groups()
            fid = _FRAME_ID.search(rest)
            call = _CALLS.search(rest)
            op = _OPCODE.match(rest)
            self.computations[cur].append(
                (name, op.group(1) if op else "",
                 int(fid.group(1)) if fid else None,
                 call.group(1) if call else None))
            if root:
                self.roots[cur] = name
        self._by_name = {i[0]: i for insts in self.computations.values()
                         for i in insts}
        fused = {i[3] for insts in self.computations.values() for i in insts
                 if i[1] == "fusion" and i[3] is not None}
        self.device_ops = [i[0] for c, insts in self.computations.items()
                           if c not in fused for i in insts]

    def stack(self, frame_id: Optional[int]) -> List[Frame]:
        """The frames from ``frame_id`` out to the outermost caller."""
        out, seen = [], set()
        while frame_id is not None and frame_id not in seen:
            seen.add(frame_id)
            fr = self._frames.get(frame_id)
            if fr is None:
                break
            out.append(self._loc.get(fr.get("file_location_id"), ("", "")))
            parent = fr.get("parent_frame_id", 0) - self._parent_shift
            frame_id = parent if parent > 0 else None
        return out

    def frame_of(self, name: str) -> Optional[int]:
        """An instruction's own frame, or (a fusion without one) that of
        the root of the computation it calls."""
        seen = set()
        while name in self._by_name and name not in seen:
            seen.add(name)
            _, _, fid, callee = self._by_name[name]
            if fid is not None:
                return fid
            if callee is None or callee not in self.roots:
                return None
            name = self.roots[callee]
        return None

    def ops_where(self, pred: Callable[[List[Frame]], bool]) -> List[str]:
        """The device ops whose call stack satisfies ``pred``."""
        out = []
        for name in self.device_ops:
            fid = self.frame_of(name)
            if fid is not None and pred(self.stack(fid)):
                out.append(name)
        return sorted(out)


def called_from(function: str, file_suffix: str):
    """A predicate: some frame is ``function`` in a file ending in
    ``file_suffix``."""
    def pred(frames: List[Frame]) -> bool:
        return any(fn == function and f.endswith(file_suffix)
                   for f, fn in frames)
    return pred
