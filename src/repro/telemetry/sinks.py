"""Pluggable step-record sinks: JSONL stream and ring buffer.

A *sink* consumes the structured per-step records the registry emits.
Protocol (duck-typed, no registration):

    write(record: dict) -> None    # record is already JSON-serialisable
    close() -> None                # flush/teardown; idempotent

The registry fans every record out to all attached sinks, so a run can
stream JSONL to disk and keep the last k steps in memory for the report
at once.
"""
from __future__ import annotations

import json
from collections import deque
from typing import Any, Dict, List, Optional


class JsonlSink:
    """One JSON object per line; append-streamed so a crashed run still
    leaves every completed step on disk."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w")

    def write(self, record: Dict[str, Any]) -> None:
        self._f.write(json.dumps(record) + "\n")
        self._f.flush()

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


class MemorySink:
    """Bounded ring buffer of the most recent records (capacity=None keeps
    everything — the report renderer's source)."""

    def __init__(self, capacity: Optional[int] = None):
        self.records: deque = deque(maxlen=capacity)

    def write(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass

    def tail(self, k: int) -> List[Dict[str, Any]]:
        return list(self.records)[-k:]


def close_all(sinks) -> None:
    for s in sinks:
        s.close()
