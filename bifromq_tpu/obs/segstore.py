"""Bounded segment-file telemetry store (ISSUE 8 tentpole, part 3).

The push exporter ships telemetry OUT of the process; nothing so far
keeps it ON the box. For post-hoc analysis after a TPU session ends —
"what did the compile ledger and the stage split look like in the
minutes before the device dropped" — profile records, compile events and
slow spans persist into a directory of JSON-lines **segment files** with
hard retention:

- records append to ``<prefix>-<seq>.jsonl``; when the active segment
  exceeds ``max_segment_bytes`` it is sealed and a new one opens;
- at most ``max_segments`` segments are retained — the oldest are
  deleted, so disk usage is bounded by ``max_segments ×
  max_segment_bytes`` no matter how long the process runs;
- a restart re-opens the same directory, continues the sequence
  numbering, and re-applies retention — surviving records stay readable
  (``read()``) across process generations.

Writes go through the caller's thread (the ObsHub advisory tick flushes
in batches); a lock keeps concurrent appenders safe. Torn final lines
from a crash are skipped on read, never propagated.
"""

from __future__ import annotations

import json
import os
import re
import threading
from typing import Dict, Iterable, List, Optional


class SegmentStore:
    def __init__(self, directory: str, *, prefix: str = "obs",
                 max_segment_bytes: int = 1 << 20,
                 max_segments: int = 8) -> None:
        if max_segment_bytes <= 0 or max_segments <= 0:
            raise ValueError("segment size and count must be positive")
        self.dir = directory
        self.prefix = prefix
        self.max_segment_bytes = max_segment_bytes
        self.max_segments = max_segments
        self._lock = threading.Lock()
        self._pat = re.compile(
            rf"^{re.escape(prefix)}-(\d+)\.jsonl$")
        os.makedirs(directory, exist_ok=True)
        # restart: continue numbering after the highest surviving segment
        existing = self._segments()
        self._seq = existing[-1][0] if existing else 0
        self.records_appended = 0
        self.rotations = 0
        self.segments_dropped = 0
        self._enforce_retention()

    # ---------------- segment bookkeeping ----------------------------------

    def _segments(self) -> List[tuple]:
        """Sorted [(seq, path)] of surviving segments."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        for name in names:
            m = self._pat.match(name)
            if m:
                out.append((int(m.group(1)), os.path.join(self.dir, name)))
        out.sort()
        return out

    def _active_path(self) -> str:
        return os.path.join(self.dir, f"{self.prefix}-{self._seq}.jsonl")

    def _rotate_if_needed(self) -> bool:
        path = self._active_path()
        try:
            size = os.path.getsize(path)
        except OSError:
            size = 0
        if size >= self.max_segment_bytes:
            self._seq += 1
            self.rotations += 1
            return True
        return False

    def _enforce_retention(self) -> None:
        segs = self._segments()
        while len(segs) > self.max_segments:
            seq, path = segs.pop(0)
            try:
                os.remove(path)
                self.segments_dropped += 1
            except OSError:
                break

    # ---------------- append / read ----------------------------------------

    def append(self, record: Dict) -> None:
        self.append_many((record,))

    def append_many(self, records: Iterable[Dict]) -> int:
        """Append records as JSON lines; returns how many were written.
        One open+write per batch — the flush tick batches, so the store
        never holds a file handle across ticks (rotation and external
        cleanup stay trivial)."""
        lines = [json.dumps(r, default=str) for r in records]
        if not lines:
            return 0
        with self._lock:
            rotated = self._rotate_if_needed()
            with open(self._active_path(), "a", encoding="utf-8") as f:
                f.write("\n".join(lines) + "\n")
            self.records_appended += len(lines)
            if rotated:
                # enforce AFTER the new active segment exists, so the
                # retained count includes it (not max_segments + 1)
                self._enforce_retention()
        return len(lines)

    def read(self, *, limit: int = 0,
             type: Optional[str] = None) -> List[Dict]:  # noqa: A002
        """All surviving records oldest-first (optionally only one
        ``type``); a torn final line (crash mid-write) is skipped."""
        out: List[Dict] = []
        with self._lock:
            segs = self._segments()
        for _, path in segs:
            try:
                with open(path, encoding="utf-8") as f:
                    for line in f:
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue
                        if type is None or rec.get("type") == type:
                            out.append(rec)
            except OSError:
                continue
        return out[-limit:] if limit > 0 else out

    def snapshot(self) -> dict:
        segs = self._segments()
        return {
            "dir": self.dir,
            "segments": len(segs),
            "active_seq": self._seq,
            "bytes": sum(os.path.getsize(p) for _, p in segs
                         if os.path.exists(p)),
            "max_segment_bytes": self.max_segment_bytes,
            "max_segments": self.max_segments,
            "records_appended": self.records_appended,
            "rotations": self.rotations,
            "segments_dropped": self.segments_dropped,
        }
