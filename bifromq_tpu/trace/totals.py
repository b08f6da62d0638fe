"""Window totals: per-name running sums in one-second slices.

Every boundary a span closes (and every counter recorded beside one)
adds ``(n, total_ns)`` to the slice of the second it ENDED in, on
CLOCK_MONOTONIC. A reader that was not there when a window opened can
take the window afterwards: ``between(t0_ns, t1_ns)`` sums the whole
slices from the one holding ``t0_ns`` up to (not including) the one
holding ``t1_ns``, for as long as the slices are kept (``KEEP_S``
seconds, at least 240).

One dict lookup and three adds per record; no lock (the serving loop is
one thread; a racing writer from a helper thread can at worst lose one
sample of telemetry, never corrupt a slice).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

NS = 1_000_000_000


class WindowTotals:
    KEEP_S = 256        # slices kept: a window stays readable this long

    def __init__(self, clock: Callable[[], int] = time.monotonic_ns) -> None:
        self.clock = clock
        # slot i holds second ``_secs[i]``'s sums: name -> [n, ns, max_ns]
        self._secs: List[int] = [-1] * self.KEEP_S
        self._slots: List[Dict[str, list]] = [{} for _ in range(self.KEEP_S)]
        self._cur_sec, self._cur = -1, {}       # the slice last written

    def add(self, name: str, n: int, total_ns: int,
            at_ns: Optional[int] = None) -> None:
        """Add ``n`` events lasting ``total_ns`` together to the slice of
        ``at_ns`` (default: now). A counter passes ``total_ns`` 0."""
        sec = (self.clock() if at_ns is None else at_ns) // NS
        if sec == self._cur_sec:
            slot = self._cur
        else:
            slot = self._open(sec)
        cell = slot.get(name)
        if cell is None:
            slot[name] = [n, total_ns, total_ns]
        else:
            cell[0] += n
            cell[1] += total_ns
            if total_ns > cell[2]:
                cell[2] = total_ns

    def _open(self, sec: int) -> Dict[str, list]:
        i = sec % self.KEEP_S
        if self._secs[i] != sec:
            self._secs[i] = sec
            self._slots[i] = {}
        self._cur_sec, self._cur = sec, self._slots[i]
        return self._cur

    def _slices(self, t0_ns: int, t1_ns: int):
        s0 = max(t0_ns // NS, self.clock() // NS - self.KEEP_S + 1)
        for sec in range(s0, t1_ns // NS):
            i = sec % self.KEEP_S
            if self._secs[i] == sec:
                yield self._slots[i]

    def between(self, t0_ns: int, t1_ns: int) -> Dict[str, Tuple[int, float]]:
        """``{name: (n, total_s)}`` over the whole slices of the window.
        Slices older than ``KEEP_S`` seconds are gone and count nothing."""
        out: Dict[str, list] = {}
        for slot in self._slices(t0_ns, t1_ns):
            for name, (n, ns, _mx) in list(slot.items()):
                cell = out.get(name)
                if cell is None:
                    out[name] = [n, ns]
                else:
                    cell[0] += n
                    cell[1] += ns
        return {name: (n, ns / NS) for name, (n, ns) in out.items()}

    def peaks(self, t0_ns: int, t1_ns: int) -> Dict[str, float]:
        """``{name: longest single record in seconds}`` over the same
        slices (``loop.lag`` reads its worst stall here)."""
        out: Dict[str, int] = {}
        for slot in self._slices(t0_ns, t1_ns):
            for name, (_n, _ns, mx) in list(slot.items()):
                if mx > out.get(name, -1):
                    out[name] = mx
        return {name: mx / NS for name, mx in out.items()}

    def clear(self) -> None:
        self._secs = [-1] * self.KEEP_S
        self._slots = [{} for _ in range(self.KEEP_S)]
        self._cur_sec, self._cur = -1, {}
