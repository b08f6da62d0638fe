"""Environment provider + memory-usage probe (≈ base-env).

``EnvProvider`` centralizes executor/thread creation (the reference's
IEnvProvider/NettyEnv picks event loops and names threads); ``MemUsage``
is the back-pressure probe (MemUsage.java): the broker's
conditional-reject stage consults ``under_pressure()`` before accepting
connections/ingress, mirroring ConditionalRejectHandler +
IngressSlowDownDirectMemoryUsage.
"""

from __future__ import annotations

import concurrent.futures
import os
import threading
import time
from typing import Optional


def env_float(name: str, default: float) -> float:
    """Float env knob with a default on unset/blank/garbage — the one
    shared parser for BIFROMQ_* tunables (obs, clusterview, resilience),
    so fallback behavior cannot diverge between copies."""
    raw = os.environ.get(name, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


# ISSUE 10 (graftcheck R3): every BIFROMQ_* knob resolves through these
# helpers, lazily at first use — NEVER at module import (the PR 7 bug
# class: SHEDDER/INGEST_GATE knobs frozen before the embedding broker or
# a monkeypatching test could set its env). This module is the single
# os.environ read site the analyzer exempts.

def env_int(name: str, default: int) -> int:
    """Int env knob, same unset/blank/garbage fallback as env_float."""
    raw = os.environ.get(name, "").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def env_str(name: str, default: str = "") -> str:
    """Stripped string env knob; unset/blank yields the default."""
    return os.environ.get(name, "").strip() or default


def env_opt_str(name: str) -> Optional[str]:
    """Stripped string knob, or None when unset/blank (for callers that
    must distinguish 'absent' from any concrete value)."""
    raw = os.environ.get(name, "").strip()
    return raw or None


def env_opt_float(name: str) -> Optional[float]:
    """Float knob, or None when unset/blank/garbage (tracer-style
    optional thresholds)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


_FALSY = ("0", "off", "false", "no")
_TRUTHY = ("1", "on", "true", "yes")


def env_bool(name: str, default: bool) -> bool:
    """Boolean env knob: explicit falsy/truthy spellings win, anything
    else (unset, blank, garbage) yields the default — so a typo'd value
    can never silently flip a kill-switch."""
    raw = os.environ.get(name, "").strip().lower()
    if raw in _FALSY:
        return False
    if raw in _TRUTHY:
        return True
    return default


class EnvProvider:
    """Names + sizes the process's auxiliary executors."""

    _instance: Optional["EnvProvider"] = None

    @classmethod
    def instance(cls) -> "EnvProvider":
        if cls._instance is None:
            cls._instance = EnvProvider()
        return cls._instance

    def __init__(self) -> None:
        self._pools = {}

    def thread_factory(self, name: str):
        """Factory producing named daemon threads (≈ EnvProvider
        newThreadFactory)."""
        counter = [0]

        def factory(target, *args):
            counter[0] += 1
            t = threading.Thread(target=target, args=args,
                                 name=f"{name}-{counter[0]}", daemon=True)
            return t
        return factory

    def executor(self, name: str, max_workers: int = 2
                 ) -> concurrent.futures.ThreadPoolExecutor:
        pool = self._pools.get(name)
        if pool is None:
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=max_workers, thread_name_prefix=name)
            self._pools[name] = pool
        return pool

    def shutdown(self) -> None:
        for p in self._pools.values():
            p.shutdown(wait=False)
        self._pools.clear()


class MemUsage:
    """Process memory pressure probe (≈ MemUsage.java nettyDirectMemoryUsage
    / heapMemoryUsage): RSS against a configurable budget, sampled at most
    every ``sample_interval`` seconds."""

    def __init__(self, budget_bytes: Optional[int] = None,
                 high_watermark: float = 0.9,
                 sample_interval: float = 1.0) -> None:
        self.budget_bytes = budget_bytes or self._cgroup_limit()
        self.high_watermark = high_watermark
        self.sample_interval = sample_interval
        self._last_sample = 0.0
        self._last_usage = 0.0

    @staticmethod
    def _cgroup_limit() -> int:
        for path in ("/sys/fs/cgroup/memory.max",
                     "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
            try:
                raw = open(path).read().strip()
                if raw.isdigit() and int(raw) < 1 << 48:
                    return int(raw)
            except OSError:
                continue
        # no cgroup limit: the budget is the machine's physical memory
        try:
            return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        except (ValueError, OSError):
            return 1 << 34  # 16 GiB fallback budget

    @staticmethod
    def rss_bytes() -> int:
        try:
            with open("/proc/self/statm") as f:
                return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        except (OSError, ValueError, IndexError):
            return 0

    def usage(self) -> float:
        now = time.monotonic()
        if now - self._last_sample >= self.sample_interval:
            self._last_sample = now
            self._last_usage = self.rss_bytes() / max(1, self.budget_bytes)
        return self._last_usage

    def under_pressure(self) -> bool:
        return self.usage() >= self.high_watermark
