"""Process-global wire-level fault injection for the RPC fabric.

``raft/transport.py``'s InMemTransport gives raft partition/kill/drop
chaos; this gives the REAL TCP fabric the same surface. Rules match by
(service, method, side) with a probability, and fire one of:

- ``drop``: the frame vanishes (client: request never sent; server:
  request never dispatched → the caller times out).
- ``delay``: the frame is held ``delay`` seconds before proceeding.
- ``corrupt``: payload bytes are mangled (codec robustness).
- ``error``: the call fails immediately (client: synthetic transport
  error; server: status-1 reply; matcher: raised exception).
- ``disconnect``: the underlying connection is torn down mid-call.

The injector is also the chaos hook for NON-wire failure points: the
dist worker consults ``service="tpu-matcher"`` before device dispatch so
tests can force the host-oracle degradation path.

ISSUE 7 adds the DEVICE-side rule set (``service="tpu-device"``), hooked
into the matcher's dispatch/fetch stages and the ring's readiness poll:

- ``error``: the dispatch (method="dispatch") or fetch (method="fetch")
  raises — a crashed kernel / poisoned buffer.
- ``hang``: the dispatched batch NEVER reports ready while the rule
  stays installed — a wedged accelerator; the watchdog deadline is the
  only way out. Removing the rule "un-wedges" the device (the arrays
  were really ready all along), which is exactly how the chaos gate
  drives breaker recovery.
- ``slow``: readiness is withheld for ``delay`` seconds — a saturated
  device.
- ``flaky_ready``: each readiness poll lies "not ready" with the rule's
  probability — a glitchy PJRT buffer query; completion is only delayed,
  never denied.

Everything is deterministic under a seeded ``random.Random``; injected
faults are counted globally (``utils.metrics.FABRIC``) and per rule.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence


@dataclass
class FaultRule:
    service: str = "*"
    method: str = "*"
    side: str = "*"            # "client" | "server" | "*"
    probability: float = 1.0
    action: str = "error"      # drop | delay | corrupt | error | disconnect
    delay: float = 0.0         # seconds, for action="delay"
    max_hits: Optional[int] = None   # stop firing after N hits
    hits: int = field(default=0, init=False)

    def matches(self, side: str, service: str, method: str) -> bool:
        if self.max_hits is not None and self.hits >= self.max_hits:
            return False
        return ((self.side in ("*", side))
                and (self.service in ("*", service))
                and (self.method in ("*", method)))


class InjectedFault(Exception):
    """Raised for action="error" at non-wire hook points (e.g. the
    tpu-matcher): carries the rule that fired."""


class FaultInjector:
    def __init__(self, seed: Optional[int] = None) -> None:
        self.rules: List[FaultRule] = []
        self.rng = random.Random(seed)
        self.enabled = False
        self.injected_total = 0

    # ---------------- configuration ----------------------------------------

    def add_rule(self, **kw) -> FaultRule:
        rule = FaultRule(**kw)
        self.rules.append(rule)
        self.enabled = True
        return rule

    def remove_rule(self, rule: FaultRule) -> None:
        if rule in self.rules:
            self.rules.remove(rule)
        self.enabled = bool(self.rules)

    def reset(self, seed: Optional[int] = None) -> None:
        self.rules.clear()
        self.enabled = False
        self.injected_total = 0
        if seed is not None:
            self.rng = random.Random(seed)

    # ---------------- decision points --------------------------------------

    def decide(self, side: str, service: str, method: str,
               actions: Optional[tuple] = None) -> Optional[FaultRule]:
        """First matching rule that fires, or None. ``actions`` restricts
        which rule actions a hook point can honor — rules it cannot act
        on are left untouched (hits/counters unconsumed) for the hook
        that can. O(1) when disabled — the hot path pays a single
        attribute check."""
        if not self.enabled:
            return None
        for rule in self.rules:
            if actions is not None and rule.action not in actions:
                continue
            if rule.matches(side, service, method) \
                    and self.rng.random() < rule.probability:
                rule.hits += 1
                self.injected_total += 1
                self._meter()
                return rule
        return None

    def check_raise(self, side: str, service: str, method: str) -> None:
        """Non-wire hook: raise InjectedFault when an ``error`` rule fires
        (other actions are meaningless without a frame and are NOT
        consumed — they stay armed for the wire hooks)."""
        if self.decide(side, service, method,
                       actions=("error",)) is not None:
            raise InjectedFault(f"{service}/{method} ({side})")

    #: the device-side action taxonomy (ISSUE 7) — see module docstring
    DEVICE_ACTIONS = ("error", "hang", "slow", "flaky_ready")

    def device_rule(self, method: str) -> Optional[FaultRule]:
        """Device-fault hook for ``service="tpu-device"`` rules at the
        matcher's dispatch/fetch stages. ``error`` rules raise here; the
        readiness-shaping actions (hang/slow/flaky_ready) return the
        fired rule for the caller to thread into ``wait_ready``. O(1)
        when the injector is disabled."""
        rule = self.decide("device", "tpu-device", method,
                           actions=self.DEVICE_ACTIONS)
        if rule is not None and rule.action == "error":
            raise InjectedFault(f"tpu-device/{method} (device)")
        return rule

    def rule_active(self, rule: Optional[FaultRule]) -> bool:
        """Is a previously-fired rule still installed? The hang action
        polls this so REMOVING the rule un-wedges the device mid-wait."""
        return rule is not None and rule in self.rules

    @staticmethod
    def _meter() -> None:
        from ..utils.metrics import FABRIC, FabricMetric
        FABRIC.inc(FabricMetric.FAULTS_INJECTED)

    def corrupt(self, payload: bytes) -> bytes:
        """Flip a byte (or fabricate one for empty payloads)."""
        if not payload:
            return b"\xff"
        i = self.rng.randrange(len(payload))
        return payload[:i] + bytes([payload[i] ^ 0xFF]) + payload[i + 1:]


# the process-global injector the fabric consults (tests reconfigure it;
# production leaves it disabled — one bool check per frame)
_INJECTOR = FaultInjector()


def get_injector() -> FaultInjector:
    return _INJECTOR


# ---------------------------------------------------------------------------
# chaos campaigns (ISSUE 16 tentpole leg 3)
# ---------------------------------------------------------------------------

@dataclass
class ChaosEvent:
    """One scripted fault transition, fired at a WORKLOAD STEP index —
    step-indexed (not wall-clock) so the same schedule replays the same
    fault sequence on any machine:

    - ``inject``: install a :class:`FaultRule` (``rule_kw`` are the
      ``add_rule`` kwargs) under ``label``;
    - ``clear``: remove the rule installed under ``label`` (absent is a
      no-op — schedules stay valid under reordering edits);
    - ``call``: invoke ``fn(step)`` — the hook for non-rule chaos like
      crashing a standby mid-promote or flapping a tunnel object.
    """

    step: int
    action: str                      # "inject" | "clear" | "call"
    label: str = ""
    rule_kw: Dict = field(default_factory=dict)
    fn: Optional[Callable[[int], None]] = None


class ChaosCampaign:
    """Seeded, scriptable fault schedule driven against a step-indexed
    workload — repeatable fault campaigns instead of one-off chaos
    scripts. The injector is ``reset(seed)`` at campaign start, every
    event fires at a deterministic step boundary, and the report's
    ``signature`` carries only deterministic facts (timeline, rule hit
    counts, per-step workload summaries) so two runs with the same
    seed + schedule compare EQUAL — the blast-radius regression gate.

    The workload callable runs one step and returns a JSON-able summary
    (or None). An optional ``monitor`` (duck-typed —
    :class:`bifromq_tpu.obs.campaign.CampaignMonitor`) is fed after
    every step with the set of live fault labels; its windows/percentile
    report rides the final report under ``"monitor"`` (latency numbers
    excluded from the signature: wall-clock is never deterministic)."""

    def __init__(self, name: str, schedule: Sequence[ChaosEvent], *,
                 seed: int = 0, injector: Optional[FaultInjector] = None,
                 monitor=None) -> None:
        self.name = name
        # stable order: by step, schedule position breaking ties
        self.schedule = sorted(enumerate(schedule),
                               key=lambda kv: (kv[1].step, kv[0]))
        self.seed = seed
        self.injector = injector or get_injector()
        self.monitor = monitor
        self.timeline: List[dict] = []
        self.step_outputs: List = []
        self._live: Dict[str, FaultRule] = {}
        self._all: Dict[str, FaultRule] = {}

    # ---------------- event firing -----------------------------------------

    def _fire(self, ev: ChaosEvent, step: int) -> None:
        if ev.action == "inject":
            label = ev.label or f"rule@{step}"
            rule = self.injector.add_rule(**ev.rule_kw)
            self._live[label] = rule
            self._all[label] = rule
        elif ev.action == "clear":
            rule = self._live.pop(ev.label, None)
            if rule is not None:
                self.injector.remove_rule(rule)
        elif ev.action == "call":
            if ev.fn is not None:
                ev.fn(step)
        else:
            raise ValueError(f"unknown chaos action {ev.action!r}")
        self.timeline.append({"step": step, "action": ev.action,
                              "label": ev.label})

    def _step_events(self, step: int) -> None:
        for _, ev in self.schedule:
            if ev.step == step:
                self._fire(ev, step)

    def _observe(self, step: int) -> None:
        if self.monitor is not None:
            self.monitor.observe_step(step, active=sorted(self._live))

    def _finish(self) -> None:
        # campaigns never leak rules into the next test/campaign
        for rule in self._live.values():
            self.injector.remove_rule(rule)
        self._live.clear()

    # ---------------- drivers ----------------------------------------------

    def run(self, workload: Callable[[int], object],
            n_steps: int) -> dict:
        self.injector.reset(self.seed)
        try:
            for step in range(n_steps):
                self._step_events(step)
                self.step_outputs.append(workload(step))
                self._observe(step)
        finally:
            self._finish()
        return self.report()

    async def arun(self, workload, n_steps: int) -> dict:
        """Async twin of :meth:`run` for workloads that await (the
        async serving plane, standby sync loops)."""
        self.injector.reset(self.seed)
        try:
            for step in range(n_steps):
                self._step_events(step)
                self.step_outputs.append(await workload(step))
                self._observe(step)
        finally:
            self._finish()
        return self.report()

    # ---------------- report -----------------------------------------------

    def report(self) -> dict:
        sig = {"name": self.name, "seed": self.seed,
               "timeline": list(self.timeline),
               "rule_hits": {lbl: r.hits for lbl, r in self._all.items()},
               "steps": [out for out in self.step_outputs]}
        out = {"signature": sig,
               "injected_total": self.injector.injected_total}
        if self.monitor is not None:
            mon = self.monitor.report()
            # the monitor's deterministic half joins the signature; its
            # latency numbers stay outside (wall-clock)
            sig["windows"] = mon.get("windows")
            sig["degradation"] = mon.get("steps")
            out["monitor"] = mon
        return out
