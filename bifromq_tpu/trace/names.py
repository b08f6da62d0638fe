"""The one registry of boundary names.

A boundary is a place where one layer hands work to the next. Its name is
registered HERE, once: the tracer looks the row up when a span opens
(which cumulative stage histogram and which tenant-window stage its exit
feeds, and whether its body never yields to the event loop and so may
carry a profiler annotation), ``utils.metrics.KNOWN_STAGES`` is derived
from the rows, the README's span table is generated from them
(``python -m bifromq_tpu.trace`` prints it, ``--write`` replaces it in
README.md), and the analyzer (``analysis/drift.py`` R5) checks code
against them in both directions.

Row kinds: ``span`` (opened with ``trace.span`` or closed with
``trace.record_finished``), ``counter`` (``trace.count``), ``stage`` (a
cumulative stage histogram fed by hand where no span sits). ``by_hand``
marks a span whose site still feeds the stage itself, because it records
on a condition the span cannot see (only batches that applied something).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional


class Boundary(NamedTuple):
    name: str
    kind: str                       # span | counter | stage
    where: str                      # module(s) that open it
    tells: str                      # the README cell
    stage: Optional[str] = None     # STAGES histogram its exit feeds
    window: Optional[str] = None    # tenant-window stage its exit feeds
    sync: bool = False              # body never awaits: annotate it
    by_hand: bool = False           # the site feeds ``stage`` itself
    feeds: bool = False             # the exit has a sink to feed


BOUNDARIES: Dict[str, Boundary] = {}


def _row(name: str, kind: str, where: str, tells: str, *,
         stage: Optional[str] = None, window: Optional[str] = None,
         sync: bool = False, by_hand: bool = False) -> None:
    if name in BOUNDARIES:
        raise ValueError(f"boundary {name!r} registered twice")
    BOUNDARIES[name] = Boundary(
        name, kind, where, tells, stage, window, sync, by_hand,
        bool((stage and not by_hand) or window))


# ---- MQTT frontend ---------------------------------------------------------
_row("mqtt.decode", "span", "mqtt/broker.py",
     "bytes of one socket read -> decoded packets", sync=True)
_row("pub.ingest", "span", "mqtt/session.py",
     "the ROOT: PUBLISH parsed -> dist call returned and acked (sampling "
     "is drawn here, per tenant); feeds the `ingest` stage and the "
     "tenant's window", stage="ingest", window="ingest")
_row("dist.pub", "span", "mqtt/session.py",
     "the dist call inside the ingest: queue wait + match + fan-out. "
     "`pub.ingest` minus this is the frontend's own time")
_row("pub.ack", "span", "mqtt/session.py",
     "PUBACK / PUBREC encode + socket write")
_row("sub.route", "span", "mqtt/session.py",
     "SUBSCRIBE parsed -> SUBACK queued: permission checks, the route's "
     "consensus write, retained replay")
_row("unsub.route", "span", "mqtt/session.py",
     "UNSUBSCRIBE parsed -> UNSUBACK queued: permission checks and the "
     "route's consensus delete (`sub.route` covers SUBSCRIBE alone)")
_row("sub.dist", "span", "dist/service.py",
     "one route add through the dist service: worker mutation + match-"
     "cache invalidation")
_row("loop.lag", "span", "mqtt/broker.py",
     "how late the serving loop's fixed 20 ms heartbeat fired (deferred "
     "span: due -> fired); the slice's max is its worst stall")
# ---- dist service / scheduler ----------------------------------------------
_row("batch.queue_wait", "span", "scheduler/batcher.py",
     "enqueue -> emit wait per call (deferred span), tagged `batch_size`, "
     "the adaptive `cap` at emit time and the `batch_id` every sampled "
     "span of that batch carries; feeds the `queue_wait` stage and the "
     "tenant's window. A batch's mean of it, against the batch's run "
     "time, is what moves the cap",
     stage="queue_wait", window="queue_wait")
_row("batch.emitted", "counter", "scheduler/batcher.py",
     "batches a staged batcher emitted (the pub scheduler's: one "
     "`process`, at most one match call)")
_row("batch.calls", "counter", "scheduler/batcher.py",
     "calls in those batches (= n of `batch.queue_wait`): over "
     "`batch.emitted` it reads calls a pub batch")
_row("batch.cap_grow", "counter", "scheduler/batcher.py",
     "times a staged batcher's cap doubled: its calls waited longer than "
     "their batch ran with a full cap more queued behind them (whatever "
     "the budget), or the queue was saturated well inside the budget")
_row("batch.cap_shrink", "counter", "scheduler/batcher.py",
     "times a staged batcher's cap halved: a batch over "
     "`max_burst_latency` whose calls had waited under a quarter of its "
     "run (the overrun guard), or the shallow-queue decay toward idle")
_row("batch.emit", "span", "scheduler/batcher.py",
     "a batch that holds several sampled callers: parented under the "
     "first, linking the others (bounded at 16)")
_row("match.no_route", "counter", "dist/service.py",
     "publishes whose match came back empty: acknowledged, delivered to "
     "nobody")
_row("deliver.fanout", "span", "dist/service.py",
     "one publish's fan-out to its sub-brokers, tagged with the achieved "
     "count; feeds the `deliver` stage and the tenant's window",
     stage="deliver", window="deliver")
_row("deliver.group", "span", "dist/service.py",
     "election, byte cap and grouping of one publish's routes by (broker, "
     "deliverer key), message pack built", sync=True)
_row("share.elect.kept", "counter", "dist/service.py",
     "shared-group elections answered from the state kept for the "
     "membership they were handed (the matcher's own `members` tuple, "
     "unchanged since the group's last election): O(1) for `$share`, one "
     "topic hash for `$oshare` (beside `deliver.group`)")
_row("share.elect.resync", "counter", "dist/service.py",
     "elections handed another object than the kept membership, which "
     "re-synced the state first, O(members): a join or leave (the "
     "patcher swapped the tuple), or a leg that builds a fresh member "
     "list a call (overlay, multi-range union, remote worker, host "
     "oracle: there `.kept` reads 0)")
_row("share.elect.first", "counter", "dist/service.py",
     "elections that found no kept state and built it, O(members): a "
     "group's first, or its first since `max_groups` dropped it (more "
     "of these than groups: the bound is thrashing)")
_row("share.elect.scanned", "counter", "dist/service.py",
     "members those re-syncs and first elections scanned")
_row("deliver.call", "span", "dist/service.py",
     "one sub-broker (or remote deliverer) call: the finest grain, 64 a "
     "publish at the fan-out corner. `deliver.fanout` less these is the "
     "fan-out's own time (grouping, match infos built, results read back)")
_row("deliver.routes", "counter", "dist/service.py",
     "routes handed to sub-brokers (beside `deliver.fanout`)")
_row("deliver.match_info.built", "counter", "dist/service.py",
     "routes delivered to for the FIRST time: their `MatchInfo` was built "
     "and is kept on the route. Against `deliver.routes` it is the share "
     "that reused it")
_row("deliver.plan.built", "counter", "dist/service.py",
     "publishes whose routes were grouped anew (a fresh match, or "
     "`normal` reassigned)")
_row("deliver.plan.reused", "counter", "dist/service.py",
     "publishes that found the grouped plan kept on their "
     "`MatchedRoutes` (pub-cache or matcher-cache hit, a topic twice in "
     "one pub batch)")
_row("deliver.settle.slow", "counter", "dist/service.py",
     "sub-broker calls whose results were not all `OK` and were settled "
     "route by route (dead routes reaped there)")
_row("deliver.local_fanout", "span", "mqtt/localrouter.py",
     "local-router re-fan-out of one shared route to its sessions")
_row("deliver.transient", "span", "mqtt/session.py",
     "per-session pushes of the transient sub-broker")
_row("deliver.remote", "span", "dist/deliverer.py",
     "the serving side of a cross-broker deliver hop")
_row("rpc.attempt", "span", "rpc/fabric.py",
     "one RPC attempt (client), tagged endpoint, breaker state, "
     "`attempt` / `failed_over`; feeds the `rpc` stage", stage="rpc")
_row("rpc.server", "span", "rpc/fabric.py",
     "one RPC handler execution (server)")
# ---- dist worker / KV ------------------------------------------------------
_row("match.device", "span", "dist/worker.py",
     "one range's match dispatch, host-oracle fallback included; feeds "
     "the `device` stage (less the ring-admission wait) and each tenant's "
     "row share of it", stage="device", window="device")
_row("match.degraded", "span", "dist/worker.py, models/matcher.py, "
     "parallel/sharded.py",
     "a batch served from the host oracle, tagged with the reason")
_row("raft.propose", "span", "kv/range.py",
     "one coproc mutation proposed -> committed and applied (a single-"
     "voter leader applies inside the propose: `raft.apply` is its child)")
_row("raft.apply", "span", "kv/range.py",
     "one committed entry applied to the range: KV batch or coproc "
     "mutation (route add/remove patches the matcher here)", sync=True)
_row("kv.resort", "span", "kv/engine.py",
     "the in-memory KV's pending puts merged into its ordered key list "
     "by the next ordered read or delete: placed key by key when few, "
     "appended and re-sorted when many", sync=True)
_row("patch.host", "span", "models/matcher.py",
     "one route op folded into the host arenas (`patch_host_s`)",
     sync=True)
_row("patch.flush", "span", "models/matcher.py",
     "accumulated host patches shipped to the device as row scatters "
     "(`patch_device_s`)", sync=True)
_row("patch.regrow", "counter", "models/automaton.py",
     "node-arena doublings and edge-table regrows of a patched base: each "
     "re-ships a whole table and re-traces the walk")
# ---- matcher ---------------------------------------------------------------
_row("match.cache.lookups", "counter", "models/matcher.py",
     "rows probed in the matcher's result cache (`models/matchcache.py`), "
     "once a match call")
_row("match.cache.hits", "counter", "models/matcher.py",
     "of those, the rows the cache answered: they reach neither tokenizer "
     "nor device")
_row("match.cache.evict_exact", "counter", "models/matchcache.py",
     "topic keys evicted one at a time by an exact filter's route "
     "mutation (either cache scope); a wildcard filter bumps the tenant's "
     "epoch instead")
_row("device.tokenize", "span", "models/matcher.py, parallel/sharded.py",
     "stage-1 byte-plane prep: TopicBytes pack + level hashing (host or "
     "the device hash program) + probe upload; feeds the `tokenize` stage",
     stage="tokenize", sync=True)
_row("device.acquire", "span", "models/matcher.py",
     "once a device batch, from leaving the line at ring admission to "
     "winning a slot: the prep (`device.tokenize`) and the slot wait")
_row("match.merged_calls", "counter", "models/matcher.py",
     "callers served by one device batch: those in line at ring "
     "admission when it boarded, per `device.dispatch`")
_row("device.dispatch", "span", "models/matcher.py, parallel/sharded.py",
     "walk enqueue cost, tagged `kernel`; feeds the stage the device "
     "breaker's deadline reads",
     stage="device.dispatch", sync=True)
_row("device.expand", "span", "models/matcher.py, parallel/sharded.py",
     "stage-2 device fan-out enqueue: interval expansion + per-peer "
     "bucketing, between dispatch and readiness",
     stage="device.expand", sync=True)
_row("device.ready", "span", "models/matcher.py",
     "the in-flight walk awaited on readiness (the loop serves between "
     "polls)", stage="device.ready")
_row("ready.polls", "counter", "models/pipeline.py",
     "`is_ready` polls that found the walk unfinished, per wait")
_row("ready.sleeps", "counter", "models/pipeline.py",
     "of those, the timed 0.5 ms sleeps after the spin phase")
_row("device.shard_ready", "span", "parallel/sharded.py",
     "one mesh shard's dispatch -> ready time from the split per-shard "
     "readiness poll (deferred span): feeds the completion board that "
     "names a hung device in `/mesh`", stage="device.shard_ready")
_row("device.fetch", "span", "models/matcher.py",
     "readiness wait + host copy of the results",
     stage="device.fetch", sync=True)
_row("device.fetch.wait", "span", "models/matcher.py",
     "the part of the fetch that blocks until the device is done "
     "(`block_until_ready`); the rest of `device.fetch` is the copy",
     sync=True)
_row("match.expand", "span", "models/matcher.py",
     "host stage 3: escalation, overlay, route assembly "
     "(`BatchRecord.expand_s`)", sync=True)
# ---- mesh, replication, retained, inbox ------------------------------------
_row("mesh.flush", "stage", "parallel/sharded.py",
     "per-shard mesh patch flush (scatters)", stage="mesh.flush",
     by_hand=True)
_row("repl.apply", "span", "replication/standby.py",
     "one standby delta-batch apply: plan scatters + trie ops + cache "
     "evictions + the replica's own device flush; the stage counts only "
     "batches that applied something", stage="repl.apply", by_hand=True)
_row("repl.audit", "span", "obs/audit.py",
     "one parity-audit emission: chunked fingerprints of every live "
     "arena scope folded into the delta stream; the stage counts only "
     "audits that emitted", stage="repl.audit", by_hand=True)
_row("retain.scan", "span", "retained_plane/scan.py",
     "one retained wildcard-scan batch on SUBSCRIBE, from the serve's "
     "call (cache probe) to its rows filled, tagged `degraded` on oracle "
     "serves (deferred span; the site feeds the stage and each scanned "
     "tenant's window per query)", stage="retain.scan", by_hand=True)
_row("retain.scan.queries", "counter", "retained_plane/scan.py",
     "filters handed to the scan plane (beside `retain.scan`)")
_row("retain.scan.cache_hits", "counter", "retained_plane/scan.py",
     "of those, the filters the filter-keyed scan cache answered: they "
     "reach no walk")
_row("retain.scan.walks", "counter", "models/retained.py",
     "retained walks dispatched on the device (one a scan batch that "
     "missed the cache)")
_row("retain.rows.device", "counter", "models/retained.py",
     "walked filter rows the device walk answered (its ranges expanded "
     "on the host)")
_row("retain.rows.native", "counter", "models/retained.py",
     "walked rows the device flagged (a `+` frontier past its states) "
     "that the native walker answered over the same tables")
_row("retain.rows.oracle", "counter", "models/retained.py, "
     "retained_plane/scan.py",
     "rows the exact host oracle answered (`match_filter_host`): flagged "
     "rows while patch-era extras exist, unhashable filters, degraded "
     "scans")
_row("sub.retained", "span", "mqtt/session.py",
     "one SUBSCRIBE's retained delivery inside `sub.route`: the retain "
     "service's match -> the last message handed to the send path or "
     "queued")
_row("retain.deliver.deferred", "counter", "mqtt/session.py",
     "retained messages a SUBSCRIBE matched that found the send window "
     "full and were queued, to be sent as PUBACKs free packet ids")
_row("inbox.drain", "span", "mqtt/persistent.py",
     "a persistent session's catch-up drain at reconnect, tagged "
     "`fetched`", stage="inbox.drain", window="inbox.drain")
_row("mesh.migrate", "span", "parallel/reshard.py",
     "one live-migration copy chunk; `resize_mesh` stamps its whole "
     "drain + re-place under the same name", stage="mesh.migrate")
_row("mesh.migrate.begin", "span", "parallel/reshard.py",
     "migration ladder: begin emit", stage="mesh.migrate.begin")
_row("mesh.migrate.copy", "span", "parallel/reshard.py",
     "migration ladder: one copy chunk (nested under `mesh.migrate`)",
     stage="mesh.migrate.copy")
_row("mesh.migrate.ready", "span", "parallel/reshard.py",
     "migration ladder: the dual-serve window opens",
     stage="mesh.migrate.ready")
_row("mesh.migrate.cutover", "span", "parallel/reshard.py",
     "migration ladder: the shard-map flip", stage="mesh.migrate.cutover")
_row("mesh.migrate.tombstone", "span", "parallel/reshard.py",
     "migration ladder: the source tombstone sweep",
     stage="mesh.migrate.tombstone")

# the cumulative stage histograms a literal may name (``STAGES.record`` /
# ``STAGES.hist`` / ``Batcher(stage=...)`` / ``OBS.record_latency``)
KNOWN_STAGES = frozenset(
    s for b in BOUNDARIES.values() for s in (b.stage, b.window) if s)

TABLE_HEAD = "| span | where | kind | tells you |"


def readme_table() -> str:
    """The README's span table, one row per registered name."""
    lines = [TABLE_HEAD, "|---|---|---|---|"]
    for b in BOUNDARIES.values():
        where = ", ".join(f"`{w.strip()}`" for w in b.where.split(","))
        kind = b.kind + (f" -> `{b.stage}`" if b.stage else "")
        lines.append(f"| `{b.name}` | {where} | {kind} | {b.tells} |")
    return "\n".join(lines)


def replace_table(readme: str) -> str:
    """``readme`` with its span table replaced by the generated one."""
    lines = readme.split("\n")
    start = next(i for i, ln in enumerate(lines)
                 if ln.strip().startswith("| span |"))
    end = start
    while end < len(lines) and lines[end].strip().startswith("|"):
        end += 1
    return "\n".join(lines[:start] + [readme_table()] + lines[end:])


def main(argv) -> int:
    """``python -m bifromq_tpu.trace`` prints the table; ``--write``
    replaces it in the checkout's README.md."""
    import os
    if "--write" in argv:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "README.md")
        with open(path, encoding="utf-8") as f:
            text = f.read()
        with open(path, "w", encoding="utf-8") as f:
            f.write(replace_table(text))
    else:
        print(readme_table())
    return 0
