"""Retained messages on SUBSCRIBE in the comparison, without a chip.

``rehearsal_retained_20k.rehearsal_resub``: 20,000 retained topics of
last-known device state over 4 tenants (``generators/retained_state.py``),
8 SUBSCRIBE lanes, 20 retained SETs a second of which a tenth CLEAR
(``--rehearse-cpu``).

- a sound traced run is correct: SUBSCRIBEs handed retained messages, the
  five retained numbers 0, the scan planes never degraded;
- ``retained_drop_one`` and ``retained_stale`` come out not correct, each
  by its own number alone;
- one hand-built case for each of the five numbers that makes exactly that
  number non-zero, and MUST / MAY around a SET or a CLEAR inside the
  SUBSCRIBE -> SUBACK;
- the seat: seeded rows come back from the program's retained scans, and
  the limit cuts a site-wide filter to exactly the limit;
- a retained message's payload is never read as a window publish;
- the six cells of BENCHMARK.json plan exactly what they planned before
  the retained keys existed;
- ``mqttlite`` sends and reports the RETAIN bit; ``selfcheck`` refuses a
  retained section without its generator, lanes without the section, and
  a retained cell that raises ``MinSendPerSec``.

    python3 -m pytest benchmarks/tests/test_retained_rehearsal.py -q   (about 1 min, CPU)
"""

import asyncio
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import loadgen  # noqa: E402
import mqttlite  # noqa: E402
import reference  # noqa: E402
import run as bench_run  # noqa: E402
import selfcheck  # noqa: E402
import traffic  # noqa: E402

BENCH = os.path.join(HERE, "rehearsal_retained_bench.json")
CELL = "rehearsal_retained_20k.rehearsal_resub"
NUMBERS = bench_run.RETAINED_NUMBERS


def run_cell(*extra: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "4",
         "--rehearse-cpu", "--bench-file", BENCH, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def over_limit(line: dict) -> set:
    return {k for k, v in line["compared"].items()
            if isinstance(v[1], int) and v[0] > v[1]}


def below_floor(line: dict) -> set:
    return {k for k, v in line["compared"].items()
            if isinstance(v[1], str) and v[0] < int(v[1][2:])}


def test_sound_traced_run_hands_retained_messages():
    line, stdout = run_cell("--trace", "1")
    assert line["correct"] is True, line["compared"]
    c = line["compared"]
    for name in NUMBERS + ("retained_degraded", "retained_tables_off_device"):
        assert c[name] == [0, 0], (name, c[name])
    assert c["retained_subs"][0] >= 100 and c["retained_walks"][0] >= 20
    assert "retained seeded: 20,000 topics" in stdout
    assert "'degraded': {}" in stdout


@pytest.mark.parametrize("control,number", [
    ("retained_drop_one", "retained_missing"),
    ("retained_stale", "retained_stale")])
def test_controls_fail_by_their_own_number_alone(control, number):
    line, _stdout = run_cell("--trace", "0", "--control", control)
    assert line["correct"] is False
    assert over_limit(line) == {number}, line["compared"]
    assert not below_floor(line), line["compared"]
    assert line["compared"][number][0] >= 3


# --------------------------------------------- the verdict, by hand

S, A, UREQ, UACK = 1_000, 2_000, 2_100, 2_200
TOPICS = [("ta", "homie/s0/d0/a"), ("ta", "homie/s0/d0/b"),
          ("ta", "homie/s0/d0/c"), ("ta", "homie/s0/d1/a"),
          ("tb", "homie/s0/d0/a")]
PLAN = {"tenants": ["ta", "tb"], "resub": {"lanes": [(0, [])]}}
DEVICE = "homie/s0/d0/#"


def table_with(*events):
    table = reference.RetainedTable()
    for tenant, topic in TOPICS:
        table.add(tenant, topic)
    for ev in events:
        table.apply(*ev)
    return table


def got(tid, version=0, retain=1, t=1_500, topic=None):
    return [0, topic or TOPICS[tid][1],
            traffic.RETAINED_MARK | tid, version, retain, 1, t]


def verdict(received, limit=10, window=True, table=None):
    report = {"retained": {
        "events": [],
        "ops": [[0, S, A, UREQ, UACK, DEVICE, window, 1]],
        "received": received, "in_window": 0}}
    out = bench_run.retained_verdict(report, PLAN, table or table_with(),
                                     limit)
    return {k: out[k] for k in NUMBERS}, out


def only(number=None):
    return {k: int(k == number) for k in NUMBERS}


def test_every_matching_topic_once_is_sound():
    numbers, out = verdict([got(0), got(1), got(2)])
    assert numbers == only() and out["retained_subs"] == 1
    # an operation outside the window is not judged
    assert verdict([got(0)], window=False)[0] == only()


@pytest.mark.parametrize("received,limit,number", [
    # one of three matching topics never came
    ([got(0), got(1)], 10, "retained_missing"),
    # a topic twice
    ([got(0), got(1), got(2), got(1)], 10, "retained_surplus"),
    # more than the limit
    ([got(0), got(1), got(2)], 2, "retained_surplus"),
    # another tenant's topic of the same name; a topic the filter does
    # not match; a name that is not its topic id's
    ([got(0), got(1), got(2), got(4)], 10, "retained_foreign"),
    ([got(0), got(1), got(2), got(3)], 10, "retained_foreign"),
    ([got(0), got(1), got(2, topic="homie/s0/d0/z")], 2, "retained_foreign"),
    # a version that never existed
    ([got(0), got(1, version=3), got(2)], 10, "retained_stale"),
    # the limit cut the third topic, which then came without the RETAIN bit
    ([got(0), got(1), got(2, retain=0)], 2, "retained_flag"),
])
def test_each_fault_moves_exactly_its_number(received, limit, number):
    assert verdict(received, limit=limit)[0] == only(number)


def test_a_receipt_outside_every_operation_is_a_surplus():
    assert verdict([got(0), got(1), got(2), got(0, t=UACK + 5)])[0] \
        == only("retained_surplus")


def test_must_and_may_around_a_set_or_a_clear_inside_the_subscribe():
    # a CLEAR of topic 2 sent and acked inside [s, a]: topic 2 is a MAY,
    # not a MUST; handing it (in the version that stood) or not is sound
    clear = (2, -1, 1_200, 1_300)
    table = table_with(clear)
    assert table.must_may(2, S, A) == (False, True)
    assert table.must_may(0, S, A) == (True, True)
    assert verdict([got(0), got(1)], table=table)[0] == only()
    assert verdict([got(0), got(1), got(2)], table=table)[0] == only()
    # ... cleared before the SUBSCRIBE: neither, and handing it is foreign
    table = table_with((2, -1, 500, 600))
    assert table.must_may(2, S, A) == (False, False)
    assert verdict([got(0), got(1)], table=table)[0] == only()
    assert verdict([got(0), got(1), got(2)], table=table)[0] \
        == only("retained_foreign")
    # a SET of topic 0 in flight across s: either version may be handed
    table = table_with((0, 1, 900, 1_100))
    assert table.must_may(0, S, A) == (False, True)
    for version in (0, 1):
        assert verdict([got(0, version), got(1), got(2)],
                       table=table)[0] == only()
    # acked before s: the seed's version is stale
    table = table_with((0, 1, 500, 600))
    assert table.must_may(0, S, A) == (True, True)
    assert verdict([got(0, 1), got(1), got(2)], table=table)[0] == only()
    assert verdict([got(0, 0), got(1), got(2)], table=table)[0] \
        == only("retained_stale")
    # its live forward (no RETAIN bit) while the lane was subscribed is
    # either way; a CLEAR's empty live forward names no topic id
    table = table_with((0, 1, 1_500, 1_600), (1, -1, 1_700, 1_800))
    live = [got(0, 1, retain=0), [0, TOPICS[1][1], -1, -1, 0, 1, 1_750]]
    numbers, out = verdict([got(0), got(1), got(2)] + live, table=table)
    assert numbers == only() and out["live_either"] == 2


def test_a_retained_payload_is_never_a_window_publish():
    payload = traffic.retained_payload(5, 0, 64)
    assert len(payload) == 64
    assert traffic.retained_header(payload) == (5, 0)
    assert loadgen.HEADER.unpack_from(payload)[0] >= loadgen.WARM_FLAG
    run = loadgen.Run(0, {"tenants": ["ta"], "population": ["a"],
                          "stress": [], "payload_bytes": 64})
    run._on_publish(types.SimpleNamespace(index=0), b"homie/x", payload, 1, 7)
    assert run.received == []
    window = loadgen.HEADER.pack(3, 0) + b"x" * 48
    assert traffic.retained_header(window) is None


# ------------------------------------------ the seat, on the program

def test_seeded_rows_come_back_and_the_limit_cuts_a_site():
    import sut
    from bifromq_tpu.plugin.events import IEventCollector
    from bifromq_tpu.retain.service import RetainService
    cfg = traffic.load_json("configs", "rehearsal_retained_20k.json")
    gen = traffic.generator_of(cfg)
    rows = list(gen.retained(cfg))
    service = RetainService(IEventCollector())
    seeded = sut.seed_retained(types.SimpleNamespace(retain_service=service),
                               rows)
    assert seeded["topics"] == 20_000
    table = bench_run.retained_table(rows)
    limit = cfg["settings"]["RetainMessageMatchLimit"]

    async def scan(flt):
        return await service.match("tenant1", flt.split("/"), limit)
    for flt, n in (("homie/s3/#", 500), ("homie/s3/+/rssi", 50),
                   ("homie/s3/d7/#", 10), ("homie/s3/d7/$state", 1)):
        hits = asyncio.run(scan(flt))
        want = {table.topics[i][1]
                for i in table.match("tenant1", flt.split("/"))}
        assert len(want) == n
        assert len(hits) == min(limit, n) == len({t for t, _m in hits})
        assert {t for t, _m in hits} <= want
        for topic, msg in hits:
            tid = table.tid_of[("tenant1", topic)]
            assert traffic.retained_header(bytes(msg.payload)) == (tid, 0)
            assert msg.is_retain


def test_six_cells_plan_as_before():
    """Fingerprints of the plans of BENCHMARK.json's six cells on three
    seeds, as the tree before the retained keys computed them."""
    parent = {
        "tenant_fleet_1k.zipf_sat": (
            "c1f1991d6f20633d150501d1354c03530aaf1ff150d1e44522faa396483ff43c",
            "b16f9148654798269559c0227755ee7fecd8675d99e0ea47987ccc38cfd95241",
            "5c8ec47652d097538b982c27f61347971ea34a2a27ac077fcda072ff42f11f29"),
        "wildcard_1m.fanout_r25": (
            "4cfcb75834805b516150903be86afd6e8a865e67e36b81553fb06886c2ee2762",
            "e98582d7f4cab753d52c01387e08e015ba55a787ed9e8d79d34740aa358d6831",
            "44c2cf08dffb53ed9711133b648470e8707b220e22f4a1a77f8051558c614b3d"),
        "device_command_1m.p2p_sat": (
            "744ab640e51a4e0d9e50364a2b5612fe8c2124887c91d16dad95300224960c0f",
            "40eb0e02e17d4b303f7a0b06c809614a4eacbdc91cb4029c8a597e2d86e15d19",
            "8939619f9a23364d7982600c2cb732b781ddc8b29504b13bbb2c8a90f9059170"),
        "wildcard_1m.fanout_sat": (
            "4b514e06408fc35da22098a57c9bc046e6da24e484bdfc48d4328a0d7c625851",
            "480775f016fc1605dc9ee8454d9ab4dfd60a0cf7027a7efc973f0f16213f921f",
            "2f342b0659ea716369eda7e4b32b983482b03a6dea31278fe5097fb5a2cb3a68"),
        "tenant_fleet_4k.zipf_sat_mesh4": (
            "c9021a70a6a573ba16189eaf605183c2018ac24251127c1d93be64e4fd2166d5",
            "a7a063e52cfcdba8b16dac9cb4b495851cdec846da6e80acff3e6a4a2bd7b894",
            "343e08f9b5284d59fdc768b67bb4ac9d8d1923b8ece09bd79f88c539cc500074"),
        "telemetry_fanin.share_sat": (
            "1f9959413570272ed55a9f56b52b324ac3bb26592c493058c451545ab866f25d",
            "52a9e9351434a710bce1af76b5cef26f1f3d621838df20e2eb7881732d23ffc3",
            "c3b577b425a73c762b58973664b5c3af8f57c0a9df0f74c3329ad28fcb486125"),
    }
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert sorted(cells) == sorted(parent)
    for name in cells:
        cell = traffic.load_cell(name)
        for seed, want in zip((7, 2147483659, 4200000101), parent[name]):
            plan = traffic.build_plan(cell["config"], cell["traffic"], seed,
                                      51.0)
            assert "resub" not in plan and "retain_sets" not in plan
            assert traffic.fingerprint(plan) == want, (name, seed)


# ------------------------------------------------ wire and selfcheck

class _Wire:
    def __init__(self):
        self.out = bytearray()

    def write(self, data):
        self.out += data


def test_mqttlite_sends_and_reports_the_retain_bit():
    seen = []

    async def wire():
        c = mqttlite.RetainClient("c", "t/u", lambda *a: seen.append(a[1:]))
        c.transport = _Wire()
        c.publish_retained(b"a/b", b"xy")
        plain = mqttlite.Client("p", "t/u", lambda *a: seen.append(a[1:]))
        plain.transport = _Wire()
        plain.publish(b"a/b", b"xy", 1)
        return c, plain
    c, plain = asyncio.run(wire())
    assert c.transport.out[0] == 0x33       # PUBLISH, QoS 1, RETAIN
    assert plain.transport.out[0] == 0x32   # PUBLISH, QoS 1
    body = mqttlite._str(b"a/b") + b"\x00\x07" + b"pl"
    for first, retain in ((0x33, 1), (0x32, 0)):
        c._on_packet(first, body, 9)
        assert seen[-1] == (b"a/b", b"pl", 1, 9, retain)
    plain._on_packet(0x33, body, 9)
    assert seen[-1] == (b"a/b", b"pl", 1, 9)


def test_selfcheck_pairs_lanes_with_a_retained_section():
    cfg = traffic.load_json("configs", "rehearsal_retained_20k.json")
    mix = traffic.load_json("traffic", "rehearsal_resub.json")
    selfcheck.check_retained_config(cfg, "ok")
    selfcheck.check_retained_cell(cfg, mix, "ok")
    with pytest.raises(SystemExit, match="offers no retained"):
        selfcheck.check_retained_config(dict(cfg, generator="zipf_tree"), "x")
    with pytest.raises(SystemExit, match="RetainMessageMatchLimit"):
        selfcheck.check_retained_config(dict(cfg, settings={}), "x")
    plain = {k: v for k, v in cfg.items() if k != "retained"}
    with pytest.raises(SystemExit, match="no retained section"):
        selfcheck.check_retained_cell(plain, mix, "x")
    with pytest.raises(SystemExit, match="retain_set_per_s"):
        selfcheck.check_retained_cell(
            plain, {"retain_set_per_s": 1.0}, "x")
    # a cell keeps the program's MinSendPerSec; the rehearsal's 100 is its
    # stated exception
    with pytest.raises(SystemExit, match="MinSendPerSec"):
        selfcheck.check_retained_window(cfg, "x")
    selfcheck.check_retained_window(plain, "ok")
    default = {k: v for k, v in cfg["settings"].items()
               if k != "MinSendPerSec"}
    selfcheck.check_retained_window(dict(cfg, settings=default), "ok")
