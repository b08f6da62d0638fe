"""Shared subscriptions in the comparison, without a chip (PR 39).

``rehearsal_share_20k.rehearsal_share`` is ``telemetry_fanin.share_sat`` at
10 tenants x 2,000 memberships (9 sites, groups of 50): every row a member
of a ``$share`` / ``$oshare`` group, live sessions joining and leaving
seeded groups inside the window (``--rehearse-cpu``).

- a sound run is correct, holds elections, sees live joins, leaves and a
  live session WIN an election, and prints the cell's two metrics;
- ``share_all`` and ``share_none`` come out not correct, by the new
  numbers alone;
- one case for each new compared number that makes exactly that number
  non-zero, and the sum over both processes of a live join and leave;
- the live side: a shared subscription is a "may", never a "must", and a
  client's repeated SUBSCRIBE of one filter is ONE subscription that its
  first UNSUBSCRIBE ends (D15a, on the seed that showed it);
- shared rows come back from the KV keys as they went in.

    python3 -m pytest benchmarks/tests/test_share_rehearsal.py -q   (about 3 min, CPU)
"""

import json
import os
import re
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
import reference  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402

BENCH = os.path.join(HERE, "rehearsal_share_bench.json")
CELL = "rehearsal_share_20k.rehearsal_share"
NEW_NUMBERS = ("group_missing", "group_surplus", "group_foreign",
               "oshare_split")
NEW_METRICS = ("group_elect_us.tput", "share_member_skew.tput")


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
    # (the 20k-row table has 350 trie nodes: under the 4,096 at which the
    # program warms its patch scatters, so a 4 s window on the CPU may
    # catch one compiling; every other comparison has to hold)
    return {k for k, v in line["compared"].items()
            if isinstance(v[1], int) and v[0] > v[1]
            and k != "compiles_in_window"}


def test_sound_traced_run_elects_and_closes_the_sum():
    line, stdout = run_cell("--trace", "1")
    assert not over_limit(line), line["compared"]
    c = line["compared"]
    for name in NEW_NUMBERS:
        assert c[name] == [0, 0], (name, c[name])
    assert c["group_matched"][0] > 1000 and c["sampled_sets"][0] > 0
    said = re.search(
        r"shared groups: ([\d,]+) \(publish, group\) elections held, (\d+) "
        r"of them won by a live session; live joins / leaves of groups "
        r"acked inside the window (\d+) / (\d+)", stdout)
    held, won, joins, leaves = (int(g.replace(",", ""))
                                for g in said.groups())
    assert held == c["group_matched"][0]
    assert joins >= 4 and leaves >= 4, (joins, leaves)
    assert won >= 1, "no live session won an election: the live half of " \
        "the sum was not exercised"
    for name in NEW_METRICS:
        assert line["metrics"][name]["value"] > 0, sorted(line["metrics"])
    assert line["metrics"][NEW_METRICS[1]]["value"] >= 1.0


@pytest.mark.parametrize("control,number", [("share_all", "group_surplus"),
                                            ("share_none", "group_missing")])
def test_controls_fail_by_the_new_numbers_alone(control, number):
    line, _stdout = run_cell("--trace", "0", "--control", control)
    assert line["correct"] is False
    assert over_limit(line) == {number}, line["compared"]
    assert line["compared"][number][0] > 1000


# ------------------------------------------------ the fleet side, by hand

T0, T1 = 0, 10_000_000_000
ROWS = [("ta", ("$share", "g", "a", "+"), "m1", "d1"),
        ("ta", ("$share", "g", "a", "+"), "m2", "d2"),
        ("ta", ("$oshare", "o", "a", "#"), "o1", "d1"),
        ("ta", ("$oshare", "o", "a", "#"), "o2", "d2"),
        ("tb", ("$share", "g", "a", "+"), "x1", "d1")]
SG, OG = "$share/g/a/+", "$oshare/o/a/#"
PLAN = {"tenants": ["ta", "tb"], "population": ["a/b", "a/c", "z"]}


def pub(seq, topic=0, sent=None, tenant=0):
    sent = 1_000_000 * (seq + 1) if sent is None else sent
    return [seq, tenant, topic, 1, 0, sent, sent, sent + 100_000]


def verdict(elected, pubs, shared=None):
    stand_in = types.SimpleNamespace(elected=elected, member_load={})
    report = {"publishes": pubs, "t0_ns": T0, "t1_ns": T1}
    if shared:
        report["shared"] = dict(shared, done_ns=[p[7] for p in pubs])
    ref = bench_run.FleetReference(ROWS, with_prefixes=False)
    out = bench_run.group_verdict(stand_in, report, PLAN, ref)
    return {k: out[k] for k in NEW_NUMBERS}, out


SOUND = {0: [(SG, "m1", "d1"), (OG, "o2", "d2")]}


def only(number=None, n=1):
    return {k: (n if k == number else 0) for k in NEW_NUMBERS}


def test_one_member_a_matching_group_is_sound():
    assert verdict(SOUND, [pub(0)])[0] == only()
    # a topic no group matches wants nothing
    assert verdict({}, [pub(0, topic=2)])[0] == only()


@pytest.mark.parametrize("elected,number", [
    ({0: [(OG, "o2", "d2")]}, "group_missing"),
    ({0: [(SG, "m1", "d1"), (SG, "m2", "d2"), (OG, "o2", "d2")]},
     "group_surplus"),
    # not a member of that group (another tenant's), the member under a
    # deliverer key that is not its own, a group that does not match
    ({0: [(SG, "x1", "d1"), (OG, "o2", "d2")]}, "group_foreign"),
    ({0: [(SG, "m1", "d2"), (OG, "o2", "d2")]}, "group_foreign"),
    ({0: SOUND[0] + [("$share/g/z/+", "m1", "d1")]}, "group_foreign"),
])
def test_each_fault_moves_exactly_its_number(elected, number):
    assert verdict(elected, [pub(0)])[0] == only(number)


def test_oshare_holds_one_topic_to_one_member_between_changes():
    two = {0: [(SG, "m1", "d1"), (OG, "o1", "d1")],
           1: [(SG, "m2", "d2"), (OG, "o2", "d2")]}
    pubs = [pub(0), pub(1)]
    assert verdict(two, pubs)[0] == only("oshare_split")
    # ... another topic is another key
    assert verdict(two, [pub(0), pub(1, topic=1)])[0] == only()
    # ... and a live session that joined the group in between ends the
    # epoch: the change of member is then the rendezvous hash's right
    join = {"subs": [[7, 0, OG, 1_200_000, 1_300_000, 0, 0]], "receipts": []}
    pubs = [pub(0, sent=1_000_000), pub(1, sent=2_000_000)]
    numbers, out = verdict(two, pubs, join)
    assert numbers == only() and out["ordered_keys"] == 2
    # a publish in flight around the join belongs to neither epoch
    pubs = [pub(0, sent=1_000_000), pub(1, sent=1_250_000)]
    assert verdict(two, pubs, join)[1]["ordered_keys"] == 1


def test_a_live_member_closes_the_sum_from_the_other_process():
    sub = [7, 0, SG, 100, 200, 0, 0]            # client 7 joined ta's group
    firm = [[SG, True]]
    # the live session won: the stand-in holds nobody of that group
    won = {"subs": [sub], "receipts": [[7, 0, 1, 0, 0, firm]]}
    numbers, out = verdict({0: [(OG, "o2", "d2")]}, [pub(0)], won)
    assert numbers == only() and out["live_elected"] == 1
    # it did not win: the stand-in's member is the one
    lost = {"subs": [sub], "receipts": [[7, 0, 0, 0, 0, firm]]}
    assert verdict(SOUND, [pub(0)], lost)[0] == only()
    # both got it: two deliveries of one group
    assert verdict(SOUND, [pub(0)], won)[0] == only("group_surplus")
    # nobody got it
    assert verdict({0: [(OG, "o2", "d2")]}, [pub(0)], lost)[0] \
        == only("group_missing")
    # nobody got it while the live member was in flight around its
    # UNSUBACK: it may have been elected and fallen with its leaving
    leaving = {"subs": [sub], "receipts": [[7, 0, 0, 0, 0, [[SG, False]]]]}
    numbers, out = verdict({0: [(OG, "o2", "d2")]}, [pub(0)], leaving)
    assert numbers == only() and out["fell_in_flight"] == 1
    # a receipt the client's in-flight PLAIN subscription may explain is
    # no proof of a second delivery (got 1, may_plain 1)
    maybe = {"subs": [sub], "receipts": [[7, 0, 1, 0, 1, firm]]}
    assert verdict(SOUND, [pub(0)], maybe)[0] == only()
    # a group that only live sessions hold (nothing seeded): one of them
    alone = "$share/live/z"
    sub2 = [8, 0, alone, 100, 200, 0, 0]
    for got, number in ((1, None), (0, "group_missing")):
        only_live = {"subs": [sub2],
                     "receipts": [[8, 0, got, 0, 0, [[alone, True]]]]}
        assert verdict({}, [pub(0, topic=2)], only_live)[0] == only(number)
    # ... still in flight around its SUBACK: either way
    band = {"subs": [sub2], "receipts": [[8, 0, 0, 0, 0, [[alone, False]]]]}
    assert verdict({}, [pub(0, topic=2)], band)[0] == only()


# ---------------------------------------------------- the live side

def make_run(plan, topics):
    run = loadgen.Run(0, dict(plan, population=topics, stress=[]))
    run.t0, run.t1 = T0, T1
    return run


def sub_rec(client, flt, sub_req, suback, unsub_req=0, unsuback=0, qos=1):
    group, levels = reference.split_filter(flt)
    return {"client": client, "tenant": 0, "filter": flt, "levels": levels,
            "group": group, "qos": qos, "sub_req": sub_req, "suback": suback,
            "unsub_req": unsub_req, "unsuback": unsuback}


def lg_pub(seq, sent, topic=0):
    return [seq, 0, topic, 1, 0, sent, sent, sent + 10, sent + 10]


def test_a_shared_live_subscription_is_a_may_never_a_must():
    plan = {"tenants": ["ta"], "payload_bytes": 64, "n_taps": 0}
    run = make_run(plan, ["a/b"])
    run.subscriptions = [sub_rec(3, "a/#", 100, 200),
                         sub_rec(3, SG, 1000, 1100, 5000, 5100),
                         sub_rec(4, OG, 1000, 1100)]
    run.pubs = [lg_pub(0, 500), lg_pub(1, 1050), lg_pub(2, 2000),
                lg_pub(3, 5050), lg_pub(4, 6000)]
    expect = run.expectations({})
    # before the request: the plain one alone; around the SUBACK: in
    # flight (not firm); standing: a may, firm; after the UNSUBACK: gone
    assert expect[(3, 0)][:2] == [1, 0] and expect[(3, 0)][3] == []
    assert expect[(3, 1)][:2] == [1, 1] and expect[(3, 1)][3] == [[SG, False]]
    assert expect[(3, 2)][:2] == [1, 1] and expect[(3, 2)][3] == [[SG, True]]
    assert expect[(3, 3)][3] == [[SG, False]]
    assert expect[(3, 4)][:2] == [1, 0] and expect[(3, 4)][3] == []
    assert (4, 0) not in expect
    assert expect[(4, 2)][:2] == [0, 1] and expect[(4, 2)][3] == [[OG, True]]
    # the verdict: a shared subscription that got nothing is not missing,
    # one delivery is not a surplus, two are; and the receipts go out
    run.received = [(3, s, 1, 10) for s in range(5)] + [(3, 2, 1, 11)] \
        + [(4, 2, 1, 12), (4, 2, 1, 13)]
    report = run.verdict(expect, 7000)
    assert report["live_missing"] == 0 and report["live_unexpected"] == 0
    assert report["live_surplus"] == 1            # client 4, publish 2: twice
    receipts = {(c, s): (got, must, may) for c, s, got, must, may, _g
                in report["shared"]["receipts"]}
    assert receipts[(3, 2)] == (2, 1, 0) and receipts[(3, 1)] == (1, 1, 0)
    assert [s[2] for s in report["shared"]["subs"]] == [SG, OG]


def test_a_repeated_subscribe_is_one_subscription_d15a():
    """``wildcard_1m.fanout_r25`` seed 3500000101 (PERF.md section 6, PR
    35: ``live_missing`` 5 on BOTH sides): its churn hands a live client
    the filter it already holds. MQTT replaces the subscription, the
    churn's UNSUBSCRIBE ends it, and nothing matches that client after."""
    cell = traffic.load_cell("wildcard_1m.fanout_r25")
    plan = traffic.build_plan(cell["config"], cell["traffic"], 3500000101, 51.0)
    own = [(at, idx, flt) for at, kind, idx, flt in plan["churn"]
           if kind == "sub" and flt == plan["subs"][idx][1]]
    assert own, "this seed no longer draws a client's own filter"
    at, idx, flt = own[0]
    levels = flt.split("/")
    topic = "/".join("x" if lv == "+" else lv for lv in levels if lv != "#")
    assert reference.filter_matches(levels, topic.split("/"))
    run = make_run(plan, [topic])
    s = int(1e9)
    t_sub = int(at * 1e9)
    run.subscriptions = [
        sub_rec(idx, flt, -5 * s, -5 * s + 1000, qos=0),             # its own
        sub_rec(idx, flt, t_sub, t_sub + 1000, t_sub + s, t_sub + s + 1000)]
    run.pubs = [lg_pub(0, t_sub - s), lg_pub(1, t_sub + s // 2),
                lg_pub(2, t_sub + 2 * s)]
    expect = run.expectations({})
    assert expect[(idx, 0)][:2] == [1, 0] and expect[(idx, 0)][2] == {0}
    assert expect[(idx, 1)][:2] == [1, 0] and expect[(idx, 1)][2] == {1}
    assert (idx, 2) not in expect, "the UNSUBSCRIBE ended the client's own too"
    assert len(run.lifetimes()) == 1


# ------------------------------------------ seeding: the rows in the KV keys

def test_shared_rows_come_back_from_their_kv_keys():
    import sut
    from bifromq_tpu.kv import schema
    from bifromq_tpu.models.oracle import SubscriptionTrie
    cfg = traffic.load_json("configs", "rehearsal_share_20k.json")
    gen = traffic.generator_of(cfg)
    rows = list(gen.subscriptions(cfg))
    tries, n = sut.build_tries(rows)
    assert n == 20000 and sum(len(t) for t in tries.values()) == 20000
    value = schema.route_value(0)
    keys, back = set(), {}
    for tenant, trie in tries.items():
        twin = back[tenant] = SubscriptionTrie()
        for route in trie.routes():
            key = schema.route_key(tenant, route.matcher, route.receiver_url)
            keys.add(key)
            again = schema.decode_route(tenant, key, value)
            assert again == route, (again, route)
            twin.add(again)
    assert len(keys) == 20000, "two members share a key"
    ref = reference.Table()
    for tenant, levels, rid, dkey in rows:
        ref.add(tenant, levels, (rid, dkey))
    for tenant, topic in (("tenant0", "app/s3/d7/telemetry"),
                          ("tenant9", "app/snone/d0/telemetry"),
                          ("tenant4", "app/s0/d0/other")):
        want = {flt: sorted(m) for flt, m in ref.match_groups(tenant, topic)}
        for trie in (tries[tenant], back[tenant]):
            got = trie.match(topic.split("/"))
            assert not got.normal
            assert {flt: sorted((r.receiver_id, r.deliverer_key)
                                for r in members)
                    for flt, members in got.groups.items()} == want
    types_seen = {r.matcher.type.name for r in tries["tenant0"].routes()}
    assert types_seen == {"UNORDERED_SHARE", "ORDERED_SHARE"}
