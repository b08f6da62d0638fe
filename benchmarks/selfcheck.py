#!/usr/bin/env python3
"""Checks of the yardstick itself. CPU, no chip, no broker, seconds.

    python3 benchmarks/selfcheck.py

- the trace reduction on the small recorded trace beside it gives the
  busy / idle numbers written down when it was recorded, and its interval
  arithmetic gives hand-worked answers;
- the plain reference agrees with hand-written ``+`` / ``#`` / ``$share``
  / ``$``-topic cases, and its two forms agree with each other;
- the schedule is a pure function of ``--seed``, and seeds share the work
  (the retained lanes and SET stream too);
- every file under ``configs/``, ``traffic/``, ``layer_metrics/`` loads,
  every name and unit holds only the allowed characters, every per-layer
  metric of BENCHMARK.json has its file and its reader;
- retained on subscribe: a configuration's ``retained`` section needs a
  generator that offers ``retained(cfg)`` (and its three helpers) and a
  ``RetainMessageMatchLimit`` among its settings; a mix with ``resub`` or
  ``retain_set_per_s`` runs only in cells whose configuration has the
  section (BENCHMARK.json's and the tests' files of its form); a cell of
  BENCHMARK.json with the section keeps the program's ``MinSendPerSec``.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import random
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"selfcheck FAILED: {what}")


def check_intervals() -> None:
    iv = [(0, 10), (5, 20), (30, 40), (32, 35)]
    check(trace_reduce.union_ns(iv) == 30, "union of overlapping intervals")
    check(trace_reduce.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)], "gaps")
    check(trace_reduce.program_name("jit_walk_routes_donated(123)")
          == "walk_routes_donated", "program name")


def check_recorded_trace() -> None:
    with open(os.path.join(HERE, "testdata", "small_trace.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(
        os.path.join(HERE, "testdata", "small_trace.xplane.pb"),
        want["window_s"])
    check(got is not None, "recorded trace has a device plane")
    check(abs(got["busy_s"] - want["busy_s"]) < 1e-9,
          f"busy_s {got['busy_s']} != recorded {want['busy_s']}")
    for prog, secs in want["programs"].items():
        check(abs(got["programs"][prog]["seconds"] - secs) < 1e-9,
              f"device time of {prog}")
    idle = 100.0 * (1 - got["busy_s"] / got["window_s"])
    check(abs(idle - want["idle_share"]) < 1e-6, "idle share")


CASES = [  # (filter, topic, matches)
    ("a/b", "a/b", True), ("a/b", "a/b/c", False), ("a/+", "a/b", True),
    ("a/+", "a", False), ("a/+", "a/b/c", False), ("+/+", "a/b", True),
    ("a/#", "a", True), ("a/#", "a/b/c", True), ("#", "a/b", True),
    ("+", "a", True), ("+", "a/b", False), ("a/+/c", "a/b/c", True),
    ("a/+/c", "a//c", True), ("a/b/#", "a", False),
    ("#", "$SYS/x", False), ("+/x", "$SYS/x", False),
    ("$SYS/#", "$SYS/x", True), ("$SYS/+", "$SYS/x", True),
    ("a/+", "a/", True), ("/", "/", True), ("+/+", "/", True),
]


def check_reference() -> None:
    for flt, topic, want in CASES:
        got = reference.filter_matches(flt.split("/"), topic.split("/"))
        check(got == want, f"filter_matches({flt!r}, {topic!r}) = {got}")
        via = tuple(flt.split("/")) in set(
            reference.generalisations(topic.split("/")))
        check(via == want, f"generalisations({topic!r}) vs {flt!r} = {via}")
    check(reference.split_filter("$share/g1/a/+") == ("$share/g1", ("a", "+")),
          "$share prefix")
    check(reference.split_filter("$oshare/g/a/#") == ("$oshare/g", ("a", "#")),
          "$oshare prefix")
    check(reference.split_filter("a/$share/x") == (None, ("a", "$share", "x")),
          "a filter that merely holds $share")
    gen = importlib.import_module("generators.zipf_tree")
    rng = random.Random(7)
    names, cum = gen.level_names(12)
    table = reference.Table()
    filters = []
    for i in range(3000):
        levels = tuple(gen.gen_filter(rng, names, cum, max_depth=4,
                                      p_plus=0.3, p_hash=0.2))
        filters.append(levels)
        table.add("t", levels, (i,))
    for _ in range(300):
        topic = gen.gen_topic(rng, names, cum, max_depth=4)
        brute = sorted(i for i, f in enumerate(filters)
                       if reference.filter_matches(f, topic))
        fast = sorted(r[0] for r in table.match("t", "/".join(topic)))
        check(brute == fast, f"table.match != definition on {topic}")
    check(table.match("other", "l0") == [], "no row crosses a tenant")
    check_shared_rows(gen, rng, names, cum)
    check_retained_table(gen, rng, names, cum)
    check(len(reference.truncated(list(range(100)), 64)) == 64, "control")


def check_retained_table(gen, rng, names, cum) -> None:
    """``RetainedTable.match`` (a filter over retained topics) against the
    definition, on drawn topics and filters and on the ``$`` / parent
    cases; and a version's MUST / MAY around one SET."""
    table, topics = reference.RetainedTable(), []
    for _ in range(2000):
        topic = "/".join(gen.gen_topic(rng, names, cum, max_depth=4))
        if ("t", topic) not in table.tid_of:
            topics.append(topic)
            table.add("t", topic)
    for topic in ("$SYS/x", "$SYS/x/y", "a"):
        topics.append(topic)
        table.add("t", topic)
    filters = [tuple(gen.gen_filter(rng, names, cum, max_depth=4, p_plus=0.3,
                                    p_hash=0.2)) for _ in range(300)]
    filters += [("#",), ("+",), ("+", "x"), ("$SYS", "#"), ("a", "#")]
    for flt in filters:
        brute = sorted(i for i, t in enumerate(topics)
                       if reference.filter_matches(flt, t.split("/")))
        check(sorted(table.match("t", flt)) == brute,
              f"RetainedTable.match({flt}) != definition")
        check(table.match("other", flt) == (), "no topic crosses a tenant")
    table.apply(0, 1, 100, 200)          # SET v1 sent at 100, acked at 200
    check(table.must_may(0, 50, 90) == (True, True), "before the SET")
    check(table.must_may(0, 150, 160) == (False, True), "inside the SET")
    check(table.current_in(0, 0, 150, 160) and table.current_in(0, 1, 150, 160)
          and not table.current_in(0, 0, 250, 260),
          "versions around one SET")


SHARED_CASES = [  # (rows as filter strings, topic, {group filter: members})
    (["$share/g/a/+", "$share/g/a/+", "a/+"], "a/b",
     {"$share/g/a/+": [0, 1]}),
    (["$oshare/g/a/#", "$share/h/a/#"], "a",
     {"$oshare/g/a/#": [0], "$share/h/a/#": [1]}),
    # one group name under both prefixes, or over two filters: two groups
    (["$share/g/a/+", "$oshare/g/a/+", "$share/g/a/#"], "a/b",
     {"$share/g/a/+": [0], "$oshare/g/a/+": [1], "$share/g/a/#": [2]}),
    # behind the prefix a filter matches as any other: a leading wildcard
    # does not reach a $-topic, a literal first level does
    (["$share/g/+/x", "$share/g/#", "$share/g/$SYS/#"], "$SYS/x",
     {"$share/g/$SYS/#": [2]}),
    (["$share/g/+/x", "$share/g/#"], "a/x",
     {"$share/g/+/x": [0], "$share/g/#": [1]}),
    (["$share/g/a/b"], "a/c", {}),
    # no share prefix without a group and a filter behind it
    (["$share/g", "$share/+"], "$share/g", {}),
]


def check_shared_rows(gen, rng, names, cum) -> None:
    for filters, topic, want in SHARED_CASES:
        table = reference.Table()
        for i, flt in enumerate(filters):
            table.add("t", tuple(flt.split("/")), (i,))
        got = {g: sorted(r[0] for r in rows)
               for g, rows in table.match_groups("t", topic)}
        check(got == want, f"match_groups({filters}, {topic!r}) = {got}")
        plain = sorted(i for i, flt in enumerate(filters)
                       if not reference.is_shared(flt.split("/"))
                       and reference.filter_matches(flt.split("/"),
                                                    topic.split("/")))
        check(sorted(r[0] for r in table.match("t", topic)) == plain,
              f"match({filters}, {topic!r}) holds a shared row or loses a "
              "plain one")
        check(table.match_groups("other", topic) == [],
              "no group crosses a tenant")
    # against the definition, on drawn filters: every third row shared
    table, rows = reference.Table(), []
    for i in range(3000):
        levels = tuple(gen.gen_filter(rng, names, cum, max_depth=4,
                                      p_plus=0.3, p_hash=0.2))
        if i % 3 == 0:
            levels = (reference.SHARE_PREFIXES[i % 2], f"g{i % 5}") + levels
        rows.append(levels)
        table.add("t", levels, (i,))
    for _ in range(300):
        topic = gen.gen_topic(rng, names, cum, max_depth=4)
        brute = {}
        for i, levels in enumerate(rows):
            group, rest = reference.split_filter("/".join(levels))
            if group is not None and reference.filter_matches(rest, topic):
                brute.setdefault("/".join(levels), []).append(i)
        fast = {g: sorted(r[0] for r in members)
                for g, members in table.match_groups("t", "/".join(topic))}
        check(brute == fast, f"match_groups != definition on {topic}")
        plain = sorted(i for i, f in enumerate(rows) if i % 3
                       and reference.filter_matches(f, topic))
        check(plain == sorted(r[0] for r in table.match("t", "/".join(topic))),
              f"table.match beside shared rows != definition on {topic}")


def check_schedule() -> None:
    cfg = traffic.load_json("configs", "rehearsal_20k.json")
    for mix in ("rehearsal_open", "rehearsal_closed"):
        tr = traffic.load_json("traffic", mix + ".json")
        check_plan_purity(cfg, tr, mix)
    check_retained_schedule()


def check_retained_schedule() -> None:
    cfg = traffic.load_json("configs", "rehearsal_retained_20k.json")
    tr = traffic.load_json("traffic", "rehearsal_resub.json")
    check_plan_purity(cfg, tr, "rehearsal_resub")
    big = 2 ** 31 + 12345
    a = traffic.build_plan(cfg, tr, big, 5.0)
    c = traffic.build_plan(cfg, tr, big + 1, 5.0)
    check([(t, sorted(p)) for t, p in a["resub"]["lanes"]]
          == [(t, sorted(p)) for t, p in c["resub"]["lanes"]],
          "rehearsal_resub: seeds do not share the lanes' filters")
    check(sorted(e[1] for e in a["retain_sets"])
          == sorted(e[1] for e in c["retain_sets"])
          and [(e[0], e[5]) for e in a["retain_sets"]]
          == [(e[0], e[5]) for e in c["retain_sets"]],
          "rehearsal_resub: seeds do not share the SET / CLEAR multiset, "
          "instants and kinds")
    gen = traffic.generator_of(cfg)
    for at, tid, t, topic, nbytes, _kind in a["retain_sets"]:
        check(gen.retained_row(cfg, tid) == (a["tenants"][t], topic, nbytes)
              and at < 5.0, f"retained event {tid} {topic!r}")
    for i, row in enumerate(gen.retained(cfg)):
        if i % 997 == 0:
            check(gen.retained_row(cfg, i) == row, f"retained_row({i})")
    check(traffic.retained_header(traffic.retained_payload(7, 3, 64))
          == (7, 3) and traffic.retained_header(b"") is None,
          "retained payload header")


def check_plan_purity(cfg: dict, tr: dict, mix: str) -> None:
    big = 2 ** 31 + 12345
    a = traffic.build_plan(cfg, tr, big, 5.0)
    b = traffic.build_plan(cfg, tr, big, 5.0)
    c = traffic.build_plan(cfg, tr, big + 1, 5.0)
    check(traffic.fingerprint(a) == traffic.fingerprint(b),
          f"{mix}: the same seed gives another plan")
    check(traffic.fingerprint(a) != traffic.fingerprint(c),
          f"{mix}: another seed gives the same plan")
    key = "arrivals" if tr["loop"] == "open" else "cycle"
    check(sorted(x[-2:] for x in a[key]) == sorted(x[-2:] for x in c[key]),
          f"{mix}: seeds do not share the multiset of (tenant, topic)")
    check(a["subs"] == c["subs"], f"{mix}: live filters differ by seed")
    if tr["loop"] == "open":
        check(all(0 <= x[0] < 5.0 for x in a["arrivals"]),
              "an arrival outside the window")


def check_retained_config(cfg: dict, where: str) -> None:
    """A ``retained`` section needs a generator that seeds it and a limit
    the reference can read as data."""
    if "retained" not in cfg:
        return
    gen = importlib.import_module(f"generators.{cfg['generator']}")
    for fn in ("retained", "retained_count", "retained_row",
               "retained_stress_filters"):
        check(callable(getattr(gen, fn, None)),
              f"{where}: a retained section, and generator "
              f"{cfg['generator']!r} offers no {fn}(cfg)")
    check(callable(getattr(getattr(gen, "FilterSource", None),
                           "retained_topic", None)),
          f"{where}: a retained section, and generator {cfg['generator']!r} "
          "offers no FilterSource.retained_topic(rng)")
    check("RetainMessageMatchLimit" in cfg.get("settings", {}),
          f"{where}: a retained section without RetainMessageMatchLimit "
          "in its settings")


def check_retained_cell(cfg: dict, mix: dict, where: str) -> None:
    """SUBSCRIBE lanes and retained SETs need seeded retained messages."""
    for key in ("resub", "retain_set_per_s"):
        check(key not in mix or "retained" in cfg,
              f"{where}: the mix has {key!r} and the configuration no "
              "retained section")


def check_retained_window(cfg: dict, where: str) -> None:
    """A cell of BENCHMARK.json that seeds retained messages runs at the
    program's own ``MinSendPerSec``: at its default 8 a QoS 1 SUBSCRIBE
    whose receive window has shrunk is handed 8 of its 10 retained
    messages, and a raised floor would keep that from ``retained_missing``.
    The rehearsal (a tests file) states its raised floor as its exception."""
    check("retained" not in cfg or "MinSendPerSec" not in cfg.get(
        "settings", {}), f"{where}: a retained cell sets MinSendPerSec")


def check_files() -> None:
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind in ("configs", "traffic", "layer_metrics"):
        for path in glob.glob(os.path.join(HERE, kind, "*.json")):
            with open(path) as f:
                data = json.load(f)
            stem = os.path.basename(path)[:-5]
            check(NAME.match(stem), f"file name {stem!r}")
            if kind == "layer_metrics":
                check(data["name"] == stem, f"{path}: name != file name")
                check(UNIT.match(data["unit"]), f"{path}: unit")
                check(data["better"] in ("lower", "higher"), f"{path}: better")
                importlib.import_module(f"readers.{data['reader']}").read
            if kind == "configs":
                importlib.import_module(f"generators.{data['generator']}")
                check_retained_config(data, path)
                for key in data.get("reduced", []):
                    check(NAME.match(key), f"{path}: reduced key {key!r}")
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        check(NAME.match(m["name"]) and UNIT.match(m["unit"]),
              f"metric {m['name']!r} / unit {m['unit']!r}")
        check(set(m.get("workloads", [])) <= cells, f"{m['name']}: cells")
    for m in bench["per_layer"]:
        check(m["moves"] in e2e, f"{m['name']} moves {m['moves']!r}")
        spec = traffic.load_json("layer_metrics", m["name"] + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            check(spec[key] == m[key], f"{m['name']}: {key} differs from "
                  "its file under layer_metrics/")
    for w in bench["workloads"]:
        check(NAME.match(w["name"]) and len(w["why"]) <= 200, w["name"])
        traffic.load_cell(w["name"])
    for path in [None] + sorted(glob.glob(os.path.join(HERE, "tests",
                                                       "*_bench.json"))):
        with open(path or os.path.join(root, "BENCHMARK.json")) as f:
            cells = json.load(f)["workloads"]
        for w in cells:
            cell = traffic.load_cell(w["name"], path or "")
            check_retained_cell(cell["config"], cell["traffic"], w["name"])
            if path is None:
                check_retained_window(cell["config"], w["name"])
    peaks = traffic.load_json("peaks.json")
    check(peaks["source"] and "TPU v5 lite" in peaks["peaks"], "peaks.json")


def main() -> None:
    for fn in (check_intervals, check_reference, check_schedule, check_files,
               check_recorded_trace):
        fn()
        print(f"ok  {fn.__name__}")
    print("selfcheck passed")


if __name__ == "__main__":
    main()
