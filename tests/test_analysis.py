"""graftcheck suite (ISSUE 10): the package must analyze clean, every
rule must fire on its known-violation fixture and stay quiet on the
clean twin, and the suppression machinery must be honest (dead entries
fail, justifications mandatory)."""

import json
import os

import pytest

from bifromq_tpu import analysis
from bifromq_tpu.analysis import (SuppressionError, build_info,
                                  parse_suppressions, run_analysis)
from bifromq_tpu.analysis.donation import UseAfterDonateRule
from bifromq_tpu.analysis.drift import RegistryDriftRule
from bifromq_tpu.analysis.envknobs import EnvKnobRule
from bifromq_tpu.analysis.hostsync import HostSyncRule
from bifromq_tpu.analysis.locks import LockDisciplineRule

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


def fixture_findings(rule_cls):
    report = run_analysis(root=FIXTURES, readme=None, suppressions=None,
                          rules=[rule_cls])
    return report.findings


@pytest.fixture(scope="module")
def default_report():
    """One full-package analysis shared by every assertion over it —
    the tree is immutable for the test run and each analysis costs
    ~2.5s."""
    return run_analysis()


# ---------------------------------------------------------------------------
# tier-1 gate: the installed package is clean
# ---------------------------------------------------------------------------

class TestPackageClean:
    def test_zero_unsuppressed_findings(self, default_report):
        assert default_report.findings == [], \
            "unsuppressed graftcheck findings:\n" + "\n".join(
                f.render() for f in default_report.findings)

    def test_no_dead_suppressions(self, default_report):
        assert default_report.dead_suppressions == [], \
            "dead suppression entries (fix = delete the line):\n" \
            + "\n".join(s.key for s in default_report.dead_suppressions)

    def test_all_five_rules_ran(self, default_report):
        assert sorted(default_report.rule_ids) == \
            ["R1", "R2", "R3", "R4", "R5"]

    def test_suppressions_carry_justifications(self):
        sups = parse_suppressions(analysis.SUPPRESSIONS_PATH)
        assert sups, "suppression file unexpectedly empty"
        for s in sups:
            assert len(s.justification) > 10, s.key


# ---------------------------------------------------------------------------
# per-rule fixtures: fires exactly on the violation file, silent on the twin
# ---------------------------------------------------------------------------

class TestRuleFixtures:
    def _split(self, findings, n):
        bad = [f for f in findings if f.path == f"r{n}_violation.py"]
        clean = [f for f in findings if f.path == f"r{n}_clean.py"]
        return bad, clean

    def test_r1_host_sync(self):
        bad, clean = self._split(fixture_findings(HostSyncRule), 1)
        assert clean == [], [f.render() for f in clean]
        symbols = {f.symbol for f in bad}
        assert "np.asarray" in symbols
        assert ".item" in symbols
        assert "float()" in symbols
        assert ".tolist" in symbols          # nested def inherits hotness
        # ...but is reported ONLY under its own scope key — one line
        # must need exactly one suppression entry
        assert not any(f.scope == "outer" for f in bad), \
            [f.key for f in bad]

    def test_r2_use_after_donate(self):
        bad, clean = self._split(fixture_findings(UseAfterDonateRule), 2)
        assert clean == [], [f.render() for f in clean]
        scopes = {f.scope for f in bad}
        assert "bad_read_after_donate" in scopes
        assert "bad_alias" in scopes         # one-hop alias followed
        # a closure-local reassignment in a nested def must not close
        # the enclosing function's donation window
        assert "bad_closure_shadow" in scopes

    def test_r3_env_knobs(self):
        bad, clean = self._split(fixture_findings(EnvKnobRule), 3)
        assert clean == [], [f.render() for f in clean]
        symbols = {f.symbol for f in bad}
        assert "BIFROMQ_FIXTURE_RAW" in symbols
        assert "BIFROMQ_FIXTURE_SUB" in symbols
        assert "BIFROMQ_FIXTURE_IN" in symbols
        assert "BIFROMQ_FIX_*" in symbols    # f-string dynamic suffix
        frozen = [f for f in bad if f.symbol == "BIFROMQ_FIXTURE_FROZEN"]
        assert frozen and frozen[0].scope == ""   # module-level freeze
        # class bodies and def default expressions execute at import
        # too — same frozen-knob class
        assert "BIFROMQ_FIXTURE_CLASS_FROZEN" in symbols
        assert "BIFROMQ_FIXTURE_DEFAULT_FROZEN" in symbols

    def test_r4_locks(self):
        bad, clean = self._split(fixture_findings(LockDisciplineRule), 4)
        assert clean == [], [f.render() for f in clean]
        symbols = {f.symbol for f in bad}
        assert any("<>" in s for s in symbols), symbols   # order pair
        assert "time.sleep" in symbols
        assert "_slow_helper->time.sleep" in symbols      # one-level
        # `with lock, open(...)`: later items run under earlier locks
        assert any(f.symbol == "open"
                   and f.scope == "bad_multi_item_with" for f in bad)

    def test_r5_registry_drift(self):
        bad, clean = self._split(fixture_findings(RegistryDriftRule), 5)
        assert clean == [], [f.render() for f in clean]
        symbols = {f.symbol for f in bad}
        assert "devcie.dispatch" in symbols   # typo'd stage
        assert "hist" in symbols              # typo'd cache field

    def test_r5_boundary_without_a_registry_row(self):
        # one registry of boundary names (trace/names.py): a span or a
        # counter opened under a name it lacks is a finding
        bad, clean = self._split(fixture_findings(RegistryDriftRule), 5)
        assert clean == [], [f.render() for f in clean]
        symbols = {f.symbol for f in bad}
        assert "deliver.fanuot" in symbols    # typo'd span
        assert "redy.polls" in symbols        # typo'd counter

    def test_r5_dead_row_and_readme_drift(self, tmp_path):
        # a row nothing opens, a stage nothing feeds and a README table
        # that lags the registry each fail, in a tree of its own
        pkg = tmp_path / "pkg"
        (pkg / "trace").mkdir(parents=True)
        (pkg / "trace" / "names.py").write_text(
            "def _row(*a, **k): pass\n"
            "_row('live.span', 'span', 'm.py', 'x', stage='live')\n"
            "_row('dead.span', 'span', 'm.py', 'x', stage='dead')\n")
        (pkg / "m.py").write_text(
            "from . import trace\n"
            "def f():\n"
            "    with trace.span('live.span'):\n"
            "        pass\n")
        readme = tmp_path / "README.md"
        readme.write_text("| span | where |\n|---|---|\n"
                          "| `live.span` | m |\n| `gone.span` | m |\n")
        report = run_analysis(root=str(pkg), readme=str(readme),
                              suppressions=None,
                              rules=[RegistryDriftRule])
        found = {(f.scope, f.symbol) for f in report.findings}
        assert ("<BOUNDARIES>", "dead.span") in found
        assert ("<KNOWN_STAGES>", "dead") in found
        assert ("<span-table>", "gone.span") in found
        assert ("<span-table>", "dead.span") in found
        assert not any(f.symbol in ("live.span", "live")
                       for f in report.findings)


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_dead_suppression_fails_run(self, tmp_path):
        sup = tmp_path / "sups.txt"
        sup.write_text("R1 nowhere.py ghost np.asarray -- covers nothing\n")
        report = run_analysis(root=FIXTURES, readme=None,
                              suppressions=str(sup),
                              rules=[HostSyncRule])
        assert len(report.dead_suppressions) == 1
        assert not report.clean

    def test_live_suppression_absorbs_finding(self, tmp_path):
        sup = tmp_path / "sups.txt"
        sup.write_text("R1 r1_violation.py bad_asarray np.asarray "
                       "-- fixture exercises the suppression path\n")
        report = run_analysis(root=FIXTURES, readme=None,
                              suppressions=str(sup),
                              rules=[HostSyncRule])
        assert not any(f.scope == "bad_asarray" for f in report.findings)
        assert any(s.key.endswith("np.asarray")
                   for _, s in report.suppressed)
        assert not report.dead_suppressions

    def test_missing_justification_rejected(self, tmp_path):
        sup = tmp_path / "sups.txt"
        sup.write_text("R1 a.py b np.asarray\n")
        with pytest.raises(SuppressionError):
            parse_suppressions(str(sup))

    def test_empty_justification_rejected(self, tmp_path):
        sup = tmp_path / "sups.txt"
        sup.write_text("R1 a.py b np.asarray --   \n")
        with pytest.raises(SuppressionError):
            parse_suppressions(str(sup))

    def test_write_stamp_refuses_custom_root(self, tmp_path):
        # the checked-in stamp describes the installed package; a clean
        # run over some other tree must never overwrite it
        from bifromq_tpu.analysis.__main__ import main
        clean = tmp_path / "pkg"
        clean.mkdir()
        (clean / "mod.py").write_text("X = 1\n")
        rc = main(["--root", str(clean), "--write-stamp"])
        assert rc == 2


# ---------------------------------------------------------------------------
# stamp / build-info surface
# ---------------------------------------------------------------------------

class TestStamp:
    def test_checked_in_stamp_well_formed(self):
        with open(analysis.STAMP_PATH, encoding="utf-8") as f:
            stamp = json.load(f)
        assert stamp["rules"] == 5
        assert stamp["unsuppressed"] == 0
        assert stamp["dead_suppressions"] == 0
        assert stamp["suppressions"] > 0
        assert len(stamp["hash"]) == 16

    def test_build_info_never_raises(self):
        info = build_info()
        assert info["stamp"] == "ok"
        assert info["rules"] == 5

    def test_hash_is_deterministic(self, default_report):
        assert run_analysis().stamp_hash() == default_report.stamp_hash()

    def test_dead_rule_config_fails(self, tmp_path):
        # HOT_SCOPES/KNOWN_DONATING rot like suppressions would: a
        # renamed hot scope must surface as a finding, not silence
        from bifromq_tpu.analysis.hostsync import HostSyncRule
        pkg = tmp_path / "models"
        pkg.mkdir()
        (pkg / "matcher.py").write_text("def renamed_away():\n    pass\n")
        (tmp_path / "ops").mkdir()
        (tmp_path / "ops" / "match.py").write_text("X = 1\n")
        report = run_analysis(root=str(tmp_path), readme=None,
                              suppressions=None, rules=[HostSyncRule])
        assert any(f.scope == "<config>" for f in report.findings)
        from bifromq_tpu.analysis.donation import UseAfterDonateRule
        report = run_analysis(root=str(tmp_path), readme=None,
                              suppressions=None,
                              rules=[UseAfterDonateRule])
        assert any(f.scope == "<config>" for f in report.findings)

    def test_metrics_carries_build_info(self):
        # the API server composes build_info into /metrics; the handler
        # path is covered by test_apiserver — here just the payload shape
        from bifromq_tpu.analysis import build_info as bi
        payload = {"build_info": {"graftcheck": bi()}}
        g = payload["build_info"]["graftcheck"]
        assert {"rules", "suppressions", "hash"} <= set(g)
