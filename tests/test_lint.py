"""Tier-1 pins for the static-analysis subsystem (tools/lint.py).

Four contracts, mirroring the acceptance gates of the lint CI job:

* every negative-corpus snippet fires exactly its named rule (the
  rules have teeth and stay aimed);
* the shipped tree is clean — zero findings under the checked-in
  allowlist, no stale entries, no rule crashes, all rules executed
  (so the CI gate passing is a property of the code, not of the gate
  silently going vacuous);
* the lane-invariant checker passes the *real* ``lane_stepper`` body
  and fails a mutated copy (the checker is pinned against both false
  positives and false negatives on the real engine);
* the fail-closed CLI semantics: unknown ``--require`` names and
  stale allowlist entries are run failures, not warnings.
"""
import dataclasses
import pathlib
import subprocess
import sys

import pytest

from repro.analysis import driver, lane_rules
from repro.analysis.allowlist import AllowEntry, load_allowlist

REPO = pathlib.Path(__file__).resolve().parents[1]
CORPUS = REPO / "tests" / "lint_corpus"

# snippet -> the one rule it exists to trip
CORPUS_EXPECT = {
    "bad_td001.py": "TD001",
    "bad_td002.py": "TD002",
    "bad_td003.py": "TD003",
    "bad_td004.py": "TD004",
    "bad_hd001.py": "HD001",
    "bad_hd002.py": "HD002",
    "bad_hd003.py": "HD003",
    "bad_hd004.py": "HD004",
    "bad_lm001.py": "LM001",
    "bad_lm002.py": "LM002",
    "bad_cc001.py": "CC001",
    "bad_cc002.py": "CC002",
    "bad_cc003.py": "CC003",
}


@pytest.mark.parametrize("fname,rule", sorted(CORPUS_EXPECT.items()))
def test_corpus_snippet_fires(fname, rule):
    rep = driver.run_lint([str(CORPUS / fname)])
    assert not rep.rule_errors, rep.rule_errors
    fired = {f.rule for f in rep.findings}
    assert fired == {rule}, \
        (fired, [f.render() for f in rep.findings])


def test_corpus_covers_every_rule():
    assert set(CORPUS_EXPECT.values()) == \
        {r.id for r in driver.all_rules()}


def test_clean_tree_zero_findings():
    """The shipped tree passes its own linter: no findings beyond the
    checked-in allowlist, no stale entries, no crashed rule, and all
    thirteen rules actually executed (no vacuous pass)."""
    entries = load_allowlist(str(REPO / "tools" / "lint_allowlist.toml"))
    rep = driver.run_lint(allowlist=entries)
    assert not rep.rule_errors, rep.rule_errors
    assert rep.findings == [], [f.render() for f in rep.findings]
    assert rep.stale_allowlist == [], \
        [f.render() for f in rep.stale_allowlist]
    assert set(rep.executed) == {r.id for r in driver.all_rules()}
    assert all(e.hits > 0 for e in entries)


# ---------------------------------------------------------------------------
# the lane checker against the real engine body
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_lane_entry():
    return lane_rules.default_lane_entries()[0]


def test_lane_checker_passes_real_body(real_lane_entry):
    findings = lane_rules.check_lane_entry(real_lane_entry)
    assert findings == [], [f.render() for f in findings]


def test_unknown_wrapper_primitive_fails_loudly(real_lane_entry):
    """A body wrapped in a call primitive other than ``jit`` must make
    the checker raise: read as one opaque eqn, every output would
    depend on every input and the checks would pass vacuously."""
    import jax

    wrapped = dataclasses.replace(real_lane_entry,
                                  body=jax.checkpoint(real_lane_entry.body),
                                  name="remat-wrapped-lane")
    with pytest.raises(ValueError, match="unknown wrapper primitive"):
        lane_rules.check_lane_entry(wrapped)


def test_lane_checker_fails_mutated_body(real_lane_entry):
    """A one-line mutation — a carry leaf overwritten with real data
    that carries no active-lane dependence — must be caught."""
    body = real_lane_entry.body

    def mutated(st):
        out = dict(body(st))
        out["t"] = st["frontier"]      # ungated: bypasses the predicate
        return out

    bad = dataclasses.replace(real_lane_entry, body=mutated,
                              name="mutated-lane")
    findings = lane_rules.check_lane_entry(bad)
    assert any(f.rule == "LM001" and "t" in f.symbol for f in findings), \
        [f.render() for f in findings]


def test_lane_checker_rejects_constant_overwrite(real_lane_entry):
    """A leaf clobbered with a constant is flagged even though it has
    no dataflow at all (neither identity nor an active-gated write)."""
    import jax.numpy as jnp
    body = real_lane_entry.body

    def mutated(st):
        out = dict(body(st))
        out["last_done_t"] = jnp.zeros_like(st["last_done_t"])
        return out

    bad = dataclasses.replace(real_lane_entry, body=mutated,
                              name="constant-lane")
    findings = lane_rules.check_lane_entry(bad)
    assert any(f.rule == "LM001" for f in findings), \
        [f.render() for f in findings]


# ---------------------------------------------------------------------------
# allowlist + CLI fail-closed semantics
# ---------------------------------------------------------------------------
def test_allowlist_suppression_and_staleness():
    hit = AllowEntry("HD003", "tests/lint_corpus/bad_hd003.py",
                     "make_step", "corpus pin")
    stale = AllowEntry("HD001", "no/such/file.py", None, "obsolete")
    rep = driver.run_lint([str(CORPUS / "bad_hd003.py")],
                          allowlist=[hit, stale])
    assert rep.findings == []            # the real finding is suppressed
    assert len(rep.suppressed) == 1 and hit.hits == 1
    assert len(rep.stale_allowlist) == 1  # the dead entry is an error
    assert "obsolete" in rep.stale_allowlist[0].message


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint.py"), *argv],
        capture_output=True, text=True, cwd=str(REPO))


def test_cli_require_unknown_name_fails():
    """--require mirrors check_bench --require: a gate that cannot run
    is a failure, never a silent pass."""
    r = _run_cli(str(CORPUS / "bad_cc001.py"), "--allowlist", "none",
                 "--require", "definitely-missing-rule")
    assert r.returncode != 0, r.stdout + r.stderr
    assert "definitely-missing-rule" in r.stdout + r.stderr


def test_cli_require_vacuous_family_fails():
    """Requiring a family with nothing to act on (the target module
    exports no trace entries) fails as vacuous rather than passing —
    HD001's warn finding alone would not fail at --fail-on error."""
    r = _run_cli(str(CORPUS / "bad_hd001.py"), "--allowlist", "none",
                 "--fail-on", "error", "--require", "trace-discipline")
    assert r.returncode != 0, r.stdout + r.stderr
