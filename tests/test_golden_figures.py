"""Golden regression fixtures for the paper-figure benchmarks.

``tests/golden/figures.json`` pins the behavioural metrics (satisfaction
rate, accuracy, throughput, per-tier slices) of every sim figure at
``--quick`` settings, captured from the event-jump core with stream
fixture v2 (``synthetic.STREAM_FIXTURE_VERSION``: SeedSequence-keyed
vectorized generation — the v1 ``seed*1000+i`` per-device derivation
collided across sweep seeds at n_devices >= 1000, so the fixture was
regenerated at the bump). This test re-runs the figures through the
current engine and fails on drift beyond tolerance — proving engine
changes (event-jump rewrite, sharded sweep engine, ...) are
behaviour-preserving end to end, not just on the unit level.

Observed drift at the event-jump switchover: sr <= 4.31 (a knife-edge
per-tier slice under overload; overall sr <= 1.6), acc <= 0.0024,
throughput <= 0.5% relative — the tolerances
(``benchmarks.common.GOLDEN_TOL``) leave modest headroom over that. To
re-capture after an *intentional* behaviour change (e.g. a
stream-fixture bump):

    PYTHONPATH=src python tools/capture_golden.py

and document why in the commit message.
"""
import json
import pathlib

import pytest

GOLDEN = pathlib.Path(__file__).parent / "golden" / "figures.json"


@pytest.fixture(scope="module")
def current_rows():
    """All sim figures at the fixture's settings through the current
    engine — the same capture path tools/capture_golden.py writes with."""
    from benchmarks.common import capture_figure_rows
    return capture_figure_rows(json.loads(GOLDEN.read_text())["_settings"])


def test_no_drift_vs_golden(current_rows):
    """Drift beyond ``benchmarks.common.GOLDEN_TOL`` (per metric family)
    fails; the comparison is shared with chip_smoke.py."""
    from benchmarks.common import golden_drift
    golden = json.loads(GOLDEN.read_text())["rows"]
    failures = golden_drift(current_rows, golden)
    assert not failures, "golden drift:\n" + "\n".join(failures)


def test_golden_fixture_version_current():
    """A stream-derivation bump without a fixture re-capture would make
    every drift failure below meaningless — fail fast on the version."""
    from repro.sim.synthetic import STREAM_FIXTURE_VERSION
    settings = json.loads(GOLDEN.read_text())["_settings"]
    assert settings.get("stream_fixture") == STREAM_FIXTURE_VERSION, (
        "stream fixture version changed; re-capture with "
        "tools/capture_golden.py and document why")


def test_golden_covers_all_figures(current_rows):
    prefixes = {n.split("/")[0] for n in current_rows}
    assert {"fig4_homog", "fig7_effb3", "fig10_convergence",
            "fig11_hetero", "fig15_vit", "fig17_switch",
            "fig19_intermittent", "fig_churn", "ablation"} <= prefixes
