"""``correct`` at a size a test run can hold, on the CPU: sound runs pass
every limit, and the control and each fault the cell can have fail one.

The control is the reference computed in bfloat16 and put in the
program's place. The faults are planted in the program underneath a
whole run of the harness (the look for a chip skipped): a step that
returns its state unchanged, half of the batch left out with the
result taken over the rest, and an answer altered where it is produced.
No cell runs on more than one chip, so none can lose the exchange
between chips. The same readings at each cell's own size come from
``perfbench/controls.py`` on the chip (``PERF.md`` gives them).
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import controls, run  # noqa: E402

# (traffic, configuration) overrides of each cell
SMALL = {
    "fig4-grid": ({"fleets": [2, 10, 25]}, {"samples_per_device": 300}),
    "fleet-4096": ({}, {"samples_per_device": 10}),
}
SEED = 2**31 + 11


def _run(cell, seed=SEED):
    traffic, config = SMALL[cell]
    return run.run_cell(cell, seed, 0.1, False, need_chip=False,
                        traffic_override=traffic, config_override=config)


def _failed(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], _failed(r)
    assert r["counters"]["window_compiles"] == 0


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_fails(cell):
    traffic, config = SMALL[cell]
    prog, ctrl = controls.readings(cell, [SEED], {SEED}, need_chip=False,
                                   traffic_override=traffic,
                                   config_override=config,
                                   emit=lambda s: None)
    from perfbench.core import spec
    _, _, traffic = spec.cell(spec.load_benchmark(), cell)
    limits = traffic["limits"]
    assert all(v <= limits[k] for k, v in prog.items() if k in limits)
    assert any(v > limits[k] for k, v in ctrl.items() if k in limits), ctrl


def test_program_counts_a_batch_at_launch():
    # the witness for the reference's attribution: the program matches
    # the reference that counts a forwarded sample in its window when the
    # batch is launched, to rounding, and not the one that counts it when
    # the batch finishes (window SR traces apart by a point or so)
    from perfbench.core import spec
    from perfbench.drivers import sim_sweep
    _, config, traffic = spec.cell(spec.load_benchmark(), "fig4-grid")
    drv = sim_sweep.Driver(
        dict(config, samples_per_device=300),
        dict(traffic, schedulers=["static"], fleets=[100], stream_seeds=1),
        SEED)
    drv.setup()
    out = drv.unit()["out"]
    at = {a: sim_sweep.compare(out, 0, drv.reference(0, attribute=a))
          for a in ("launch", "finish")}
    assert at["launch"]["trace_sr"] < 1e-3 < 0.1 < at["finish"]["trace_sr"]
    assert at["launch"]["trace_acc"] < 1e-5 < at["finish"]["trace_acc"]


# ---------------------------------------------------------------------------
# faults planted in the sim core
# ---------------------------------------------------------------------------
@pytest.fixture
def jaxsim_fault(monkeypatch):
    from repro.sim import jaxsim

    def plant(run_sweep):
        monkeypatch.setattr(jaxsim, "run_sweep", run_sweep)
        jaxsim._make_core.cache_clear()
    yield jaxsim, plant
    jaxsim._make_core.cache_clear()


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sim_state_unchanged_fails(cell, jaxsim_fault, monkeypatch):
    jaxsim, plant = jaxsim_fault
    real = jaxsim.run_sweep

    def frozen(*a, **k):
        # the event loop returns its initial state: no step is taken
        monkeypatch.setattr(jaxsim.jax.lax, "while_loop",
                            lambda cond, body, init: init)
        return real(*a, **k)
    plant(frozen)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sim_half_batch_fails(cell, jaxsim_fault):
    jaxsim, plant = jaxsim_fault
    real = jaxsim.run_sweep

    def half(specs, streams, latency, slo, *a, **k):
        # half of each lane's devices left out, the metrics taken over
        # the rest
        import dataclasses
        specs = [dataclasses.replace(s, n_devices=-(-s.n_devices // 2))
                 for s in specs]
        w = max(s.n_devices for s in specs)
        return real(specs, {k: v[:, :w] for k, v in streams.items()},
                    latency[:w], slo[:w], *a, **k)
    plant(half)
    r = _run(cell)
    assert not r["correct"] and "lost_samples" in _failed(r)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sim_altered_answer_fails(cell, jaxsim_fault):
    jaxsim, plant = jaxsim_fault
    real = jaxsim.run_sweep

    def altered(specs, streams, *a, **k):
        # the light model's answer (its confidence) on every sample is
        # altered where the core reads it
        streams = dict(streams, confidence=1 - streams["confidence"])
        return real(specs, streams, *a, **k)
    plant(altered)
    assert not _run(cell)["correct"]
