"""Shared harness for the paper-figure benchmarks.

Each figure module exposes ``run() -> list[Row]``; benchmarks/run.py
prints them as ``name,us_per_call,derived`` CSV (us_per_call = wall time
of the sim/kernel call per sweep point; derived = the figure's metrics).

All sim figures go through ``sweep`` below: the seeds of one sweep point
run batched in a single lane-aligned call — sharded over ``MESH`` when
``benchmarks/run.py --mesh-shape`` configured one — and sample streams
are cached so the schedulers of one figure share them instead of
regenerating.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import pathlib
import time
from typing import Dict, List

import numpy as np

from repro.configs.cascade_tiers import (DEVICE_PROFILES, SERVER_PROFILES,
                                         DeviceProfile, ServerProfile)
from repro.core.calibration import calibrate_static_threshold
from repro.sim import jaxsim, synthetic

SEEDS = (0, 1, 2)            # paper: three seeds, report mean/min/max
SAMPLES = 600                # per device (paper: 5000; scaled for CPU)
DEVICE_COUNTS = (2, 5, 10, 25, 50, 100)
MESH = None                  # set by run.py --mesh-shape; None = one chip

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
GOLDEN_FIGURES = REPO_ROOT / "tests" / "golden" / "figures.json"

# golden-figure drift tolerances, per metric family: just above the
# drift observed at the event-jump switchover (sr <= 4.31 on a knife-
# edge per-tier slice under overload, overall sr <= 1.6; acc <= 0.0024;
# throughput <= 0.5% relative)
GOLDEN_TOL = {
    "sr": 5.0,         # absolute, for 0-100 sr-family metrics
    "acc": 0.01,       # absolute, for [0,1] accuracy-family metrics
    "thr": 0.03,       # relative, for throughput (samples/s)
    "corr": 0.5,       # absolute, for the fig19 threshold/activity corr
    "switches": 1.0,   # absolute, for fig17 model-switch counts
}


def use_compile_cache() -> None:
    """Persistent compile cache for an entry point: where
    ``JAX_COMPILATION_CACHE_DIR`` says if it is set (JAX reads it
    itself), else the fixed ``<repo>/.jax_cache`` — a fixed path, since
    the directory is part of what a later run must find again."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def sweep(specs, streams, dev_latency, slo, servers, **kw):
    """Every figure's sweep call funnels through here so one flag shards
    the whole harness: ``run_sweep_sharded`` over ``MESH`` (bitwise equal
    to ``run_sweep`` when MESH is None or single-lane)."""
    return jaxsim.run_sweep_sharded(specs, streams, dev_latency, slo,
                                    servers, mesh=MESH, **kw)


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


@functools.lru_cache(maxsize=None)
def static_threshold_for(dev: DeviceProfile, srv: ServerProfile) -> float:
    cal = synthetic.calibration_set(dev.accuracy, srv.accuracy)
    t, _ = calibrate_static_threshold(cal.confidence, cal.correct_light,
                                      cal.correct_heavy[:, 0])
    return t


@functools.lru_cache(maxsize=32)
def _streams_cached(seeds, n, samples, light_accs, heavy_accs):
    return synthetic.batched_device_streams(
        seeds, n, samples, np.asarray(light_accs), list(heavy_accs))


def cached_streams(seeds, n, samples, light_accs, heavy_accs):
    """Batched (len(seeds), n, samples) streams, cached across schedulers
    so every figure generates each stream tensor once."""
    light = tuple(float(a) for a in np.atleast_1d(light_accs))
    heavy = tuple(float(a) for a in np.atleast_1d(heavy_accs))
    return _streams_cached(tuple(seeds), n, samples,
                           light[0] if len(light) == 1 else light, heavy)


def run_point(scheduler: str, n: int, dev: DeviceProfile,
              servers, slo: float, *, seeds=None, samples=None,
              static_t: float | None = None, **sim_kw) -> Dict:
    """Mean/min/max over seeds of (sr, accuracy, throughput).

    All seeds run in ONE batched ``run_sweep`` call; seeds/samples default
    to the *current* module values so ``--quick`` applies everywhere.
    """
    seeds = SEEDS if seeds is None else seeds
    samples = SAMPLES if samples is None else samples
    if static_t is None and scheduler == "static":
        static_t = static_threshold_for(dev, servers[0])
    streams = cached_streams(seeds, n, samples, dev.accuracy,
                             [s.accuracy for s in servers])
    spec = jaxsim.JaxSimSpec(
        scheduler=scheduler, n_devices=n, samples_per_device=samples,
        static_threshold=static_t or 0.35, **sim_kw)
    t0 = time.perf_counter()
    out = sweep(spec, streams, np.full(n, dev.latency),
                np.full(n, slo), tuple(servers))
    srs = np.asarray(out["sr"], np.float64)
    accs = np.asarray(out["accuracy"], np.float64)
    thrs = np.asarray(out["throughput"], np.float64)
    wall = time.perf_counter() - t0
    return {
        "sr": float(srs.mean()), "sr_min": float(srs.min()),
        "sr_max": float(srs.max()),
        "acc": float(accs.mean()),
        "thr": float(thrs.mean()),
        "wall_us": wall / len(seeds) * 1e6,
    }


def derived_str(d: Dict) -> str:
    return (f"sr={d['sr']:.2f};sr_min={d['sr_min']:.2f};"
            f"sr_max={d['sr_max']:.2f};acc={d['acc']:.4f};thr={d['thr']:.1f}")


# behavioural sim figures, in run order — the golden fixture's coverage.
# fig11_scaleout is deliberately absent: it is a perf probe of the
# sharded engine, not a behaviour row.
SIM_FIGURE_MODULES = (
    "fig4_homogeneous", "fig7_heavy_server", "fig10_convergence",
    "fig11_heterogeneous", "fig15_transformers", "fig17_switching",
    "fig19_intermittent", "fig_churn", "ablation_components")


def capture_figure_rows(settings: Dict) -> Dict[str, Dict[str, float]]:
    """Run every behavioural sim figure at ``settings`` and return
    ``{row_name: {metric: value}}`` (perf probe rows dropped).

    The single source of truth for golden-fixture capture: both
    tests/test_golden_figures.py and tools/capture_golden.py call this,
    so the figure list and the ``derived`` parsing can never diverge
    between the gate and the re-capture tool. Module settings are
    restored on exit.
    """
    import importlib

    global SEEDS, SAMPLES, DEVICE_COUNTS
    old = (SEEDS, SAMPLES, DEVICE_COUNTS)
    SEEDS = tuple(settings["seeds"])
    SAMPLES = settings["samples"]
    DEVICE_COUNTS = tuple(settings["device_counts"])
    try:
        rows = {}
        for name in SIM_FIGURE_MODULES:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row in mod.run():
                if "probe" in row.name:
                    continue
                rows[row.name] = {
                    k: float(v) for k, v in
                    (kv.split("=") for kv in row.derived.split(";"))}
        return rows
    finally:
        SEEDS, SAMPLES, DEVICE_COUNTS = old


def _golden_family(key: str) -> str:
    if "corr" in key:
        return "corr"
    if key.startswith("acc"):
        return "acc"
    if key.startswith("switches"):
        return "switches"
    if key.startswith("thr"):
        return "thr"
    return "sr"      # sr, sr_min, sr_max, sr_<tier>


def golden_drift(current: Dict[str, Dict[str, float]],
                 golden: Dict[str, Dict[str, float]]) -> List[str]:
    """Every metric of ``current`` (``capture_figure_rows``' output)
    that drifted from ``golden`` beyond ``GOLDEN_TOL``; [] when none."""
    if set(current) != set(golden):
        return ["figure row set changed; re-capture "
                "tests/golden/figures.json"]
    failures = []
    for name, gm in golden.items():
        cm = current[name]
        for key, gv in gm.items():
            if key not in cm:
                failures.append(f"{name}: {key} missing")
                continue
            cv = cm[key]
            if math.isnan(gv) or math.isnan(cv):
                if math.isnan(gv) != math.isnan(cv):
                    failures.append(f"{name}: {key} nan mismatch "
                                    f"golden={gv} now={cv}")
                continue
            fam = _golden_family(key)
            tol = GOLDEN_TOL[fam]
            if fam == "thr":
                tol *= max(abs(gv), 1e-9)
            if abs(cv - gv) > tol:
                failures.append(
                    f"{name}: {key} golden={gv:.4f} now={cv:.4f}")
    return failures
