"""Lane-aligned batched event engine: cross-lane isolation test suite.

The flat (B, ...) engine in repro.sim.jaxsim advances every lane
independently with masked per-field writes — a classic source of
cross-lane contamination if any mask is wrong. These tests pin the
isolation guarantees:

* per-lane bitwise equality against serial ``run`` for heterogeneous
  lane mixes: different schedulers, device counts (``n_real`` is
  traced), latency scales (and thus early-exit times) and offline
  windows packed into ONE ``run_sweep`` call;
* companion independence: a lane's results are bitwise identical no
  matter which other lanes share the batch or in what order;
* inert padding: garbage in a narrower lane's stream rows beyond its
  own ``n_devices`` must not leak into any lane's results;
* one compiled core serves every mix that shares static structure
  (the recompile guard);
* event-frontier invariants, property-tested by stepping the engine's
  real loop body via ``jaxsim.lane_stepper``: the frontier is
  non-decreasing per lane, an inactive lane is bitwise frozen, and
  ``any(active)`` going False means every lane fully drained.
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic mini engine from conftest
    from conftest import given, settings, st  # noqa: F401

import jax

import compile_guard
from lane_utils import SCALARS, assert_lane_bitwise, pack_lanes
from repro.configs.cascade_tiers import SERVER_PROFILES
from repro.sim import jaxsim, synthetic

SERVERS = (SERVER_PROFILES["inceptionv3"], SERVER_PROFILES["efficientnetb3"])
SAMPLES = 64
LIGHT_ACC = 0.72


@dataclasses.dataclass
class LaneCase:
    seed: int
    scheduler: str
    n: int
    lat_scale: float           # per-lane latency magnitude -> duration
    model_switching: bool = False
    offline: bool = False
    churn: bool = False        # rng join/leave schedule for ~40% of devices
    drift: bool = False        # bursty MMPP arrivals (non-stationary)
    static_threshold: float = 0.55
    init_threshold: float = 0.5


# deliberately heterogeneous: schedulers, device counts, an ~8x latency
# spread (the fast lane early-exits while the slow one still runs), one
# offline lane and one switching lane — all in a single batch
MIX = (
    LaneCase(0, "multitasc++", n=6, lat_scale=0.08),
    LaneCase(1, "multitasc", n=3, lat_scale=0.35),
    LaneCase(2, "static", n=8, lat_scale=0.05, static_threshold=0.7),
    LaneCase(3, "multitasc++", n=2, lat_scale=0.2, offline=True),
    LaneCase(4, "static", n=5, lat_scale=0.12, model_switching=True),
)


def _lane_inputs(case: LaneCase, samples=SAMPLES):
    """One lane's unpadded (n-wide) simulator inputs, rng-derived."""
    rng = np.random.default_rng(1000 + case.seed)
    n = case.n
    streams = synthetic.device_streams(
        n, samples, LIGHT_ACC, [s.accuracy for s in SERVERS],
        7000 + case.seed)
    lat = (case.lat_scale * rng.uniform(0.8, 1.2, n)).astype(np.float32)
    slo = (lat * rng.uniform(1.3, 2.4, n)).astype(np.float32)
    tier = rng.integers(0, 3, n).astype(np.int32)
    c_upper = rng.uniform(0.7, 0.9, 3).astype(np.float32)
    if case.offline:
        off_start = np.where(rng.random(n) < 0.5,
                             rng.uniform(0.5, 3.0, n), np.inf)
        off_start = off_start.astype(np.float32)
        off_for = rng.uniform(1.0, 4.0, n).astype(np.float32)
    else:
        off_start = np.full(n, np.inf, np.float32)
        off_for = np.zeros(n, np.float32)
    horizon = float(lat.max()) * samples
    if case.churn:
        join_t = np.where(rng.random(n) < 0.4,
                          rng.uniform(0.1, 0.4, n) * horizon,
                          0.0).astype(np.float32)
        leave_t = np.where(rng.random(n) < 0.4,
                           rng.uniform(0.5, 0.9, n) * horizon,
                           np.inf).astype(np.float32)
    else:
        join_t = np.zeros(n, np.float32)
        leave_t = np.full(n, np.inf, np.float32)
    if case.drift:
        rate = 1.0 / lat.astype(np.float64)
        streams = dict(streams, arrive=synthetic.mmpp_arrivals(
            (3000 + case.seed,), n, samples, 1.6 * rate, 0.5 * rate)[0])
    spec = jaxsim.JaxSimSpec(
        scheduler=case.scheduler, n_devices=n, samples_per_device=samples,
        static_threshold=case.static_threshold,
        init_threshold=case.init_threshold,
        model_switching=case.model_switching)
    return (spec, streams, lat, slo, tier, c_upper, off_start, off_for,
            join_t, leave_t)


def pack(cases, samples=SAMPLES, junk_seed=None):
    """Pack heterogeneous lanes into one run_sweep argument set (via the
    shared ``lane_utils.pack_lanes`` convention).

    Narrower lanes' extra rows are zero — or rng junk when ``junk_seed``
    is given, which the engine must treat identically (inert).
    """
    lanes = []
    for case in cases:
        (spec, streams, la, sl, ti, cu, os_, of_,
         jo, le) = _lane_inputs(case, samples)
        lanes.append(dict(spec=spec, streams=streams, lat=la, slo=sl,
                          tier=ti, c_upper=cu, off_start=os_, off_for=of_,
                          join_t=jo, leave_t=le))
    specs, streams, lat, slo, kw = pack_lanes(lanes)
    if junk_seed is not None:
        n_max = max(c.n for c in cases)
        for i, case in enumerate(cases):
            n, m = case.n, n_max - case.n
            if m == 0:
                continue
            jrng = np.random.default_rng(junk_seed + i)
            streams["confidence"][i, n:] = jrng.random((m, samples),
                                                       np.float32)
            streams["correct_light"][i, n:] = jrng.integers(0, 2,
                                                            (m, samples))
            streams["correct_heavy"][i, n:] = jrng.integers(
                0, 2, (m, samples, len(SERVERS)))
            if "arrive" in streams:
                streams["arrive"][i, n:] = jrng.uniform(0.0, 9.0,
                                                        (m, samples))
            lat[i, n:] = jrng.uniform(0.01, 0.5, m)
            slo[i, n:] = jrng.uniform(0.01, 0.5, m)
            kw["tier_ids"][i, n:] = jrng.integers(0, 3, m)
            kw["offline_start"][i, n:] = jrng.uniform(0.0, 5.0, m)
            kw["offline_for"][i, n:] = jrng.uniform(0.0, 5.0, m)
            kw["join_t"][i, n:] = jrng.uniform(0.0, 5.0, m)
            kw["leave_t"][i, n:] = jrng.uniform(0.0, 5.0, m)
    return specs, streams, lat, slo, kw



def _solo(case: LaneCase):
    (spec, streams, lat, slo, tier, cu, os_, of_,
     jo, le) = _lane_inputs(case)
    return jaxsim.run(spec, streams, lat, slo, SERVERS, tier_ids=tier,
                      c_upper=cu, offline_start=os_, offline_for=of_,
                      join_t=jo, leave_t=le)


def test_heterogeneous_mix_each_lane_matches_serial():
    """The headline isolation guarantee: five maximally-different lanes
    in one batched call, each bitwise equal to its own serial run."""
    specs, streams, lat, slo, kw = pack(MIX)
    out = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    for i, case in enumerate(MIX):
        assert_lane_bitwise(out, i, _solo(case), case.n)


def test_lane_results_independent_of_companions():
    """Bitwise-identical per lane under reordering and under different
    batch compositions — no cross-lane state can exist."""
    specs, streams, lat, slo, kw = pack(MIX)
    fwd = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    rev_cases = MIX[::-1]
    specs_r, streams_r, lat_r, slo_r, kw_r = pack(rev_cases)
    rev = jaxsim.run_sweep(specs_r, streams_r, lat_r, slo_r, SERVERS, **kw_r)
    b = len(MIX)
    for i in range(b):
        j = b - 1 - i
        for k in SCALARS:
            assert float(np.asarray(fwd[k])[i]) == \
                   float(np.asarray(rev[k])[j]), k
        np.testing.assert_array_equal(
            np.asarray(fwd["per_device_sr"])[i, :MIX[i].n],
            np.asarray(rev["per_device_sr"])[j, :MIX[i].n])
    # a 2-lane sub-batch reproduces the same lanes bitwise
    sub = (MIX[0], MIX[3])
    specs_s, streams_s, lat_s, slo_s, kw_s = pack(sub)
    out_s = jaxsim.run_sweep(specs_s, streams_s, lat_s, slo_s, SERVERS,
                             **kw_s)
    for si, case in enumerate(sub):
        assert_lane_bitwise(out_s, si, _solo(case), case.n)


# ---------------------------------------------------------------------------
# dynamic-environment scenario lanes: churn schedules (join_t/leave_t)
# and non-stationary arrival tensors are per-lane traced state — exactly
# the kind of input a masking slip would leak across lanes. One batch
# mixes churn-only, drift-only, churn+drift, churn+offline and a plain
# control lane.
# ---------------------------------------------------------------------------
CHURN_MIX = (
    LaneCase(10, "multitasc++", n=6, lat_scale=0.08, churn=True),
    LaneCase(11, "static", n=3, lat_scale=0.3, churn=True, drift=True,
             static_threshold=0.7),
    LaneCase(12, "multitasc", n=8, lat_scale=0.06, drift=True),
    LaneCase(13, "multitasc++", n=4, lat_scale=0.15),        # control
    LaneCase(14, "static", n=5, lat_scale=0.1, churn=True, offline=True),
)


def test_churn_mix_each_lane_matches_serial():
    """Heterogeneous churn schedules + arrival tensors in one batch:
    every lane bitwise equal to its own B=1 run (the batch pools a
    larger window budget from the churn/drift lanes' longer horizons —
    the drain early-exit must absorb that surplus identically)."""
    specs, streams, lat, slo, kw = pack(CHURN_MIX)
    out = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    for i, case in enumerate(CHURN_MIX):
        assert_lane_bitwise(out, i, _solo(case), case.n)


def test_churn_lane_independent_of_companions():
    """A churn lane's results don't depend on which scenario lanes share
    the batch: a 2-lane sub-batch reproduces the same lanes bitwise."""
    sub = (CHURN_MIX[1], CHURN_MIX[3])
    specs, streams, lat, slo, kw = pack(sub)
    out = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    for i, case in enumerate(sub):
        assert_lane_bitwise(out, i, _solo(case), case.n)


def test_churn_junk_beyond_lane_width_is_inert():
    """Junk join/leave schedules and arrival times in a narrower lane's
    padding rows (the engine keeps them inert via the inf-latency mask,
    and the pooled duration lead only reads real rows)."""
    specs, streams, lat, slo, kw = pack(CHURN_MIX)
    clean = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    specs_j, streams_j, lat_j, slo_j, kw_j = pack(CHURN_MIX, junk_seed=77)
    junk = jaxsim.run_sweep(specs_j, streams_j, lat_j, slo_j, SERVERS,
                            **kw_j)
    for i, case in enumerate(CHURN_MIX):
        assert_lane_bitwise(junk, i,
                            {k: (np.asarray(v)[i] if k != "traces" else
                                 {tk: np.asarray(tv)[i]
                                  for tk, tv in v.items()})
                             for k, v in clean.items()}, case.n)


def test_scenario_values_are_traced():
    """Recompile guard for the scenario inputs: changing leave_t values
    across calls must hit the warm core (join_t and arrive also stay
    traced, but varying them can legitimately change the derived window
    budget — i.e. the static key — so the cross-call check uses leave,
    which never feeds the duration)."""
    specs, streams, lat, slo, kw = pack(CHURN_MIX)
    jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    kw2 = dict(kw, leave_t=np.where(np.isfinite(kw["leave_t"]),
                                    kw["leave_t"] * 0.9, np.inf))
    streams2 = {k: np.array(v) for k, v in streams.items()}
    with compile_guard.no_recompiles():
        jaxsim.run_sweep(specs, streams2, np.array(lat), np.array(slo),
                         SERVERS, **kw2)


def test_junk_beyond_lane_width_is_inert():
    """A narrower lane's rows beyond its own n_devices are forced inert
    (infinite latency): rng garbage there must change nothing."""
    specs, streams, lat, slo, kw = pack(MIX)
    clean = jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    specs_j, streams_j, lat_j, slo_j, kw_j = pack(MIX, junk_seed=99)
    junk = jaxsim.run_sweep(specs_j, streams_j, lat_j, slo_j, SERVERS,
                            **kw_j)
    for i, case in enumerate(MIX):
        for k in SCALARS:
            assert float(np.asarray(clean[k])[i]) == \
                   float(np.asarray(junk[k])[i]), k
        for k in ("per_device_sr", "per_device_acc", "final_thresh"):
            np.testing.assert_array_equal(
                np.asarray(clean[k])[i, :case.n],
                np.asarray(junk[k])[i, :case.n], err_msg=k)


def test_one_core_serves_heterogeneous_mixes():
    """Recompile guard: schedulers, device counts and offline windows
    are traced — remixing them at a fixed shape must not compile."""
    specs, streams, lat, slo, kw = pack(MIX)
    jaxsim.run_sweep(specs, streams, lat, slo, SERVERS, **kw)
    # same shapes, different lane mix: rotate schedulers, change device
    # counts (within the packed width), drop the offline windows
    remix = (
        dataclasses.replace(MIX[0], scheduler="static", n=4),
        dataclasses.replace(MIX[1], scheduler="multitasc++", n=8),
        dataclasses.replace(MIX[2], scheduler="multitasc", n=2),
        dataclasses.replace(MIX[3], offline=False, n=7),
        dataclasses.replace(MIX[4], scheduler="multitasc++", n=1),
    )
    specs_r, streams_r, lat_r, slo_r, kw_r = pack(remix)
    with compile_guard.no_recompiles():
        jaxsim.run_sweep(specs_r, streams_r, lat_r, slo_r, SERVERS, **kw_r)


def test_b1_rides_the_same_core():
    """The serial bypass is gone: B=1 must build the same lane-aligned
    core (cores_built ticks once per static structure, not per path)."""
    case = dataclasses.replace(MIX[0], seed=42)
    spec, streams, lat, slo, tier, cu, os_, of_, _, _ = \
        _lane_inputs(case, 48)
    spec = dataclasses.replace(spec, samples_per_device=48)
    # slowest device first so a narrower slice keeps the pooled max
    # latency (same derived window count -> same static structure)
    order = np.argsort(-lat)
    streams = {k: v[order] for k, v in streams.items()}
    lat, slo, tier = lat[order], slo[order], tier[order]
    os_, of_ = os_[order], of_[order]
    out = jaxsim.run(spec, streams, lat, slo, SERVERS, tier_ids=tier,
                     c_upper=cu, offline_start=os_, offline_for=of_)
    # B=1 points with different traced values — including a smaller
    # device count (inputs sliced to the narrower width): zero compiles,
    # because the device axis pads to the same bucket either way
    spec2 = dataclasses.replace(spec, scheduler="static", n_devices=3)
    with compile_guard.no_recompiles():
        jaxsim.run(spec2, {k: v[:3] for k, v in streams.items()}, lat[:3],
                   slo[:3], SERVERS, tier_ids=tier[:3], c_upper=cu,
                   offline_start=os_[:3], offline_for=of_[:3])
    assert int(out["completed"]) == case.n * 48


# ---------------------------------------------------------------------------
# event-frontier invariants, property-tested on the engine's real body
# via jaxsim.lane_stepper (hypothesis when installed, the conftest mini
# engine otherwise)
# ---------------------------------------------------------------------------
def _lane_view(state, i, cap):
    # the queue ring is carried flat over the lanes, lane i owning slots
    # [i * cap, (i + 1) * cap): view it as (B, cap) so a held lane's
    # whole ring is checked, not one slot
    view = {k: np.asarray(v).reshape(-1, cap) if k in jaxsim.RING_FIELDS
            else v for k, v in state.items()}
    return jax.tree.map(lambda x: np.asarray(x)[i], view)


def _frozen(a, b):
    la, _ = jax.tree.flatten(a)
    lb, _ = jax.tree.flatten(b)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(la, lb))


def _drive_and_check(cases, samples=12, max_iters=8000):
    specs, streams, lat, slo, kw = pack(cases, samples=samples)
    state, step, static = jaxsim.lane_stepper(
        specs, streams, lat, slo, SERVERS, **kw)
    b = len(cases)
    prev_frontier = np.asarray(state["frontier"]).copy()
    prev_views = [None] * b
    iters = 0
    while bool(np.any(np.asarray(state["active"]))):
        assert iters < max_iters, "engine failed to terminate"
        # an active lane's window index stays in range (the trace write
        # relies on it: rows land at w, inactive lanes drop out of
        # bounds)
        w = np.asarray(state["w"])
        act = np.asarray(state["active"])
        assert np.all(w[act] < static.n_windows)
        for i in range(b):
            if not act[i] and prev_views[i] is None:
                prev_views[i] = _lane_view(state, i, static.cap)
        state = step(state)
        frontier = np.asarray(state["frontier"])
        # frontier is non-decreasing per lane (an event advances it, a
        # boundary or a held lane leaves it); NaN would break the <=
        assert not np.any(np.isnan(frontier))
        assert np.all(frontier >= prev_frontier), (frontier, prev_frontier)
        prev_frontier = frontier.copy()
        # a lane that went inactive is bitwise frozen ever after
        for i in range(b):
            if prev_views[i] is not None:
                assert _frozen(prev_views[i],
                               _lane_view(state, i, static.cap)), \
                    f"inactive lane {i} mutated"
        iters += 1
    # any(active) False implies every lane drained: all real samples
    # consumed and the server queue empty
    cursor = np.asarray(state["cursor"])
    for i, case in enumerate(cases):
        assert int(np.asarray(state["tail"])[i]) == \
               int(np.asarray(state["head"])[i]), f"lane {i} queue"
        assert np.all(cursor[i, :case.n] >= samples), f"lane {i} samples"


@given(seed=st.integers(0, 10_000),
       fast=st.sampled_from(["multitasc++", "multitasc", "static"]),
       slow=st.sampled_from(["multitasc++", "multitasc", "static"]),
       offline=st.booleans())
@settings(max_examples=4, deadline=None)
def test_frontier_invariants_property(seed, fast, slow, offline):
    cases = (
        LaneCase(seed % 500, fast, n=2, lat_scale=0.05),
        LaneCase(seed % 500 + 1, slow, n=4, lat_scale=0.4,
                 offline=offline),
    )
    _drive_and_check(cases)


def test_frontier_invariants_heterogeneous_mix():
    """The deterministic anchor: the full 5-lane mix through the
    stepper, invariants checked every iteration."""
    _drive_and_check(MIX[:3], samples=10)


def test_frontier_invariants_churn_mix():
    """Scenario lanes through the real loop body: frontier monotonicity
    and the drain guarantee hold with departures (a departed device's
    stream counts as exhausted) and arrival-gapped completions."""
    _drive_and_check(CHURN_MIX[:3], samples=10)
