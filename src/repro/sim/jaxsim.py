"""Vectorized closed-loop simulator: the full multi-device cascade as one
jit-compiled lane-aligned event loop, batched over sweep points.

Everything the event simulator (repro.sim.events) does — device sample
streams, Eq. 3 forwarding decisions, the server request queue, dynamic
batching over the paper's ladder, SLO window accounting, and the
MultiTASC++ / MultiTASC / Static scheduler updates — runs inside a single
compiled core with per-device state vectors, so sweeps over 100+ devices
x schedulers x seeds execute in seconds on one chip. The queue is a
fixed-capacity ring buffer sized to the worst case (every sample
forwarded), so no event is ever dropped.

Time model: event jumps, not a tick grid
----------------------------------------
The simulator is event-driven. Each iteration of the inner loop advances
``t`` directly to the next event time

    t_next = min( next device completion over the fleet,
                  server batch finish (only when the queue is non-empty) )

and processes *every* state transition scheduled at that instant: all
device completions (local classification or forwarding), then — if the
server is free and the queue non-empty — one batch launch at exactly
``t_next``. Window-boundary work (scheduler update via ``lax.switch``,
model switching, SR window reset, trace row) runs after all events with
``t <= (w+1) * window`` have been consumed, so an event landing exactly
on a boundary is attributed to the closing window and the window update
sees its effect — the deterministic resolution order for simultaneous
events is: device completions, then batch finish + launch, then the
window boundary.

Consequences of the event-jump core (vs. the old ``dt = min latency / 2``
tick grid):

* simulator cost is proportional to the number of *events*, not to the
  simulated duration: idle stretches and drain tails cost zero
  iterations, and a heterogeneous fleet with one fast device no longer
  pays a fine grid for everyone;
* completions and batch launches happen at exact float32 times — there
  is no tick-snap bias. In particular a batch can never launch before
  the completion that filled it (the old grid could decide a launch at
  ``t - dt``); launches are back-to-back with the previous batch when
  the queue is backed up, and instantaneous on arrival when the server
  is idle;
* the loop is a ``lax.while_loop`` bounded by the static
  ``max_events_per_window`` cap (a safety valve, not a cost: it bounds
  *possible* iterations at 2 * n_pad * samples — one completion plus at
  most one launch per sample — while the loop only runs actual events).

Lane-aligned batched loop
-------------------------
A B-point sweep runs ONE flat ``lax.while_loop`` whose carry is a dict
of (B, ...) arrays — the while_loop itself is never ``vmap``ped. Under
a vmapped while_loop each iteration pays a select over the *whole*
carry (3 queue buffers of ``cap`` entries per lane, every iteration)
to freeze finished lanes, and nested window/event loops synchronize
all lanes at every window boundary: a lane that drained its window's
events idles until the slowest lane catches up. The lane-aligned
engine instead advances every lane independently to its own next
event:

* each lane carries an ``active`` flag, its event-time ``frontier``
  (pre-extracted: recomputed only by the event that moves it), its
  window index ``w`` and per-window event count ``k`` — the global loop
  condition is a cheap ``any(active)``, not a full-state merge;
* an iteration applies the event step to every lane whose frontier
  falls inside its current window, with ``where``-masks only on the
  fields that event touches (queue writes are n-sized scatters, never
  cap-sized selects); lanes with no event due are bitwise frozen;
* window boundaries (scheduler update, switching, trace row) run in a
  ``lax.cond`` that fires only on iterations where some lane's
  frontier left its window, and exchanges only the handful of small
  fields a boundary touches (``BOUNDARY_FIELDS`` + one trace row) —
  event-only iterations skip all scheduler math;
* loop trips are max-over-lanes of (events + windows) instead of
  sum-over-windows of max-over-lanes, so heterogeneous lane mixes
  (different schedulers, device counts, offline windows, durations in
  one batch) never wait on each other.

B=1 is the degenerate case of the same code — there is no separate
serial core (the old B=1 bypass existed only to dodge the vmapped
carry select) — and a lane's results are bitwise independent of B and
of which other lanes share the batch (tests/test_lanes.py). One caveat
scopes that guarantee: the window *budget* is pooled from the batch's
slowest lane (``n_windows`` is static), so a lane that drains inside
its own duration is unaffected by companions (it early-exits at the
same event either way), but a lane still congested at its own
duration cap would keep simulating into a slower companion's surplus
windows. The default ``extra_time`` (40 s) exists to make draining
the universal case; don't batch deliberately-truncated runs with
longer ones if the truncation point must be preserved.

Static/traced split
-------------------
A sweep point is described by a ``JaxSimSpec``, which the engine splits in
two:

* **static structure** (``JaxSimStatic``): the device-count bucket,
  ``samples_per_device``, the window length and window count derived from
  ``window``, ``extra_time`` and the slowest device, queue capacity, the
  events-per-window cap, and the number of server models. Only these
  force a recompile — one compiled core serves every sweep point that
  shares them.
* **traced values**: everything calibrated or swept — ``a``,
  ``sr_target``, ``init_threshold``, ``static_threshold``,
  ``multitasc_step``, ``mult_growth``, ``c_lower``, the derived ``b_opt``
  and ``server_init``, the server latency profile, the *per-device
  latency and SLO vectors* (the event core has no latency-derived grid,
  so latency profiles vary freely inside one compiled core), and even
  the *scheduler kind* and ``model_switching`` flag: the scheduler
  update is a cheap per-window 3-way ``lax.switch``, so folding it into
  the traced side costs nothing and lets all three schedulers share one
  core.

To keep the static key coarse, the engine additionally:

* pads the device axis up to a ``N_BUCKET`` multiple and threads a traced
  ``n_real`` mask through every update/metric, so n=6 and n=99 hit the
  same executable (padded devices have infinite latency and are inert);
* pads the tier axis to ``MAX_TIERS`` (empty tiers are ignored by the
  switching rule);
* rounds the simulated duration up to a ``DURATION_QUANTUM`` grid and
  runs the window loop as an early-exiting ``lax.while_loop`` that stops
  as soon as every real device finished its stream and the server queue
  drained — padding and the post-completion drain tail cost nothing.

Sharding / placement design (``run_sweep_sharded``)
---------------------------------------------------
``run_sweep`` runs the B sweep points' lanes on one device. At
production scale (1000s of points) the sweep axis itself becomes the
parallel resource, so ``run_sweep_sharded(..., mesh=...)`` shards the
leading B axis over a ``jax.sharding`` mesh:

* the batch axes come from ``launch.mesh.batch_axes_of(mesh)`` (every
  mesh axis except ``model``), and B is padded up to a multiple of the
  lane count by repeating point 0 — padded lanes are computed and then
  dropped, never reported;
* inputs are placed with ``NamedSharding(mesh, P(batch_axes))`` via
  ``jax.device_put`` *before* the call (a pure transfer: no throwaway
  jit ops hit the compile counters) and the per-point arrays enter a
  ``shard_map`` whose body is the same lane-aligned event core
  ``run_sweep`` uses — each shard runs its own independent
  ``while_loop`` over its B/n_shards lanes, so there is no cross-shard
  synchronization per event, only at exit;
* server profile tables are replicated (``P()``); stream buffers stay
  donated exactly as in the unsharded path;
* a mesh whose lane count is 1 (or ``mesh=None``), and a B=1 sweep —
  which padding could only duplicate onto every lane — fall back to the
  local path, bitwise identical by construction.

One compiled executable serves every (scheduler, fleet, threshold)
point that shares static structure, per (mesh, padded-B) shape; wall
time scales down with the shard count because the shards' event loops
never talk to each other.

``run_sweep`` contract
----------------------
``run_sweep(specs, streams, dev_latency, slo, servers, ...)`` runs B
sweep points in one call:

* ``specs``: one ``JaxSimSpec`` (broadcast over the batch) or a sequence
  of B specs that must share their static structure (a ``ValueError``
  otherwise). Schedulers, thresholds, gains — and ``n_devices``, which
  is traced — may differ per point.
* ``streams``: dict with ``confidence``/``correct_light`` of shape
  ``(B, N, S)`` (or ``(N, S)``, broadcast) and ``correct_heavy`` of shape
  ``(B, N, S, P)``; see ``synthetic.batched_device_streams``. ``N`` is
  the widest lane's device count: a narrower lane's rows beyond its own
  ``n_devices`` are forced inert (infinite latency) and its per-device
  outputs beyond ``n_devices`` are meaningless padding.
* ``dev_latency``/``slo``/``tier_ids``/``offline_*``: ``(N,)`` shared or
  ``(B, N)`` per-point; ``c_upper``: ``(n_tiers,)`` or ``(B, n_tiers)``.
  Latency profiles may differ freely across points: the simulated
  duration (and thus the window count) is derived from the pooled
  slowest device, and points that finish earlier early-exit.
* returns the same metric dict as ``run`` with a leading batch axis on
  every leaf (``sr``: ``(B,)``, ``traces.thresh``: ``(B, n_windows)``,
  ...), plus ``n_events`` — the number of event-loop iterations per
  point. Trace rows for windows after the early exit are NaN.

The core runs the flat lane-aligned loop over the batch axis (see
"Lane-aligned batched loop") and donates the stream buffers to the
computation. Trace accumulation is window-wise: each lane's boundary
step writes one trace row per window (mean threshold, window SR, active
fraction, server index, cumulative forwarded count, running accuracy).

Semantics vs. the event simulator (cross-validated in
tests/test_differential.py):
  * event times are exact (float32) — there is no grid bias; remaining
    differences vs. the float64 reference sim are rounding-level;
  * window SR attribution happens at batch *launch* (finish time is
    known then); misattribution is bounded by one batch latency << T;
  * a device whose completion falls inside its offline window completes
    at the end of the offline window (the reference sim re-schedules the
    sample the same way for time-based offline);
  * scheduler updates stop at the early exit — final thresholds are the
    values when the last sample drained, not after an idle tail.

Dynamic-environment scenarios: churn + non-stationary arrivals
--------------------------------------------------------------
Two traced per-device scenario inputs make the *environment* — not just
the fleet profile — a sweep axis (see docs/ARCHITECTURE.md for the full
design and repro.configs.scenarios for the spec type):

* **Device churn** (``join_t``/``leave_t``, seconds, per device): a
  device joins the fleet at ``join_t`` (its first completion lands at
  ``max(join_t, arrival of sample 0) + latency``; before that it is as
  inert as a padded device) and departs at ``leave_t``. A departure is
  *lazy*: the first would-be completion at ``t >= leave_t`` converts
  into a departure event that sets the device's ``dev_next`` to +inf
  and marks its stream exhausted — remaining samples are dropped, never
  completed (``completed`` counts only processed samples). Samples the
  device forwarded *before* leaving still finish on the server and are
  credited normally. No new event *time* enters ``next_event_t``: a
  join is an initial offset, a leave rides the completion that would
  have crossed it — so the frontier invariant ("only events move the
  frontier") is untouched. At a window boundary a device is reported
  active iff ``join_t <= t_end < leave_t`` (closed-form from the traced
  schedule, matching the reference sim's EV_JOIN < EV_LEAVE < EV_WINDOW
  priority at equal timestamps).
* **Non-stationary arrivals** (``streams["arrive"]``, cumulative
  seconds, shape ``(N, S)`` or ``(B, N, S)``): sample ``k`` of a device
  becomes available at ``arrive[k]``; the device starts it at
  ``max(previous finish, arrive[k])`` and completes ``latency`` later
  (deferred by offline windows as usual). All-zero arrivals (the
  default) reproduce the legacy saturated-stream model bitwise.
  Piecewise-rate and MMPP-style bursty tensors are generated
  vectorized by ``synthetic.piecewise_arrivals`` /
  ``synthetic.mmpp_arrivals``. The simulated duration (and thus the
  static window count) covers the pooled worst-case lead
  ``max(join_t + arrive[-1])`` so late joiners and lulls drain before
  the window budget runs out.

Both inputs are traced: churn schedules and arrival tensors vary freely
across the lanes of one batch without recompiling, and every lane-
masking invariant (masked writes, inert padding, per-lane reductions)
applies to them unchanged. Only the *presence* of an arrival tensor is
static (``JaxSimStatic.has_arrive``), so the legacy saturated path
compiles without the (B, N, S) buffer or the per-event arrival gather.

Fleet scale: segmented frontier + device-axis sharding
------------------------------------------------------
At 100k devices the flat per-event argmin (O(N) per event) and the
dense stream generator (O(N*S) float64 temps) dominate. Three opt-in
mechanisms (full design in docs/ARCHITECTURE.md, probed end to end by
benchmarks/fig_scale.py, pinned by tests/test_scale.py):

* ``frontier_seg`` (kwarg on ``run``/``run_sweep``/...): groups the
  device axis into segments of G ~ sqrt(N) with an incrementally
  maintained per-segment min; each event touches one segment (argmin
  over N/G mins + a G-wide completion slice). Auto-on at
  ``n_pad >= SEG_AUTO_MIN``; bitwise equal to the flat path — ties
  spanning segments drain over several pops (launches gated on
  ``t_dev > t``), so only ``n_events`` may differ under ties.
* ``synthetic.chunked_device_streams``: a lazy ``StreamChunks`` handle
  accepted anywhere a stream dict is — generation peaks at O(chunk)
  host memory, bitwise equal to the dense fixture-v2 tensors.
* ``run_device_sharded(..., mesh=make_sweep_mesh((k,)))``: shards ONE
  fleet's device axis (and segment mins) over the mesh; per event three
  ``pmin``s elect the frontier/owner shard and the completion frontier,
  and ``psum``s exchange O(G + MAX_POP)-sized buffers only. Fleet
  dynamics are bitwise equal to the local segmented run; float
  aggregates built from per-shard partial sums (``accuracy``, trace
  thresh/sr/acc) may differ in the last ulp (psum reduction order).

``JaxSimSpec.queue_cap`` bounds the server ring (must exceed
``MAX_POP``): each lane's ``cap`` slots of the lane core's flat ring
(see ``RING_FIELDS``), and the device-sharded core's one replicated
ring; the realized high-water mark is reported as ``queue_peak``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import jaxpr_tools
from repro.configs.cascade_tiers import BATCH_LADDER, ServerProfile
from repro.core import multitasc as mt
from repro.core import multitascpp as mtpp
from repro.core import switching
from repro.launch.mesh import batch_axes_of, device_axis_of, n_lanes

MAX_POP = 64
N_BUCKET = 128          # device axis pads up to a multiple of this
MAX_TIERS = 4           # tier axis is padded to this fixed width
DURATION_QUANTUM = 30.0  # simulated duration rounds up to this grid (s)
SEG_AUTO_MIN = 2048      # n_pad at/above which the segmented frontier
#                          auto-enables (frontier_seg=None); below it the
#                          flat argmin is faster and stays the default so
#                          small-fleet sweeps keep their exact compiled
#                          cores (and n_events counts)

SCHED_CODES = {"multitasc++": 0, "multitasc": 1, "static": 2}

# per-point scalars that are traced inputs of the compiled core (stacked
# on the sweep axis by run_sweep); structure lives in JaxSimStatic
TRACED_FIELDS = ("a", "sr_target", "init_threshold", "static_threshold",
                 "multitasc_step", "mult_growth", "c_lower")

TRACE_KEYS = ("thresh", "sr", "active", "server_idx", "fwd", "acc")


@dataclasses.dataclass(frozen=True)
class JaxSimSpec:
    scheduler: str                  # "multitasc++" | "multitasc" | "static"
    n_devices: int
    samples_per_device: int
    window: float = 1.5
    a: float = mtpp.DEFAULT_A
    sr_target: float = 95.0
    init_threshold: float = 0.5
    static_threshold: float = 0.35
    multitasc_step: float = 0.05
    mult_growth: float = 0.1       # Alg. 1 accelerator; 0 disables it
    model_switching: bool = False
    c_lower: float = switching.DEFAULT_C_LOWER
    extra_time: float = 40.0
    server_init: int = 0
    # optional override of the server queue ring capacity. The default
    # (n_pad * samples + MAX_POP) can absorb every sample being forwarded
    # at once and so can never drop an event, but at fleet scale it is
    # O(total samples) of replicated memory; a closed-loop fleet whose
    # thresholds converged forwards at roughly the server's service rate,
    # so a much smaller ring suffices. The engine tracks the realized
    # ``queue_peak`` metric — a run whose peak approaches the cap is
    # under-provisioned and must be re-run with a larger cap.
    queue_cap: int | None = None


@dataclasses.dataclass(frozen=True)
class JaxSimStatic:
    """The recompile key: structure only, no calibrated scalars.

    The event-jump core has no latency-derived tick grid, so the key is
    coarser than it used to be: latency profiles are fully traced and
    only the window length / window count / bucket sizes remain static.
    """
    n_pad: int
    samples_per_device: int
    n_servers: int
    window: float
    n_windows: int
    max_events_per_window: int   # safety cap on the inner event loop
    cap: int
    # whether the sweep carries an arrival tensor: static so the legacy
    # saturated path compiles without the (B, N, S) buffer, its
    # transfer/donation, or the per-event arrival gather
    has_arrive: bool = False
    # segmented-frontier segment width G (0 = flat argmin). When on, the
    # event step touches one G-wide segment plus the (n_pad / G,)
    # segment-min vector instead of full n_pad-wide rows — per-event cost
    # O(G + n_pad / G) instead of O(n_pad). Static: it changes the
    # compiled core's structure (see "Fleet scale" in
    # docs/ARCHITECTURE.md).
    seg: int = 0


@dataclasses.dataclass
class SweepStats:
    """Process-wide counters for benchmark/regression accounting."""
    cores_built: int = 0        # distinct (static,) lane cores traced
    backend_compiles: int = 0   # XLA compiles (all of jax), cache loads not
    points: int = 0             # sweep points simulated
    events: int = 0             # event-loop iterations across all points
    sharded_points: int = 0     # points executed by a >1-lane sharded core
    device_sharded_points: int = 0  # points run with the DEVICE axis sharded
    # collectives (psum/pmin) one trip of the device-sharded core issues,
    # counted from its jaxpr (``_exchanges_per_trip``), and the trips its
    # calls made (summed ``n_events``)
    device_sharded_exchanges_per_trip: int = 0
    device_sharded_trips: int = 0


stats = SweepStats()

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# JAX times a load from the persistent compilation cache as a backend
# compile too, and records this event inside that timing: each hit takes
# back the compile event that follows it
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_jax_event(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        stats.backend_compiles += 1


def _on_jax_cache_hit(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        stats.backend_compiles -= 1


try:  # compile counting is best-effort: cores_built remains the fallback
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    jax.monitoring.register_event_listener(_on_jax_cache_hit)
except Exception:  # pragma: no cover - monitoring API unavailable
    pass


def stats_snapshot() -> Dict[str, int]:
    return dataclasses.asdict(stats)


def _seg_layout(n_pad: int, frontier_seg, device_shards: int = 1):
    """Resolve ``(seg, n_pad)`` for the frontier structure.

    ``frontier_seg``: ``None`` auto-enables the segmented frontier at
    ``n_pad >= SEG_AUTO_MIN`` (so existing small-fleet sweeps keep their
    flat cores bitwise), ``False``/``0`` forces the flat argmin,
    ``True`` forces segments at the auto size, and a positive
    ``N_BUCKET`` multiple forces that exact segment width. When on,
    ``n_pad`` rounds up so every shard holds a whole number of segments.
    """
    if frontier_seg is False or (frontier_seg is not None
                                 and not isinstance(frontier_seg, bool)
                                 and int(frontier_seg) == 0):
        if device_shards > 1:
            raise ValueError(
                "device-axis sharding requires the segmented frontier "
                "(frontier_seg must not be disabled)")
        return 0, n_pad
    if frontier_seg is None and device_shards <= 1 and n_pad < SEG_AUTO_MIN:
        return 0, n_pad
    if frontier_seg is None or isinstance(frontier_seg, bool):
        # ~sqrt(n) segments, tile-aligned: G doubles from N_BUCKET until
        # G^2 covers n_pad, balancing the O(G) segment slice against the
        # O(n_pad / G) head reduction
        g = N_BUCKET
        while g * g < n_pad:
            g *= 2
    else:
        g = int(frontier_seg)
        if g <= 0 or g % N_BUCKET:
            raise ValueError(
                f"frontier_seg must be a positive multiple of {N_BUCKET},"
                f" got {g}")
    quantum = g * max(1, device_shards)
    return g, -(-n_pad // quantum) * quantum


def _static_of(spec: JaxSimSpec, n_servers: int, max_lat: float,
               n_stream: int | None = None, lead: float = 0.0,
               has_arrive: bool = False, frontier_seg=None,
               device_shards: int = 1) -> JaxSimStatic:
    # ``lead`` = pooled worst-case head start before a device's last
    # sample can begin (max over real devices of join_t + arrive[-1]):
    # zero for the legacy saturated model, so the derived window count —
    # and with it the static structure — is unchanged there
    duration = max_lat * spec.samples_per_device + lead + spec.extra_time
    duration = -(-duration // DURATION_QUANTUM) * DURATION_QUANTUM
    # bucket from the packed stream width: lanes with different device
    # counts (n_real is traced) share one static structure and one core
    n_pad = -(-(n_stream or spec.n_devices) // N_BUCKET) * N_BUCKET
    seg, n_pad = _seg_layout(n_pad, frontier_seg, device_shards)
    cap = n_pad * spec.samples_per_device + MAX_POP
    if spec.queue_cap is not None:
        if spec.queue_cap <= MAX_POP:
            raise ValueError(f"queue_cap must exceed MAX_POP={MAX_POP}")
        cap = min(cap, int(spec.queue_cap))
    # every event-loop iteration consumes a device completion and/or
    # launches a batch over >= 1 queued sample, so 2 * samples + slack
    # bounds the whole sim; per-window it is a pure safety valve
    return JaxSimStatic(
        n_pad=n_pad, samples_per_device=spec.samples_per_device,
        n_servers=n_servers, window=float(spec.window),
        n_windows=int(-(-duration // spec.window)),
        max_events_per_window=2 * n_pad * spec.samples_per_device + MAX_POP,
        cap=cap, has_arrive=has_arrive, seg=seg)


def _params_of(spec: JaxSimSpec, servers: Sequence[ServerProfile],
               slo_min: float) -> Dict[str, np.ndarray]:
    if spec.scheduler not in SCHED_CODES:
        raise ValueError(f"unknown scheduler {spec.scheduler!r}")
    p = {f: np.float32(getattr(spec, f)) for f in TRACED_FIELDS}
    p["scheduler"] = np.int32(SCHED_CODES[spec.scheduler])
    p["model_switching"] = np.int32(spec.model_switching)
    p["n_real"] = np.int32(spec.n_devices)
    p["b_opt"] = np.int32(mt.optimal_batch(servers[spec.server_init],
                                           slo_min))
    p["server_init"] = np.int32(spec.server_init)
    return p


def run(spec: JaxSimSpec, streams, dev_latency, slo, servers:
        Sequence[ServerProfile], *, tier_ids=None, c_upper=None,
        offline_start=None, offline_for=None, join_t=None, leave_t=None,
        frontier_seg=None):
    """Single sweep point: ``run_sweep`` with B=1, batch axis stripped.

    Args:
      spec: the point's ``JaxSimSpec`` (scheduler, fleet size, gains).
      streams: dict of per-device sample tensors —
        ``confidence`` (N, S) float in [0, 1], ``correct_light`` (N, S)
        {0, 1} (a confidence below 0 or NaN, or a ``correct_light``
        outside {0, 1}, raises ``ValueError``; a -0.0 runs as +0.0),
        ``correct_heavy`` (N, S, P) {0, 1} with one column per
        server profile (a (N, S) array is treated as P=1), and optional
        ``arrive`` (N, S): cumulative arrival time of each sample in
        seconds (omitted/zeros = the saturated legacy model). Generate
        with ``synthetic.device_streams`` (+ ``piecewise_arrivals`` /
        ``mmpp_arrivals`` for the arrival tensor); N may exceed
        ``spec.n_devices`` (extra rows are forced inert).
      dev_latency: per-device inference latency, seconds — scalar or
        (N,).
      slo: per-device latency SLO, seconds — scalar or (N,).
      servers: the server ``ServerProfile`` ladder (model switching
        moves ``server_idx`` along it).
      tier_ids: per-device tier index in [0, MAX_TIERS), scalar or (N,).
      c_upper: per-tier switching threshold, (n_tiers,).
      offline_start / offline_for: time-based offline window per device,
        seconds (start inf = never offline).
      join_t / leave_t: churn schedule per device, seconds — the device
        is a fleet member on [join_t, leave_t); defaults 0 / +inf (see
        the module docstring for departure semantics).

    Returns a dict of scalar jnp metrics (``sr`` [0-100], ``accuracy``
    [0-1], ``throughput`` samples/s, ``forwarded_frac``, ``completed``,
    ``queue_left``, ``n_events``), per-device vectors
    (``per_device_sr``/``per_device_acc``/``final_thresh``, (N,)) and
    window traces (``traces[key]`` (n_windows,), NaN past the early
    exit).
    """
    out = run_sweep([spec], streams, dev_latency, slo, servers,
                    tier_ids=tier_ids, c_upper=c_upper,
                    offline_start=offline_start, offline_for=offline_for,
                    join_t=join_t, leave_t=leave_t,
                    frontier_seg=frontier_seg)
    return jax.tree.map(lambda x: x[0], out)


def _prepare(specs, streams, dev_latency, slo, servers, tier_ids, c_upper,
             offline_start, offline_for, join_t=None, leave_t=None,
             frontier_seg=None, device_shards=1):
    """Validate and stack a sweep's host-side inputs.

    Returns ``(static, params, srv, arrays, b, n)`` where ``params`` is a
    dict of (B,)-stacked per-point scalars, ``srv`` the replicated server
    profile tables, and ``arrays`` the (B, ...) per-point tensors in core
    argument order — all numpy: nothing here touches a device, so the
    dispatch paths (local / sharded) control placement explicitly.
    """
    if isinstance(specs, JaxSimSpec):
        specs = [specs]
    specs = list(specs)
    if not specs:
        raise ValueError("run_sweep needs at least one spec")

    if hasattr(streams, "materialize"):
        # a synthetic.StreamChunks handle: the whole-sweep paths need the
        # dense tensors anyway (one transfer into the donated buffers);
        # chunk-at-a-time fill keeps generation's working set at one
        # chunk. Callers that want truly chunked consumption iterate
        # streams.chunks() themselves (benchmarks/fig_scale.py).
        streams = streams.materialize()
    conf = np.asarray(streams["confidence"], np.float32)
    cl = np.asarray(streams["correct_light"], np.int32)
    ch = np.asarray(streams["correct_heavy"], np.int32)
    arrive = streams.get("arrive")
    arrive = None if arrive is None else np.asarray(arrive, np.float32)
    # the flat lane core packs a sample's confidence and light-model
    # correctness into one 32-bit word (_pack_stream_words): the sign
    # bit carries correct_light, so a confidence must be >= 0 (NaN
    # fails the test too) and correct_light 0 or 1
    if not np.all(conf >= 0):
        raise ValueError("streams['confidence'] must be >= 0 and not NaN")
    if cl.size and (cl.min() < 0 or cl.max() > 1):
        raise ValueError("streams['correct_light'] must be 0 or 1")
    if conf.ndim == 2:
        conf, cl, ch = conf[None], cl[None], ch[None]
    if arrive is not None and arrive.ndim == 2:
        arrive = arrive[None]
    if ch.ndim == 3:
        ch = ch[..., None]
    b = max(len(specs), conf.shape[0])
    if len(specs) == 1 and b > 1:
        specs = specs * b
    if len(specs) != b:
        raise ValueError(f"{len(specs)} specs for stream batch {conf.shape[0]}")
    if conf.shape[0] == 1 and b > 1:
        conf = np.broadcast_to(conf, (b,) + conf.shape[1:])
        cl = np.broadcast_to(cl, (b,) + cl.shape[1:])
        ch = np.broadcast_to(ch, (b,) + ch.shape[1:])
    if arrive is not None and arrive.shape[0] == 1 and b > 1:
        arrive = np.broadcast_to(arrive, (b,) + arrive.shape[1:])

    # device counts may differ per lane (n_real is traced): streams come
    # packed at the widest lane's width and narrower lanes' extra rows
    # are forced inert below. samples_per_device is a static shape and
    # must be shared.
    n = max(sp.n_devices for sp in specs)
    s = specs[0].samples_per_device
    if conf.shape != (b, n, s):
        raise ValueError(f"streams shape {conf.shape} != {(b, n, s)}"
                         " (device axis = widest lane)")
    bad = [sp.samples_per_device for sp in specs
           if sp.samples_per_device != s]
    if bad:  # a shape mismatch the bucketing would silently absorb
        raise ValueError(
            f"all specs must share samples_per_device={s};"
            f" got {sorted(set(bad))}")
    if arrive is not None and arrive.shape != (b, n, s):
        raise ValueError(f"streams['arrive'] shape {arrive.shape} != "
                         f"{(b, n, s)} (cumulative seconds per sample)")
    n_real = np.asarray([sp.n_devices for sp in specs], np.int32)

    def per_point(x, fill, dtype, width, pad_fill=None):
        arr = (np.full((width,), fill, dtype) if x is None
               else np.atleast_1d(np.asarray(x, dtype)))
        if arr.ndim == 1 and arr.shape[0] == 1 and width != 1:
            arr = np.broadcast_to(arr, (width,))
        arr = np.broadcast_to(arr, (b, arr.shape[-1])).astype(dtype)
        if arr.shape[-1] < width:
            pad = np.full((b, width - arr.shape[-1]),
                          fill if pad_fill is None else pad_fill, dtype)
            arr = np.concatenate([arr, pad], axis=-1)
        return arr

    dev_lat_real = per_point(dev_latency, 0.0, np.float32, n)
    # the window count covers the slowest REAL device of the whole batch
    # (a narrower lane's rows beyond its own n_devices are junk); faster
    # points just early-exit sooner (latencies are fully traced)
    real_mask = np.arange(n)[None, :] < n_real[:, None]
    max_lat = float(dev_lat_real[real_mask].max())
    # pooled scenario lead: a late joiner / arrival lull delays a
    # device's last sample by at most join_t + arrive[-1] past the
    # saturated schedule — the window budget must cover it (leaves only
    # shorten runs, so leave_t never enters the duration)
    join_real = per_point(join_t, 0.0, np.float32, n)
    lead = join_real + (arrive[..., -1] if arrive is not None else 0.0)
    lead_max = float(lead[real_mask].max()) if np.any(real_mask) else 0.0

    statics = {_static_of(sp, len(servers), max_lat, n, lead_max,
                          arrive is not None, frontier_seg, device_shards)
               for sp in specs}
    if len(statics) != 1:
        raise ValueError(
            "run_sweep points must share static structure; got "
            f"{len(statics)} distinct structures: {sorted(map(str, statics))}")
    static = statics.pop()
    n_pad = static.n_pad

    def pad_streams(x):
        if n_pad == n:
            return x
        shape = (b, n_pad) + x.shape[2:]
        out = np.zeros(shape, x.dtype)
        out[:, :n] = x
        return out

    # devices beyond each lane's own n_devices are inert: infinite
    # latency -> never complete (covers both the bucket padding and a
    # narrower lane's tail in a mixed-device-count batch)
    dev_lat = per_point(dev_lat_real, 0.0, np.float32, n_pad,
                        pad_fill=np.inf)
    dev_lat = np.where(np.arange(n_pad)[None, :] < n_real[:, None],
                       dev_lat, np.inf).astype(np.float32)
    slo_b = per_point(slo, 0.0, np.float32, n_pad)
    tier_b = per_point(tier_ids, 0, np.int32, n_pad)
    if int(tier_b.max()) + 1 > MAX_TIERS:
        raise ValueError(f"at most {MAX_TIERS} device tiers supported")
    c_upper_b = per_point(c_upper, 0.8, np.float32, MAX_TIERS)
    off_start_b = per_point(offline_start, np.inf, np.float32, n_pad)
    off_for_b = per_point(offline_for, 0.0, np.float32, n_pad)
    # churn schedules: padded / out-of-lane devices never join (their
    # inf latency already keeps them inert; join 0 / leave inf is the
    # no-churn identity for real devices)
    join_b = per_point(join_real, 0.0, np.float32, n_pad)
    leave_b = per_point(leave_t, np.inf, np.float32, n_pad,
                        pad_fill=np.inf)
    if arrive is None:
        # static has_arrive=False: the engine never reads this — an
        # empty sample axis keeps the legacy path free of a dead
        # (B, N, S) buffer, its transfer, and its donation
        arrive_b = np.zeros((b, n_pad, 0), np.float32)
    else:
        arrive_b = pad_streams(np.ascontiguousarray(arrive))

    plist = [_params_of(sp, servers, float(slo_b[i, :sp.n_devices].min()))
             for i, sp in enumerate(specs)]
    params = {k: np.stack([p[k] for p in plist]) for k in plist[0]}
    # numpy on purpose: jnp.asarray on host lists/views dispatches tiny
    # jit(convert_element_type) programs that pollute the compile
    # counters (the old fig4/fig17 "recompile leak"); jax.device_put at
    # the call sites is a pure transfer
    srv = {
        "base_lat": np.asarray([p.base_latency for p in servers],
                               np.float32),
        "scaling": np.asarray([p.batch_scaling for p in servers],
                              np.float32),
        "max_batch": np.asarray([p.max_batch for p in servers], np.int32),
    }

    arrays = (pad_streams(conf), pad_streams(cl), pad_streams(ch),
              arrive_b,
              dev_lat, slo_b, tier_b, c_upper_b, off_start_b, off_for_b,
              join_b, leave_b)
    return static, params, srv, arrays, b, n


def _host_span(name: str):
    """A host span named ``name`` on the profiler's clock, so a trace can
    put the device's idle time down to the sweep phase the host was in:
    ``jaxsim.prepare`` (``_prepare``), ``jaxsim.transfer`` (placing the
    inputs, waited for) and ``jaxsim.execute`` (dispatch, the core run and
    the fetch of its outputs). With the profiler off it is one check."""
    return jax.profiler.TraceAnnotation(name)


def _finalize(out, b, n):
    out = dict(out)
    for k in ("per_device_sr", "per_device_acc", "final_thresh"):
        out[k] = np.asarray(out[k])[:, :n]
    out["n_events"] = np.asarray(out["n_events"])
    stats.points += b
    stats.events += int(out["n_events"].sum())
    return out


def run_sweep(specs: Union[JaxSimSpec, Sequence[JaxSimSpec]], streams,
              dev_latency, slo, servers: Sequence[ServerProfile], *,
              tier_ids=None, c_upper=None, offline_start=None,
              offline_for=None, join_t=None, leave_t=None,
              frontier_seg=None):
    """Batched sweep: B points through one lane-aligned, jit-compiled core.

    Args: as ``run``, with a leading batch axis B —

      * ``specs``: one spec (broadcast) or a sequence of B specs sharing
        static structure (``samples_per_device``, ``window``,
        ``extra_time``-derived window count; a ``ValueError`` names the
        mismatch otherwise). Schedulers, thresholds, gains and
        ``n_devices`` (traced) may differ per point.
      * ``streams``: ``confidence``/``correct_light`` (B, N, S) — or
        (N, S), broadcast — ``correct_heavy`` (B, N, S, P), optional
        ``arrive`` (B, N, S) cumulative seconds. N is the widest lane's
        device count.
      * device vectors (``dev_latency``/``slo``/``tier_ids``/
        ``offline_*``/``join_t``/``leave_t``): (N,) shared or (B, N)
        per-point; ``c_upper``: (n_tiers,) or (B, n_tiers).

    Returns the ``run`` metric dict with a leading B axis on every leaf
    (``sr``: (B,), ``traces[key]``: (B, n_windows), ...). All traced
    values — including churn schedules and arrival tensors — vary freely
    across points without recompiling; only static structure forces a
    new executable. Stream buffers are donated to the computation. With
    the flat frontier the core packs ``confidence`` and
    ``correct_light`` once a call into one flat lane-major buffer of
    32-bit words, lane b, device i, sample j at ``(b * n_pad + i) * S +
    j`` (docs/ARCHITECTURE.md, "The lane-aligned batched loop"); the
    input contract is ``run``'s.
    """
    with _host_span("jaxsim.prepare"):
        static, params, srv, arrays, b, n = _prepare(
            specs, streams, dev_latency, slo, servers, tier_ids, c_upper,
            offline_start, offline_for, join_t, leave_t,
            frontier_seg=frontier_seg)
    return _run_local(static, params, srv, arrays, b, n)


def _run_local(static, params, srv, arrays, b, n):
    # B=1 is the degenerate case of the same lane-aligned core (the old
    # serial bypass is gone: without a vmapped while_loop there is no
    # whole-carry select for a single lane to dodge — see
    # benchmarks/fig11_lanes.py for the measured B=1 parity)
    with _host_span("jaxsim.transfer"):
        # waited for, so that the span ends with the inputs on the device
        # (the core waits for them before its first op anyway)
        args = jax.block_until_ready(
            (jax.device_put(params), jax.device_put(srv),
             *(jax.device_put(a) for a in arrays)))
    with _host_span("jaxsim.execute"), warnings.catch_warnings():
        # scoped to this jit call only: the *local* path may legitimately
        # fail to alias donated stream buffers on some backends (the copy
        # is what would have happened anyway); the sharded path must not
        # swallow donation regressions, so it runs unfiltered
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        out = _make_core(static)(*args)
        return _finalize(out, b, n)


def run_sweep_sharded(specs: Union[JaxSimSpec, Sequence[JaxSimSpec]],
                      streams, dev_latency, slo,
                      servers: Sequence[ServerProfile], *, mesh=None,
                      tier_ids=None, c_upper=None, offline_start=None,
                      offline_for=None, join_t=None, leave_t=None,
                      frontier_seg=None):
    """``run_sweep`` with the B axis sharded over a ``jax.sharding`` mesh.

    Same argument contract and return value as ``run_sweep`` (build the
    mesh with ``launch.mesh.make_sweep_mesh``); see the module docstring
    ("Sharding / placement design") for how points are placed.
    ``mesh=None``, a single-lane mesh, or a single-point sweep falls
    back to the local path (bitwise identical): padding B=1 to the lane
    count would make every lane compute the same duplicated point, so a
    single point can never finish sooner sharded than on the B=1
    single-core fast path. B >= 2 is padded up to a multiple of the
    lane count; padded lanes repeat point 0 and are dropped from the
    result.
    """
    lanes = n_lanes(mesh)
    if lanes <= 1:
        return run_sweep(specs, streams, dev_latency, slo, servers,
                         tier_ids=tier_ids, c_upper=c_upper,
                         offline_start=offline_start,
                         offline_for=offline_for, join_t=join_t,
                         leave_t=leave_t, frontier_seg=frontier_seg)
    with _host_span("jaxsim.prepare"):
        static, params, srv, arrays, b, n = _prepare(
            specs, streams, dev_latency, slo, servers, tier_ids, c_upper,
            offline_start, offline_for, join_t, leave_t,
            frontier_seg=frontier_seg)
        b_pad = -(-b // lanes) * lanes
        if b > 1 and b_pad != b:
            def pad(x):
                return np.concatenate(
                    [x, np.repeat(x[:1], b_pad - b, axis=0)], axis=0)
            params = {k: pad(v) for k, v in params.items()}
            arrays = tuple(pad(a) for a in arrays)
    if b == 1:
        return _run_local(static, params, srv, arrays, b, n)
    bspec = jax.sharding.PartitionSpec(tuple(batch_axes_of(mesh)))
    batch_sh = jax.sharding.NamedSharding(mesh, bspec)
    rep_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    with _host_span("jaxsim.transfer"):
        args = jax.block_until_ready(
            (jax.device_put(params, batch_sh), jax.device_put(srv, rep_sh),
             *(jax.device_put(a, batch_sh) for a in arrays)))
    with _host_span("jaxsim.execute"):
        out = _make_core_sharded(static, mesh)(*args)
        out = jax.tree.map(lambda x: np.asarray(x)[:b], out)
        stats.sharded_points += b
        return _finalize(out, b, n)


@functools.lru_cache(maxsize=256)
def _make_core(static: JaxSimStatic):
    stats.cores_built += 1
    return jax.jit(functools.partial(_run_core_lanes, static),
                   donate_argnums=(2, 3, 4, 5))


@functools.lru_cache(maxsize=256)
def _make_core_sharded(static: JaxSimStatic, mesh):
    """One executable per (static structure, mesh): the lane-aligned core
    runs inside ``shard_map``, so each shard's event loop is independent —
    no cross-shard collective per event, only the final gather."""
    stats.cores_built += 1
    bspec = jax.sharding.PartitionSpec(tuple(batch_axes_of(mesh)))
    rep = jax.sharding.PartitionSpec()
    # check_vma=False: the body is collective-free (each shard loops over
    # its own lanes), and the replication checker has no rule for while
    sharded = jax.shard_map(functools.partial(_run_core_lanes, static),
                            mesh=mesh, in_specs=(bspec, rep) + (bspec,) * 12,
                            out_specs=bspec, check_vma=False)
    return jax.jit(sharded, donate_argnums=(2, 3, 4, 5))


# carry fields a window boundary touches: the boundary lax.cond passes
# exactly these (plus the trace row) so event-only iterations never copy
# or recompute anything else
BOUNDARY_FIELDS = ("thresh", "mult", "win_met", "win_total", "server_idx",
                   "w", "k", "active")


def _ratio32(num, den):
    # int/int true division promotes to the DEFAULT float — float64 under
    # enable_x64 — which would split the boundary cond's branch dtypes.
    # Casting both sides first keeps every ratio float32 in either mode
    # (bitwise identical under the standard config: int32->f32 convert +
    # f32 divide is exactly what true_divide lowers to there).
    return num.astype(jnp.float32) / den.astype(jnp.float32)


# The server queue ring: an entry per forwarded sample (its start time,
# device id and sample index), ``static.cap`` slots per lane. The lane
# core carries each buffer flat over its lanes, lane i owning slots
# [i * cap, (i + 1) * cap), so the ring is written and read outside the
# per-lane vmap and the TPU compiler updates the carried buffer in place
# (a (B, cap) carry made it copy all three buffers in and out of a flat
# layout on every trip). The device-sharded core holds one replicated
# ring, at base 0. All three engines share the helpers below, which is
# what keeps their rings bitwise alike.
RING_FIELDS = ("q_start", "q_dev", "q_samp")


def _ring_slots(tail, fwd, cap):
    """Ring slots of one event's appends, and the tail after them.

    Forwarding rows (``fwd`` > 0) take the slots after ``tail`` in row
    order, mod ``cap``; every other row gets ``cap``, the mark that
    ``_ring_write`` drops."""
    pos = tail + jnp.cumsum(fwd, dtype=jnp.int32) - 1
    return (jnp.where(fwd > 0, pos % cap, cap),
            tail + jnp.sum(fwd, dtype=jnp.int32))


def _ring_write(ring, base, slots, rows, cap):
    """Write ``rows`` into the flat ring buffers at ``base + slots``.

    A row marked ``cap`` aims at the buffer's own size, past every
    lane, and is dropped: ``base + cap`` is the next lane's first slot,
    and an in-ring dummy slot would collide with a REAL append once a
    small queue_cap wraps tail past it (duplicate-index scatter,
    order-dependent). A held lane's rows are all marked, so it writes
    nothing."""
    size = ring["q_start"].shape[0]
    idx = jnp.where(slots < cap, base + slots, size).reshape(-1)
    return {key: ring[key].at[idx].set(rows[key].reshape(-1), mode="drop")
            for key in RING_FIELDS}


def _ring_read(ring, base, head, cap):
    """The ``MAX_POP`` entries from the ring head on, after the event's
    appends (a batch may take same-event entries)."""
    idx = base + (head + jnp.arange(MAX_POP, dtype=jnp.int32)) % cap
    return {key: ring[key][idx] for key in RING_FIELDS}


# The flat lane core's sample streams: one 32-bit word per sample, the
# confidence's float32 bits with the light model's correctness in the
# sign bit, laid flat and lane-major (lane b, device i, sample j at
# ``(b * n_pad + i) * s + j``). An event reads each device's sample with
# one 1-D gather of one word: a 3-D gather per stream cost the TPU
# about 22 ns an index, and the compiler splits a gather of a
# two-element slice into two scalar gathers. ``_prepare`` holds the
# confidence to >= 0, so its sign bit is free; a -0.0 packs as +0.0,
# which compares the same.
_CONF_BITS = 0x7FFFFFFF     # a word's bits below the sign bit


def _pack_stream_words(conf, cl):
    bits = jax.lax.bitcast_convert_type(conf, jnp.uint32)
    return ((bits & jnp.uint32(_CONF_BITS))
            | (cl.astype(jnp.uint32) << jnp.uint32(31)))


def _unpack_stream_words(word):
    """``(confidence, correct_light)`` of packed stream words."""
    conf = jax.lax.bitcast_convert_type(word & jnp.uint32(_CONF_BITS),
                                        jnp.float32)
    return conf, (word >> jnp.uint32(31)).astype(jnp.int32)


def _pop_calc(t, popped, server_idx, srv, qlen, can_pop):
    """The ladder batch launched at ``t`` from the ring head's entries
    ``popped``: the taken entries' device ids (0 where not taken),
    samples and latencies. The caller scatters the counters: it owns
    the (local or full) per-device arrays."""
    ladder = jnp.asarray(BATCH_LADDER, jnp.int32)
    braw = jnp.minimum(qlen, srv["max_batch"][server_idx])
    b = jnp.max(jnp.where(ladder <= braw, ladder, 1))
    take = (jnp.arange(MAX_POP, dtype=jnp.int32) < b) & can_pop
    lat_b = srv["base_lat"][server_idx] * (
        1.0 + srv["scaling"][server_idx] * (b - 1).astype(jnp.float32))
    # exact launch: t is the batch-finish time when the queue was backed
    # up, or the arrival of the sample that made it non-empty — by
    # construction never before any popped sample was enqueued
    finish = t + lat_b
    return {"take": take, "devs": jnp.where(take, popped["q_dev"], 0),
            "samps": popped["q_samp"], "b": b, "finish": finish,
            "latency": finish - popped["q_start"]}


def _seg_phases(static: JaxSimStatic):
    """Shared segment-event arithmetic for the segmented engines.

    The local segmented lane (``_engine_fns`` with ``static.seg > 0``)
    and the device-sharded core (``_device_engine``) are the SAME math
    with a psum exchange spliced between the completion and the queue
    (``_ring_*``, ``_pop_calc``) — factoring it here makes their bitwise
    parity hold by construction. Returns
    ``completion(dev, t, base, gbase, has_due)``: all device completions
    of one G-wide segment at instant ``t``. ``dev`` holds the owning
    array set (full arrays locally, the shard's local slice sharded)
    with flattened stream views; ``base`` is the segment start within
    those arrays and ``gbase`` its global device-id base. It returns
    ``(seg_upd, append, seg_min_new, comp_any)`` — per-segment state
    slices to write back at ``base``, a G-wide append buffer (the
    ``RING_FIELDS`` rows with GLOBAL device ids, and ``fwd``; all-zero
    when ``has_due`` is false, so a psum over shards reproduces the
    owner's buffer), the segment's new partial min, and whether any
    local completion happened.
    """
    G, s = static.seg, static.samples_per_device

    def completion(dev, t, base, gbase, has_due):
        def dsl(a):
            return jax.lax.dynamic_slice_in_dim(a, base, G)
        dn, cur = dsl(dev["dev_next"]), dsl(dev["cursor"])
        th = dsl(dev["thresh"])
        lat, slo = dsl(dev["dev_latency"]), dsl(dev["slo"])
        leave = dsl(dev["leave_t"])
        offs, offf = dsl(dev["off_start"]), dsl(dev["off_for"])
        ar = jnp.arange(G, dtype=jnp.int32)
        due = (dn <= t) & (cur < s) & has_due
        departs = due & (dn >= leave)
        done = due & ~departs
        cj = jnp.clip(cur, 0, s - 1)
        flat_ix = (base + ar) * s + cj
        conf_j = dev["conf_flat"][flat_ix]
        local = conf_j >= th                     # Eq. 3
        comp_local = done & local
        met_local = lat <= slo
        fwd_mask = done & ~local
        cursor2 = jnp.where(departs, s, cur + done)
        if static.has_arrive:
            arrive_next = dev["arrive_flat"][
                (base + ar) * s + jnp.clip(cursor2, 0, s - 1)]
            start_next = jnp.maximum(dn, arrive_next)
        else:
            start_next = dn
        off_end = offs + offf
        t_c = start_next + lat
        t_c = jnp.where((t_c >= offs) & (t_c < off_end), off_end, t_c)
        dn2 = jnp.where(done, t_c, dn)
        dn2 = jnp.where(departs, jnp.inf, dn2)
        seg_upd = {
            "dev_next": dn2,
            "cursor": cursor2,
            "win_met": dsl(dev["win_met"]) + (comp_local & met_local),
            "win_total": dsl(dev["win_total"]) + comp_local,
            "tot_met": dsl(dev["tot_met"]) + (comp_local & met_local),
            "tot": dsl(dev["tot"]) + comp_local,
            "correct": dsl(dev["correct"])
                       + comp_local * dev["cl_flat"][flat_ix],
            "fwd": dsl(dev["fwd"]) + fwd_mask,
        }
        append = {
            "q_start": jnp.where(fwd_mask, dn - lat,
                                 0.0).astype(jnp.float32),
            "q_dev": jnp.where(fwd_mask, gbase + ar, 0).astype(jnp.int32),
            "q_samp": jnp.where(fwd_mask, cj, 0).astype(jnp.int32),
            "fwd": fwd_mask.astype(jnp.int32),
        }
        with jax.named_scope("jaxsim.frontier"):
            seg_min_new = jnp.min(jnp.where(cursor2 < s, dn2, jnp.inf))
        return seg_upd, append, seg_min_new, jnp.any(comp_local)

    return completion


def _engine_fns(static: JaxSimStatic):
    """Per-lane (unbatched) engine pieces of the lane-aligned event loop.

    Each function sees ONE lane's state dict plus that lane's traced
    constants ``c`` (per-point scalars + device vectors + streams) and a
    scalar ``go`` saying whether the lane takes this step; every write is
    masked by ``go`` so a held lane is bitwise frozen. ``_run_core_lanes``
    vmaps these over the flat (B, ...) carry — the ``lax.while_loop``
    itself is never vmapped, so there is no whole-carry select and no
    cross-lane window synchronization. The queue ring is not in the
    state they see: an event step is ``lane_event`` (completions and the
    ring slots of their appends), the batched ring write and read
    (``_batched_engine``), then ``lane_launch`` (the batch launch and
    the next frontier).
    """
    n, s = static.n_pad, static.samples_per_device
    window, cap = static.window, static.cap
    G = static.seg

    def defer_offline(t_complete, c):
        # a completion falling inside the device's offline window fires
        # when the device comes back online (the sample is not dropped)
        off_end = c["off_start"] + c["off_for"]
        offline = (t_complete >= c["off_start"]) & (t_complete < off_end)
        return jnp.where(offline, off_end, t_complete)

    @jax.named_scope("jaxsim.frontier")
    def next_event_t(st):
        # next device completion; padded / finished devices sit at +inf.
        # Segmented frontier: the completion min reduces over the
        # maintained per-segment partial mins instead of the full fleet
        if G:
            t_dev = jnp.min(st["seg_min"])
        else:
            t_dev = jnp.min(jnp.where(st["cursor"] < s, st["dev_next"],
                                      jnp.inf))
        # the server matters only while a batch is in flight AND samples
        # wait behind it: launches otherwise happen inside the event that
        # enqueued the triggering sample, and an in-flight batch over an
        # empty queue changes nothing when it lands (SR attribution is at
        # launch). The segmented path adds a pending-launch case — a
        # free server over a non-empty queue at the current instant
        # (possible there because a tie's segments drain one event at a
        # time before the launch; see lane_event_seg)
        qlen = st["tail"] - st["head"]
        t_srv = jnp.where((st["busy_until"] > st["t"]) & (qlen > 0),
                          st["busy_until"], jnp.inf)
        if G:
            t_srv = jnp.where((st["busy_until"] <= st["t"]) & (qlen > 0),
                              st["t"], t_srv)
        return jnp.minimum(t_dev, t_srv)

    def drained(st, c):
        valid = jnp.arange(n, dtype=jnp.int32) < c["n_real"]
        return ((st["tail"] == st["head"])
                & jnp.all(jnp.where(valid, st["cursor"] >= s, True)))

    def lane_init(c):
        init_thresh = jnp.where(c["scheduler"] == SCHED_CODES["static"],
                                c["static_threshold"], c["init_threshold"])
        # sample 0 starts when the device has joined AND the sample has
        # arrived (join 0 + zero arrivals = the legacy saturated start;
        # without an arrival tensor the arrive term compiles out)
        first = (jnp.maximum(c["join_t"], c["arrive"][:, 0])
                 if static.has_arrive else c["join_t"])
        st = {
            "t": jnp.zeros((), jnp.float32),
            "n_events": jnp.zeros((), jnp.int32),
            "dev_next": defer_offline(first + c["dev_latency"], c),
            "cursor": jnp.zeros((n,), jnp.int32),
            "thresh": jnp.broadcast_to(init_thresh, (n,)).astype(jnp.float32),
            "mult": jnp.ones((n,), jnp.float32),
            "win_met": jnp.zeros((n,), jnp.int32),
            "win_total": jnp.zeros((n,), jnp.int32),
            "tot_met": jnp.zeros((n,), jnp.int32),
            "tot": jnp.zeros((n,), jnp.int32),
            "correct": jnp.zeros((n,), jnp.int32),
            "fwd": jnp.zeros((n,), jnp.int32),
            "head": jnp.zeros((), jnp.int32),
            "tail": jnp.zeros((), jnp.int32),
            "busy_until": jnp.zeros((), jnp.float32),
            "last_batch": jnp.zeros((), jnp.int32),
            "server_idx": c["server_init"].astype(jnp.int32),
            "last_done_t": jnp.zeros((), jnp.float32),
            "max_qlen": jnp.zeros((), jnp.int32),
            "w": jnp.zeros((), jnp.int32),
            "k": jnp.zeros((), jnp.int32),
        }
        if G:
            # per-segment partial min over (cursor < s -> dev_next); the
            # invariant the seg event step maintains incrementally
            st["seg_min"] = jnp.where(
                st["cursor"] < s, st["dev_next"],
                jnp.inf).reshape(n // G, G).min(axis=1)
        st["frontier"] = next_event_t(st)
        st["active"] = ~drained(st, c) & (static.n_windows > 0)
        st["traces"] = {key: jnp.full((static.n_windows,), jnp.nan,
                                      jnp.float32) for key in TRACE_KEYS}
        return st

    def stream_read(c, key, j):
        # each device's sample j from a flat lane-major stream buffer
        # (``_batched_engine``), shared by every lane: one 1-D gather
        row = c["lane"] * n + jnp.arange(n, dtype=jnp.int32)
        return c[key][row * s + j]

    def lane_event(st, c, go):
        """The device completions of one lane's frontier event; no-op
        bitwise if ~go. Returns the updated state (the tail past the
        appends; head, clock and frontier not yet moved) and the ring
        append: ``RING_FIELDS`` rows plus their ``slots``."""
        dev_latency, slo = c["dev_latency"], c["slo"]
        t = st["frontier"]

        # --- device completions at exactly this instant -------------------
        with jax.named_scope("jaxsim.devices"):
            due = (st["dev_next"] <= t) & (st["cursor"] < s) & go
            # a would-be completion at or past leave_t is the lazy
            # departure event: the sample (and the rest of the stream) is
            # dropped, the device goes inert — samples already forwarded
            # to the server are unaffected and finish normally
            departs = due & (st["dev_next"] >= c["leave_t"])
            done = due & ~departs
            cj = jnp.clip(st["cursor"], 0, s - 1)
            conf_j, cl_j = _unpack_stream_words(
                stream_read(c, "stream_word", cj))
            local = conf_j >= st["thresh"]          # Eq. 3
            comp_local = done & local
            met_local = dev_latency <= slo
            win_met = st["win_met"] + (comp_local & met_local)
            win_total = st["win_total"] + comp_local
            tot_met = st["tot_met"] + (comp_local & met_local)
            tot = st["tot"] + comp_local
            correct = st["correct"] + comp_local * cl_j
            fwd_mask = done & ~local
            st_fwd = st["fwd"] + fwd_mask

            # a departed device's stream counts as exhausted (drained()
            # and next_event_t both read cursor >= s), so the drain
            # early-exit fires without its dropped samples ever completing
            cursor = jnp.where(departs, s, st["cursor"] + done)
            # next sample starts when the device is free AND it has
            # arrived (no arrival tensor -> back-to-back, the gather
            # compiles out)
            if static.has_arrive:
                arrive_next = stream_read(c, "stream_arrive",
                                          jnp.clip(cursor, 0, s - 1))
                start_next = jnp.maximum(st["dev_next"], arrive_next)
            else:
                start_next = st["dev_next"]
            dev_next = jnp.where(done,
                                 defer_offline(start_next + dev_latency, c),
                                 st["dev_next"])
            dev_next = jnp.where(departs, jnp.inf, dev_next)
            last_done_t = jnp.where(jnp.any(comp_local), t,
                                    st["last_done_t"])

        with jax.named_scope("jaxsim.queue"):
            slots, tail = _ring_slots(st["tail"], fwd_mask, cap)
            push = {"slots": slots, "q_start": st["dev_next"] - dev_latency,
                    "q_dev": jnp.arange(n, dtype=jnp.int32), "q_samp": cj}
        return dict(st, dev_next=dev_next, cursor=cursor, win_met=win_met,
                    win_total=win_total, tot_met=tot_met, tot=tot,
                    correct=correct, fwd=st_fwd, tail=tail,
                    last_done_t=last_done_t), push

    completion_seg = _seg_phases(static) if G else None

    def lane_event_seg(st, c, go):
        """Segmented-frontier event step: one segment per instant.

        The argmin picks the LOWEST-INDEX segment whose partial min
        equals the frontier, processes all of that segment's completions
        at ``t``, and updates only its G-wide state slices plus its
        ``seg_min`` entry — O(G + n/G) work per event instead of O(n).
        Simultaneous completions across segments drain one segment per
        iteration in ascending segment order (== the flat engine's
        device-index append order), and the batch launch is gated on
        ``t_dev > t`` so it fires only after the last same-instant
        segment — the resulting trajectory is bitwise identical to the
        flat engine's, though ``n_events`` counts the extra iterations.
        Returns what ``lane_event`` returns.
        """
        t = st["frontier"]
        with jax.named_scope("jaxsim.frontier"):
            sidx = jnp.argmin(st["seg_min"]).astype(jnp.int32)
            has_due = go & (st["seg_min"][sidx] <= t)
            base = sidx * G
        with jax.named_scope("jaxsim.devices"):
            dev = {
                "dev_next": st["dev_next"], "cursor": st["cursor"],
                "thresh": st["thresh"], "win_met": st["win_met"],
                "win_total": st["win_total"], "tot_met": st["tot_met"],
                "tot": st["tot"], "correct": st["correct"],
                "fwd": st["fwd"],
                "dev_latency": c["dev_latency"], "slo": c["slo"],
                "leave_t": c["leave_t"], "off_start": c["off_start"],
                "off_for": c["off_for"],
                "conf_flat": c["conf"].reshape(-1),
                "cl_flat": c["cl"].reshape(-1),
                "arrive_flat": (c["arrive"].reshape(-1)
                                if static.has_arrive else c["arrive"]),
            }
            seg_upd, append, seg_min_new, comp_any = completion_seg(
                dev, t, base, base, has_due)
            wb = {key: jax.lax.dynamic_update_slice_in_dim(
                      st[key], upd_k, base, axis=0)
                  for key, upd_k in seg_upd.items()}
            last_done_t = jnp.where(comp_any, t, st["last_done_t"])
        with jax.named_scope("jaxsim.frontier"):
            seg_min = st["seg_min"].at[sidx].set(
                jnp.where(has_due, seg_min_new, st["seg_min"][sidx]))
        with jax.named_scope("jaxsim.queue"):
            slots, tail = _ring_slots(st["tail"], append["fwd"], cap)
        return dict(st, **wb, tail=tail, last_done_t=last_done_t,
                    seg_min=seg_min), dict(append, slots=slots)

    def lane_launch(st, c, srv, go, popped):
        """The rest of the event after the ring write: the batch launch
        from the head entries ``popped``, the launched batch's counters,
        and the frontier. ``st`` is ``lane_event``'s state; no-op bitwise
        if ~go."""
        t = st["frontier"]
        with jax.named_scope("jaxsim.queue"):
            # --- server dynamic batching ----------------------------------
            qlen = st["tail"] - st["head"]
            can_pop = (t >= st["busy_until"]) & (qlen > 0) & go
            if G:
                # only once the instant's completions have all drained
                # (t_dev > t), so ties across segments enqueue in full
                # device-index order before the ladder sizes the batch
                with jax.named_scope("jaxsim.frontier"):
                    t_dev = jnp.min(st["seg_min"])
                can_pop = can_pop & (t_dev > t)
            p = _pop_calc(t, popped, st["server_idx"], srv, qlen, can_pop)
            devs, take = p["devs"], p["take"]
            met_srv = (p["latency"] <= c["slo"][devs]) & take
            win_met = st["win_met"].at[devs].add(met_srv)
            win_total = st["win_total"].at[devs].add(take)
            tot_met = st["tot_met"].at[devs].add(met_srv)
            tot = st["tot"].at[devs].add(take)
            correct = st["correct"].at[devs].add(
                take * c["ch"][devs, p["samps"], st["server_idx"]])
            head = st["head"] + jnp.where(can_pop, p["b"], 0)
            busy_until = jnp.where(can_pop, p["finish"], st["busy_until"])
            last_batch = jnp.where(can_pop, p["b"], st["last_batch"])
            last_done_t = jnp.where(can_pop, p["finish"], st["last_done_t"])
            max_qlen = jnp.where(go, jnp.maximum(st["max_qlen"], qlen),
                                 st["max_qlen"])

        with jax.named_scope("jaxsim.frontier"):
            st2 = dict(
                st, t=jnp.where(go, t, st["t"]),
                n_events=st["n_events"] + go,
                win_met=win_met, win_total=win_total, tot_met=tot_met,
                tot=tot, correct=correct, head=head, busy_until=busy_until,
                last_batch=last_batch, last_done_t=last_done_t,
                max_qlen=max_qlen, k=st["k"] + go)
            # the pre-extracted frontier: the only place it ever moves — a
            # window boundary touches no queue/cursor/server-timing state
            st2["frontier"] = jnp.where(go, next_event_t(st2),
                                        st["frontier"])
        return st2

    def lane_boundary(st, c, go):
        """One window boundary: scheduler + switching + trace row.

        Returns ``(upd, row)``: the BOUNDARY_FIELDS updates (masked by
        ``go``) and the float32 trace row — never the full carry, so the
        enclosing ``lax.cond`` stays cheap on event-only iterations.
        """
        valid = jnp.arange(n, dtype=jnp.int32) < c["n_real"]
        n_real_f = c["n_real"].astype(jnp.float32)
        off_end = c["off_start"] + c["off_for"]
        t_end = (st["w"] + 1).astype(jnp.float32) * window
        # fleet membership is closed-form from the traced churn schedule
        # (matching the reference sim's EV_JOIN < EV_LEAVE < EV_WINDOW
        # order at equal timestamps: a device joining exactly at t_end
        # counts present, one leaving exactly at t_end counts departed)
        member = (t_end >= c["join_t"]) & (t_end < c["leave_t"])
        active = (~((t_end >= c["off_start"]) & (t_end < off_end))) \
            & member & valid
        sr = jnp.where(st["win_total"] > 0,
                       100.0 * _ratio32(st["win_met"],
                                        jnp.maximum(st["win_total"], 1)),
                       jnp.float32(100.0))
        thresh, mult = st["thresh"], st["mult"]

        def upd_multitascpp(_):
            upd = mtpp.update({"thresh": thresh, "mult": mult}, sr,
                              mtpp.MultiTASCPPConfig(
                                  a=c["a"],
                                  sr_target=c["sr_target"],
                                  mult_growth=c["mult_growth"]),
                              n_active=jnp.sum(active, dtype=jnp.int32),
                              active=active)
            return upd["thresh"], upd["mult"]

        def upd_multitasc(_):
            upd = mt.update({"thresh": thresh}, st["last_batch"],
                            c["b_opt"],
                            mt.MultiTASCConfig(step=c["multitasc_step"]),
                            active=active)
            return upd["thresh"], mult

        def upd_static(_):
            return thresh, mult

        thresh2, mult2 = jax.lax.switch(
            c["scheduler"],
            (upd_multitascpp, upd_multitasc, upd_static), None)
        win_met = jnp.where(active, 0, st["win_met"])
        win_total = jnp.where(active, 0, st["win_total"])

        sw = switching.decide(thresh2, c["tier_ids"], MAX_TIERS,
                              c["c_lower"], c["c_upper"], active=active)
        server_idx = jnp.clip(
            st["server_idx"] + jnp.where(c["model_switching"] != 0, sw, 0),
            0, static.n_servers - 1)

        acc_run = jnp.where(st["tot"] > 0,
                            _ratio32(st["correct"],
                                     jnp.maximum(st["tot"], 1)),
                            jnp.float32(1.0))
        row = {
            "thresh": jnp.nanmean(jnp.where(active, thresh2, jnp.nan)),
            "sr": jnp.sum(jnp.where(valid, sr, 0.0)) / n_real_f,
            "active": jnp.sum(active, dtype=jnp.int32) / n_real_f,
            "server_idx": server_idx.astype(jnp.float32),
            "fwd": jnp.sum(jnp.where(valid, st["fwd"], 0)).astype(jnp.float32),
            "acc": jnp.sum(jnp.where(valid, acc_run, 0.0)) / n_real_f,
        }
        w2 = st["w"] + go
        upd = {
            "thresh": jnp.where(go, thresh2, thresh),
            "mult": jnp.where(go, mult2, mult),
            "win_met": jnp.where(go, win_met, st["win_met"]),
            "win_total": jnp.where(go, win_total, st["win_total"]),
            "server_idx": jnp.where(go, server_idx, st["server_idx"]),
            "w": w2,
            "k": jnp.where(go, 0, st["k"]),
            # a lane leaves the loop when its duration is exhausted or
            # every real sample drained (the early exit)
            "active": jnp.where(go,
                                (w2 < static.n_windows) & ~drained(st, c),
                                st["active"]),
        }
        return upd, row

    def lane_metrics(final, c):
        valid = jnp.arange(n, dtype=jnp.int32) < c["n_real"]
        n_real_f = c["n_real"].astype(jnp.float32)
        tot = jnp.maximum(final["tot"], 1)
        per_acc = _ratio32(final["correct"], tot)
        return {
            "sr": 100.0 * _ratio32(final["tot_met"].sum(),
                                   jnp.maximum(final["tot"].sum(), 1)),
            "per_device_sr": 100.0 * _ratio32(final["tot_met"], tot),
            "per_device_acc": per_acc,
            "accuracy": jnp.sum(jnp.where(valid, per_acc, 0.0)) / n_real_f,
            "throughput": final["tot"].sum().astype(jnp.float32)
                          / jnp.maximum(final["last_done_t"], 1e-9),
            "forwarded_frac": _ratio32(final["fwd"].sum(),
                                       jnp.maximum(final["tot"].sum(), 1)),
            "completed": final["tot"].sum(),
            "queue_left": final["tail"] - final["head"],
            # realized queue high-water mark: must stay clear of
            # static.cap when JaxSimSpec.queue_cap shrinks the ring
            "queue_peak": final["max_qlen"],
            "n_events": final["n_events"],
            "traces": final["traces"],
            "final_thresh": final["thresh"],
        }

    return (lane_init, lane_event_seg if G else lane_event, lane_launch,
            lane_boundary, lane_metrics)


def _batched_engine(static, params, srv, conf, cl, ch, arrive, dev_latency,
                    slo, tier_ids, c_upper, off_start, off_for, join_t,
                    leave_t):
    """The flat (B, ...) lane-aligned loop: returns (st0, body, finalize).

    The carry is one dict of B-leading arrays plus per-lane ``active``,
    ``frontier`` (next-event time), ``w`` (window) and ``k`` (events this
    window), and the queue ring: ``RING_FIELDS``, each one flat
    ``(B * cap,)`` buffer in which lane i owns slots
    ``[i * cap, (i + 1) * cap)``. Each ``body`` call advances EVERY lane
    that has an event due inside its current window by exactly that one
    event (per-field masked writes — a held or finished lane is bitwise
    frozen), then runs a ``lax.cond``-gated window-boundary step for
    lanes whose frontier passed their window end. Lanes never wait for
    each other: the loop trips are max-over-lanes of (events + windows),
    not sum-over-windows of max-over-lanes as under vmapped while_loops.

    With the flat frontier (``static.seg == 0``) the streams enter the
    loop packed, once a call, as flat lane-major buffers that every lane
    reads by its ``lane`` id (``_pack_stream_words``): ``stream_word``,
    and ``stream_arrive`` with an arrival tensor. The segmented engine
    reads G-wide slices of the (B, N, S) streams.
    """
    lane_init, lane_event, lane_launch, lane_boundary, lane_metrics = (
        _engine_fns(static))
    bsz, cap = conf.shape[0], static.cap
    if bsz * cap >= 2 ** 31:
        raise ValueError(f"{bsz} lanes of {cap} ring slots overflow the "
                         "int32 ring index; split the sweep")
    # each lane's first slot in the flat ring, (B, 1) against its rows
    base = (jnp.arange(bsz, dtype=jnp.int32) * cap)[:, None]
    consts = dict(params, ch=ch, dev_latency=dev_latency, slo=slo,
                  tier_ids=tier_ids, c_upper=c_upper, off_start=off_start,
                  off_for=off_for, join_t=join_t, leave_t=leave_t)
    if static.seg:
        consts.update(conf=conf, cl=cl, arrive=arrive)
        init_consts = consts
    else:
        if conf.size >= 2 ** 31:
            raise ValueError(f"{bsz} lanes of {conf.shape[1]} x "
                             f"{conf.shape[2]} samples overflow the int32 "
                             "stream index; split the sweep")
        consts.update(lane=jnp.arange(bsz, dtype=jnp.int32),
                      stream_word=_pack_stream_words(conf, cl).reshape(-1))
        if static.has_arrive:
            consts["stream_arrive"] = arrive.reshape(-1)
        # the first sample's arrival, read once before the loop
        init_consts = dict(consts, arrive=arrive)

    def axes(c):
        # the flat stream buffers are shared by every lane, not batched
        return {k: None if k.startswith("stream_") else 0 for k in c}

    c_axes = axes(consts)
    init_v = jax.vmap(lane_init, in_axes=(axes(init_consts),))
    event_v = jax.vmap(lane_event, in_axes=(0, c_axes, 0))
    launch_v = jax.vmap(lane_launch, in_axes=(0, c_axes, None, 0, 0))
    boundary_v = jax.vmap(lane_boundary, in_axes=(0, c_axes, 0))
    metrics_v = jax.vmap(lane_metrics, in_axes=(0, c_axes))

    def split(st):
        # the per-lane state the vmapped pieces see, and the flat ring
        return ({k: v for k, v in st.items() if k not in RING_FIELDS},
                {k: st[k] for k in RING_FIELDS})

    @jax.named_scope("jaxsim.frontier")
    def event_flags(st):
        # an event is due iff it lands inside the lane's current window
        # and the per-window safety cap has room; otherwise the lane's
        # next step is its window boundary
        t_end = (st["w"] + 1).astype(jnp.float32) * static.window
        return (st["active"] & (st["frontier"] <= t_end)
                & (st["k"] < static.max_events_per_window))

    def body(st):
        st, ring = split(st)
        # one named scope per phase of a trip names its ops in a profiler
        # trace (docs/ARCHITECTURE.md, "Tracing"); no op changes
        with jax.named_scope("jaxsim.event"):
            go = event_flags(st)
            st, push = event_v(st, consts, go)
            # one flat scatter and gather over all lanes, on the carried
            # buffers (in place); the pop reads the updated ring
            with jax.named_scope("jaxsim.queue"):
                ring = _ring_write(ring, base, push["slots"], push, cap)
                popped = _ring_read(ring, base, st["head"][:, None], cap)
            st = launch_v(st, consts, srv, go, popped)
        # boundary after the event of the same iteration: a lane whose
        # frontier just left the window takes its boundary immediately
        # (same per-lane op sequence as event-then-boundary, fewer trips)
        with jax.named_scope("jaxsim.boundary"):
            go_b = st["active"] & ~event_flags(st)

            def do_boundary(op):
                st_, go_ = op
                return boundary_v(st_, consts, go_)

            def skip_boundary(op):
                st_, _ = op
                return ({k: st_[k] for k in BOUNDARY_FIELDS},
                        {k: jnp.zeros((bsz,), jnp.float32)
                         for k in TRACE_KEYS})

            upd, row = jax.lax.cond(jnp.any(go_b), do_boundary,
                                    skip_boundary, (st, go_b))
            # lanes not at a boundary write their row out of bounds and
            # are dropped: one gather-free scatter per key, no per-lane
            # select over the trace buffers (an active lane's w is <
            # n_windows, so in-bounds exactly for the lanes that really
            # close a window)
            bidx = jnp.arange(bsz, dtype=jnp.int32)
            wj = jnp.where(go_b, st["w"], static.n_windows)
            traces = {key: st["traces"][key].at[bidx, wj].set(
                          row[key], mode="drop")
                      for key in TRACE_KEYS}
        return dict(st, traces=traces, **upd, **ring)

    def finalize(st):
        return metrics_v(split(st)[0], consts)

    st0 = dict(init_v(init_consts),
               q_start=jnp.zeros((bsz * cap,), jnp.float32),
               q_dev=jnp.zeros((bsz * cap,), jnp.int32),
               q_samp=jnp.zeros((bsz * cap,), jnp.int32))
    return st0, body, finalize


def _run_core_lanes(static, params, srv, conf, cl, ch, arrive, dev_latency,
                    slo, tier_ids, c_upper, off_start, off_for, join_t,
                    leave_t):
    st0, body, finalize = _batched_engine(
        static, params, srv, conf, cl, ch, arrive, dev_latency, slo,
        tier_ids, c_upper, off_start, off_for, join_t, leave_t)
    final = jax.lax.while_loop(lambda st: jnp.any(st["active"]), body, st0)
    return finalize(final)


def _device_engine(static: JaxSimStatic, k: int, axis: str):
    """One shard's slice of the device-axis-sharded event loop (B=1).

    Each of the ``k`` shards holds ``n_pad / k`` devices' state, streams
    and segment mins; queue/server/time/window state is replicated and
    every shard applies the identical update to it. The per-event
    arithmetic is ``_seg_phases`` — the same closures the local
    segmented lane runs — with a small fixed set of collectives spliced
    between the phases (frontier pmin + owner-segment pmin + completion-
    frontier pmin, a G-wide append psum, a MAX_POP-wide gather psum, and
    two boundary partial-sum psums). Every trip issues all of them: the
    boundary psums run on every trip too, with zeros where no window
    closes, because a collective may not sit inside a ``lax.cond``
    under ``shard_map``. They carry the ``jaxsim.exchange`` scope. All
    collective operands are O(G + MAX_POP + MAX_TIERS), independent of
    fleet size. The fleet's *dynamics* (thresholds, queue contents,
    switching, event order) are bitwise identical to the local segmented
    engine's: every quantity that feeds back into state is an exact
    integer sum or an elementwise float op. Only reported float
    *aggregates* (trace-row means, the ``accuracy`` metric) may differ
    in the last ulp, because a psum of per-shard partial sums associates
    float additions differently than one flat ``jnp.sum``.
    """
    n, s = static.n_pad, static.samples_per_device
    window, cap, G = static.window, static.cap, static.seg
    n_loc = n // k
    n_segs_loc = n_loc // G
    completion = _seg_phases(static)

    @jax.named_scope("jaxsim.exchange")
    def psum(x):
        return jax.lax.psum(x, axis)

    @jax.named_scope("jaxsim.exchange")
    def pmin(x):
        return jax.lax.pmin(x, axis)

    def shard_off():
        return jax.lax.axis_index(axis).astype(jnp.int32) * n_loc

    def valid_mask(c):
        return (shard_off() + jnp.arange(n_loc, dtype=jnp.int32)) < c["n_real"]

    def defer_offline(t_complete, c):
        off_end = c["off_start"] + c["off_for"]
        offline = (t_complete >= c["off_start"]) & (t_complete < off_end)
        return jnp.where(offline, off_end, t_complete)

    def undrained_local(st, c):
        return (~jnp.all(jnp.where(valid_mask(c), st["cursor"] >= s,
                                   True))).astype(jnp.int32)

    def init(c):
        init_thresh = jnp.where(c["scheduler"] == SCHED_CODES["static"],
                                c["static_threshold"], c["init_threshold"])
        first = (jnp.maximum(c["join_t"], c["arrive"][:, 0])
                 if static.has_arrive else c["join_t"])
        st = {
            "t": jnp.zeros((), jnp.float32),
            "n_events": jnp.zeros((), jnp.int32),
            "dev_next": defer_offline(first + c["dev_latency"], c),
            "cursor": jnp.zeros((n_loc,), jnp.int32),
            "thresh": jnp.broadcast_to(init_thresh,
                                       (n_loc,)).astype(jnp.float32),
            "mult": jnp.ones((n_loc,), jnp.float32),
            "win_met": jnp.zeros((n_loc,), jnp.int32),
            "win_total": jnp.zeros((n_loc,), jnp.int32),
            "tot_met": jnp.zeros((n_loc,), jnp.int32),
            "tot": jnp.zeros((n_loc,), jnp.int32),
            "correct": jnp.zeros((n_loc,), jnp.int32),
            "fwd": jnp.zeros((n_loc,), jnp.int32),
            "q_start": jnp.zeros((cap,), jnp.float32),
            "q_dev": jnp.zeros((cap,), jnp.int32),
            "q_samp": jnp.zeros((cap,), jnp.int32),
            "head": jnp.zeros((), jnp.int32),
            "tail": jnp.zeros((), jnp.int32),
            "busy_until": jnp.zeros((), jnp.float32),
            "last_batch": jnp.zeros((), jnp.int32),
            "server_idx": c["server_init"].astype(jnp.int32),
            "last_done_t": jnp.zeros((), jnp.float32),
            "max_qlen": jnp.zeros((), jnp.int32),
            "w": jnp.zeros((), jnp.int32),
            "k": jnp.zeros((), jnp.int32),
        }
        st["seg_min"] = jnp.where(
            st["cursor"] < s, st["dev_next"],
            jnp.inf).reshape(n_segs_loc, G).min(axis=1)
        # queue empty at t=0: the frontier is the global completion min
        st["frontier"] = pmin(jnp.min(st["seg_min"]))
        drained0 = psum(undrained_local(st, c)) == 0
        st["active"] = ~drained0 & (static.n_windows > 0)
        st["traces"] = {key: jnp.full((static.n_windows,), jnp.nan,
                                      jnp.float32) for key in TRACE_KEYS}
        return st

    def event(st, c, srv, go):
        t = st["frontier"]
        off = shard_off()
        with jax.named_scope("jaxsim.frontier"):
            loc_best = jnp.min(st["seg_min"])
            lidx = jnp.argmin(st["seg_min"]).astype(jnp.int32)
            t_dev0 = pmin(loc_best)
            # owner = globally lowest-index segment attaining the frontier
            # min (ties across shards resolve to the lowest shard,
            # matching the local engine's argmin over the concatenated
            # seg_min)
            cand = jnp.where(
                loc_best == t_dev0,
                jax.lax.axis_index(axis).astype(jnp.int32) * n_segs_loc
                + lidx,
                jnp.int32(2 ** 30))
            owner = pmin(cand)
            mine = cand == owner
            has_due = go & (t_dev0 <= t) & mine
            base = jnp.where(mine, lidx, 0) * G
        with jax.named_scope("jaxsim.devices"):
            dev = {
                "dev_next": st["dev_next"], "cursor": st["cursor"],
                "thresh": st["thresh"], "win_met": st["win_met"],
                "win_total": st["win_total"], "tot_met": st["tot_met"],
                "tot": st["tot"], "correct": st["correct"],
                "fwd": st["fwd"],
                "dev_latency": c["dev_latency"], "slo": c["slo"],
                "leave_t": c["leave_t"], "off_start": c["off_start"],
                "off_for": c["off_for"],
                "conf_flat": c["conf"].reshape(-1),
                "cl_flat": c["cl"].reshape(-1),
                "arrive_flat": (c["arrive"].reshape(-1)
                                if static.has_arrive else c["arrive"]),
            }
            seg_upd, append, seg_min_new, comp_any_loc = completion(
                dev, t, base, off + base, has_due)
            wb = {key: jax.lax.dynamic_update_slice_in_dim(
                      st[key], upd_k, base, axis=0)
                  for key, upd_k in seg_upd.items()}
        with jax.named_scope("jaxsim.frontier"):
            widx = jnp.where(mine, lidx, 0)
            seg_min = st["seg_min"].at[widx].set(
                jnp.where(has_due, seg_min_new, st["seg_min"][widx]))
            t_dev = pmin(jnp.min(seg_min))
        with jax.named_scope("jaxsim.queue"):
            # replicate the owner's append buffer (all-zero off-owner)
            ex = psum(dict(append,
                           comp_any=comp_any_loc.astype(jnp.int32)))
            comp_any = ex.pop("comp_any") > 0
            # the one replicated ring, at base 0: every shard applies the
            # identical write
            slots, tail = _ring_slots(st["tail"], ex["fwd"], cap)
            ring = _ring_write({key: st[key] for key in RING_FIELDS}, 0,
                               slots, ex, cap)
            last_done_t = jnp.where(comp_any, t, st["last_done_t"])

            qlen = tail - st["head"]
            can_pop = (go & (t >= st["busy_until"]) & (qlen > 0)
                       & (t_dev > t))
            p = _pop_calc(t, _ring_read(ring, 0, st["head"], cap),
                          st["server_idx"], srv, qlen, can_pop)
            # popped entries' slo / heavy-correctness live on the owning
            # shards: masked local gathers, one psum to replicate
            ldev = p["devs"] - off
            inr = (ldev >= 0) & (ldev < n_loc) & p["take"]
            lclip = jnp.clip(ldev, 0, n_loc - 1)
            g = psum({
                "slo": jnp.where(inr, c["slo"][lclip], 0.0),
                "ch": jnp.where(inr,
                                c["ch"][lclip, p["samps"],
                                        st["server_idx"]],
                                0),
            })
            met_srv = (p["latency"] <= g["slo"]) & p["take"]
            win_met = wb["win_met"].at[lclip].add(
                jnp.where(inr, met_srv, False))
            win_total = wb["win_total"].at[lclip].add(
                jnp.where(inr, p["take"], False))
            tot_met = wb["tot_met"].at[lclip].add(
                jnp.where(inr, met_srv, False))
            tot = wb["tot"].at[lclip].add(jnp.where(inr, p["take"], False))
            correct = wb["correct"].at[lclip].add(
                jnp.where(inr, p["take"] * g["ch"], 0))
            head = st["head"] + jnp.where(can_pop, p["b"], 0)
            busy_until = jnp.where(can_pop, p["finish"], st["busy_until"])
            last_batch = jnp.where(can_pop, p["b"], st["last_batch"])
            last_done_t = jnp.where(can_pop, p["finish"], last_done_t)
            max_qlen = jnp.where(go, jnp.maximum(st["max_qlen"], qlen),
                                 st["max_qlen"])

        with jax.named_scope("jaxsim.frontier"):
            st2 = dict(
                st, t=jnp.where(go, t, st["t"]),
                n_events=st["n_events"] + go,
                dev_next=wb["dev_next"], cursor=wb["cursor"],
                win_met=win_met, win_total=win_total, tot_met=tot_met,
                tot=tot, correct=correct, fwd=wb["fwd"], **ring, head=head,
                tail=tail, busy_until=busy_until, last_batch=last_batch,
                last_done_t=last_done_t, seg_min=seg_min,
                max_qlen=max_qlen, k=st["k"] + go)
            qlen2 = tail - head
            t_srv = jnp.where(qlen2 > 0,
                              jnp.where(busy_until > t, busy_until, t),
                              jnp.inf)
            st2["frontier"] = jnp.where(go, jnp.minimum(t_dev, t_srv),
                                        st["frontier"])
        return st2

    # --- window boundary, split into collective-free cond bodies with
    # the two partial-sum psums between them (a collective may not sit
    # inside a lax.cond branch under shard_map, and the boundary's
    # global quantities come in two rounds: n_active feeds the threshold
    # update, whose output feeds the switching counts) ----------------
    def boundary_pre(st, c):
        valid = valid_mask(c)
        t_end = (st["w"] + 1).astype(jnp.float32) * window
        off_end = c["off_start"] + c["off_for"]
        member = (t_end >= c["join_t"]) & (t_end < c["leave_t"])
        active = (~((t_end >= c["off_start"]) & (t_end < off_end))) \
            & member & valid
        sr = jnp.where(st["win_total"] > 0,
                       100.0 * _ratio32(st["win_met"],
                                        jnp.maximum(st["win_total"], 1)),
                       jnp.float32(100.0))
        acc_run = jnp.where(st["tot"] > 0,
                            _ratio32(st["correct"],
                                     jnp.maximum(st["tot"], 1)),
                            jnp.float32(1.0))
        return {
            "n_active": jnp.sum(active, dtype=jnp.int32),
            "sr_sum": jnp.sum(jnp.where(valid, sr, 0.0)),
            "fwd_sum": jnp.sum(jnp.where(valid, st["fwd"], 0)),
            "acc_sum": jnp.sum(jnp.where(valid, acc_run, 0.0)),
            "undrained": undrained_local(st, c),
        }

    def zeros_pre(_st):
        z32 = jnp.zeros((), jnp.int32)
        zf = jnp.zeros((), jnp.float32)
        return {"n_active": z32, "sr_sum": zf, "fwd_sum": z32,
                "acc_sum": zf, "undrained": z32}

    def boundary_mid(st, c, pre_g):
        valid = valid_mask(c)
        t_end = (st["w"] + 1).astype(jnp.float32) * window
        off_end = c["off_start"] + c["off_for"]
        member = (t_end >= c["join_t"]) & (t_end < c["leave_t"])
        active = (~((t_end >= c["off_start"]) & (t_end < off_end))) \
            & member & valid
        sr = jnp.where(st["win_total"] > 0,
                       100.0 * _ratio32(st["win_met"],
                                        jnp.maximum(st["win_total"], 1)),
                       jnp.float32(100.0))
        thresh, mult = st["thresh"], st["mult"]

        def upd_multitascpp(_):
            upd = mtpp.update({"thresh": thresh, "mult": mult}, sr,
                              mtpp.MultiTASCPPConfig(
                                  a=c["a"],
                                  sr_target=c["sr_target"],
                                  mult_growth=c["mult_growth"]),
                              n_active=pre_g["n_active"], active=active)
            return upd["thresh"], upd["mult"]

        def upd_multitasc(_):
            upd = mt.update({"thresh": thresh}, st["last_batch"],
                            c["b_opt"],
                            mt.MultiTASCConfig(step=c["multitasc_step"]),
                            active=active)
            return upd["thresh"], mult

        def upd_static(_):
            return thresh, mult

        thresh2, mult2 = jax.lax.switch(
            c["scheduler"],
            (upd_multitascpp, upd_multitasc, upd_static), None)
        sums = dict(
            switching.decide_partials(thresh2, c["tier_ids"], MAX_TIERS,
                                      c["c_lower"], c["c_upper"],
                                      active=active),
            thresh_sum=jnp.sum(jnp.where(active, thresh2, 0.0)))
        return {"thresh": thresh2, "mult": mult2,
                "win_met": jnp.where(active, 0, st["win_met"]),
                "win_total": jnp.where(active, 0, st["win_total"]),
                "sums": sums}

    def zeros_mid(st):
        zt = jnp.zeros((MAX_TIERS,), jnp.float32)
        zf = jnp.zeros((), jnp.float32)
        return {"thresh": st["thresh"], "mult": st["mult"],
                "win_met": st["win_met"], "win_total": st["win_total"],
                "sums": {"count": zt, "active": zt, "below": zt,
                         "not_above": zf, "any_active": zf,
                         "thresh_sum": zf}}

    def boundary_fin(st, c, mid, sums_g, pre_g):
        sw = switching.decide_from_partials(sums_g)
        server_idx = jnp.clip(
            st["server_idx"] + jnp.where(c["model_switching"] != 0, sw, 0),
            0, static.n_servers - 1)
        n_real_f = c["n_real"].astype(jnp.float32)
        n_act_f = pre_g["n_active"].astype(jnp.float32)
        row = {
            "thresh": jnp.where(pre_g["n_active"] > 0,
                                sums_g["thresh_sum"]
                                / jnp.maximum(n_act_f, 1.0), jnp.nan),
            "sr": pre_g["sr_sum"] / n_real_f,
            "active": n_act_f / n_real_f,
            "server_idx": server_idx.astype(jnp.float32),
            "fwd": pre_g["fwd_sum"].astype(jnp.float32),
            "acc": pre_g["acc_sum"] / n_real_f,
        }
        w2 = st["w"] + 1
        drained_g = (st["tail"] == st["head"]) & (pre_g["undrained"] == 0)
        upd = {
            "thresh": mid["thresh"], "mult": mid["mult"],
            "win_met": mid["win_met"], "win_total": mid["win_total"],
            "server_idx": server_idx, "w": w2,
            "k": jnp.zeros((), jnp.int32),
            "active": (w2 < static.n_windows) & ~drained_g,
        }
        return upd, row

    def skip_fin(st):
        return ({key: st[key] for key in BOUNDARY_FIELDS},
                {key: jnp.zeros((), jnp.float32) for key in TRACE_KEYS})

    def metrics(final, c):
        valid = valid_mask(c)
        n_real_f = c["n_real"].astype(jnp.float32)
        per_acc = _ratio32(final["correct"], jnp.maximum(final["tot"], 1))
        gsum = psum({
            "tot": final["tot"].sum(),
            "tot_met": final["tot_met"].sum(),
            "fwd": final["fwd"].sum(),
            "acc": jnp.sum(jnp.where(valid, per_acc, 0.0)),
        })
        return {
            "sr": 100.0 * _ratio32(gsum["tot_met"],
                                   jnp.maximum(gsum["tot"], 1)),
            "per_device_sr": 100.0 * _ratio32(final["tot_met"],
                                              jnp.maximum(final["tot"], 1)),
            "per_device_acc": per_acc,
            "accuracy": gsum["acc"] / n_real_f,
            "throughput": gsum["tot"].astype(jnp.float32)
                          / jnp.maximum(final["last_done_t"], 1e-9),
            "forwarded_frac": _ratio32(gsum["fwd"],
                                       jnp.maximum(gsum["tot"], 1)),
            "completed": gsum["tot"],
            "queue_left": final["tail"] - final["head"],
            "queue_peak": final["max_qlen"],
            "n_events": final["n_events"],
            "traces": final["traces"],
            "final_thresh": final["thresh"],
        }

    fns = {"init": init, "event": event, "boundary_pre": boundary_pre,
           "zeros_pre": zeros_pre, "boundary_mid": boundary_mid,
           "zeros_mid": zeros_mid, "boundary_fin": boundary_fin,
           "skip_fin": skip_fin, "metrics": metrics, "psum": psum}
    return fns


def _run_core_device(static, k, axis, params, srv, conf, cl, ch, arrive,
                     dev_latency, slo, tier_ids, c_upper, off_start,
                     off_for, join_t, leave_t):
    """shard_map body for the device-axis-sharded core (one sweep point).

    Receives the LOCAL (n_pad / k)-row slice of every device-dim input
    and replicated scalars/tables; runs ONE scalar lane whose replicated
    control state (t, frontier, window, queue pointers) keeps all shards
    taking identical branches, so the ``lax.cond``-gated boundary stays
    legal with its collectives hoisted to the body's top level.
    """
    e = _device_engine(static, k, axis)
    consts = dict(params, conf=conf, cl=cl, ch=ch, arrive=arrive,
                  dev_latency=dev_latency, slo=slo, tier_ids=tier_ids,
                  c_upper=c_upper, off_start=off_start, off_for=off_for,
                  join_t=join_t, leave_t=leave_t)

    @jax.named_scope("jaxsim.frontier")
    def event_go(st):
        t_end = (st["w"] + 1).astype(jnp.float32) * static.window
        return (st["active"] & (st["frontier"] <= t_end)
                & (st["k"] < static.max_events_per_window))

    def body(st):
        # the local engines' phase scopes (``_batched_engine``)
        with jax.named_scope("jaxsim.event"):
            st = e["event"](st, consts, srv, event_go(st))
        with jax.named_scope("jaxsim.boundary"):
            go_b = st["active"] & ~event_go(st)
            pre = jax.lax.cond(go_b,
                               lambda s_: e["boundary_pre"](s_, consts),
                               e["zeros_pre"], st)
            pre_g = e["psum"](pre)
            mid = jax.lax.cond(
                go_b,
                lambda op: e["boundary_mid"](op[0], consts, op[1]),
                lambda op: e["zeros_mid"](op[0]), (st, pre_g))
            sums_g = e["psum"](mid["sums"])
            upd, row = jax.lax.cond(
                go_b,
                lambda op: e["boundary_fin"](op[0], consts, op[1], op[2],
                                             op[3]),
                lambda op: e["skip_fin"](op[0]), (st, mid, sums_g, pre_g))
            wj = jnp.where(go_b, st["w"], static.n_windows)
            traces = {key: st["traces"][key].at[wj].set(row[key],
                                                        mode="drop")
                      for key in TRACE_KEYS}
        return dict(st, traces=traces, **upd)

    st0 = e["init"](consts)
    final = jax.lax.while_loop(lambda st: st["active"], body, st0)
    return e["metrics"](final, consts)


# device-dim per-device outputs: sharded on the device axis; everything
# else replicated (identical on every shard by construction)
_DEVICE_OUT_SHARDED = ("per_device_sr", "per_device_acc", "final_thresh")

# run_device_sharded's contract against the local segmented engine:
# fleet dynamics bitwise (integer totals, elementwise per-device floats,
# integer trace rows); psum-of-partials float aggregates may differ in
# the last ulp (their reduction order differs from the flat sum)
SHARDED_EXACT_KEYS = ("completed", "queue_left", "queue_peak", "sr",
                      "throughput", "forwarded_frac", "per_device_sr",
                      "per_device_acc", "final_thresh", "n_events")
SHARDED_EXACT_TRACES = ("active", "server_idx", "fwd")
SHARDED_ULP_KEYS = ("accuracy",)
SHARDED_ULP_TRACES = ("thresh", "sr", "acc")


@functools.lru_cache(maxsize=64)
def _make_core_device(static: JaxSimStatic, mesh):
    """One executable per (static structure, mesh) for the device-axis
    sharded core: per-shard local frontier mins, a handful of O(G)-sized
    collectives per event (see ``_device_engine``)."""
    stats.cores_built += 1
    axis = device_axis_of(mesh)
    k = n_lanes(mesh)
    P = jax.sharding.PartitionSpec
    dspec, rep = P(axis), P()
    # arrays order: conf cl ch arrive lat slo tier c_upper off_start
    # off_for join leave — c_upper (index 7) is per-tier, replicated
    in_specs = (rep, rep) + tuple(
        rep if i == 7 else dspec for i in range(12))
    out_specs = {
        key: dspec for key in _DEVICE_OUT_SHARDED}
    out_specs.update({key: rep for key in (
        "sr", "accuracy", "throughput", "forwarded_frac", "completed",
        "queue_left", "queue_peak", "n_events")})
    out_specs["traces"] = {key: rep for key in TRACE_KEYS}
    sharded = jax.shard_map(functools.partial(_run_core_device, static, k,
                                              axis),
                            mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return jax.jit(sharded, donate_argnums=(2, 3, 4, 5))


@functools.lru_cache(maxsize=64)
def _exchanges_per_trip(jaxpr) -> int:
    """Collectives one trip of the device-sharded core issues, read from
    its ClosedJaxpr: the ``psum`` and ``pmin`` equations of its while
    loop's body, with those
    of the branches and calls inside it. JAX binds one equation per
    array of a collective's operand; the compiler may combine
    independent ones into fewer all-reduces."""
    body = next(eqn.params["body_jaxpr"].jaxpr
                for _, eqn in jaxpr_tools.walk_eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "while")
    return sum(eqn.primitive.name in ("psum", "pmin")
               for _, eqn in jaxpr_tools.walk_eqns(body))


def run_device_sharded(spec: JaxSimSpec, streams, dev_latency, slo,
                       servers: Sequence[ServerProfile], *, mesh=None,
                       tier_ids=None, c_upper=None, offline_start=None,
                       offline_for=None, join_t=None, leave_t=None,
                       frontier_seg=None):
    """One sweep point with the DEVICE axis sharded over the mesh.

    Complements ``run_sweep_sharded`` (which shards the *sweep* axis and
    keeps each point's fleet on one chip): here a single fleet's
    per-device state, streams and segment mins are placed over the mesh
    — the path to 100k+ devices per lane, where one chip's memory or
    per-event bandwidth becomes the binding constraint. Requires the
    segmented frontier (``frontier_seg`` defaults on; ``False`` raises)
    and a single-batch-axis mesh from ``make_sweep_mesh((k,))``; B=1
    only — shard the sweep axis instead when you have many points.
    ``mesh=None`` / a single-lane mesh falls back to the local
    segmented path.

    Fleet dynamics are bitwise identical to the local segmented engine
    (and so to the flat engine); reported float aggregates (trace-row
    means, ``accuracy``) can differ in the last ulp — see
    ``_device_engine``.
    """
    if not isinstance(spec, JaxSimSpec):
        raise ValueError("run_device_sharded takes a single JaxSimSpec "
                         "(B=1); use run_sweep_sharded for sweeps")
    k = n_lanes(mesh)
    if mesh is None or k <= 1:
        return run(spec, streams, dev_latency, slo, servers,
                   tier_ids=tier_ids, c_upper=c_upper,
                   offline_start=offline_start, offline_for=offline_for,
                   join_t=join_t, leave_t=leave_t,
                   frontier_seg=True if frontier_seg is None
                   else frontier_seg)
    with _host_span("jaxsim.prepare"):
        static, params, srv, arrays, b, n = _prepare(
            [spec], streams, dev_latency, slo, servers, tier_ids, c_upper,
            offline_start, offline_for, join_t, leave_t,
            frontier_seg=frontier_seg, device_shards=k)
        if b != 1:
            raise ValueError("run_device_sharded runs one sweep point "
                             f"(B=1); got a stream batch of {b}")
        params1 = {key: v[0] for key, v in params.items()}
        arrays1 = tuple(a[0] for a in arrays)
    dev_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(device_axis_of(mesh)))
    rep_sh = jax.sharding.NamedSharding(mesh,
                                        jax.sharding.PartitionSpec())
    with _host_span("jaxsim.transfer"):
        args = jax.block_until_ready(
            (jax.device_put(params1, rep_sh), jax.device_put(srv, rep_sh),
             *(jax.device_put(a, rep_sh if i == 7 else dev_sh)
               for i, a in enumerate(arrays1))))
    with _host_span("jaxsim.execute"):
        core = _make_core_device(static, mesh)
        exchanges = _exchanges_per_trip(core.trace(*args).jaxpr)
        out = dict(core(*args))
        for key in _DEVICE_OUT_SHARDED:
            out[key] = np.asarray(out[key])[:n]
        out["n_events"] = np.asarray(out["n_events"])
    stats.points += 1
    stats.events += int(out["n_events"])
    stats.device_sharded_points += 1
    stats.device_sharded_exchanges_per_trip = exchanges
    stats.device_sharded_trips += int(out["n_events"])
    return out


def lane_stepper(specs, streams, dev_latency, slo,
                 servers: Sequence[ServerProfile], *, tier_ids=None,
                 c_upper=None, offline_start=None, offline_for=None,
                 join_t=None, leave_t=None):
    """Debug/test hook: the engine's initial carry plus a jitted
    single-iteration ``step`` — literally the ``body`` the compiled core
    loops over, so invariant tests (frontier monotonicity, inactive-lane
    freezing, drain <=> any(active)) observe the real engine, not a
    mirror. Not a performance path.

    Args: exactly ``run_sweep``'s (batched, including the scenario
    inputs ``join_t``/``leave_t`` and ``streams["arrive"]``).

    Returns ``(state, step, static)``: ``state`` is the flat (B, ...)
    carry dict (per-lane ``active``/``frontier``/``w``/``k`` plus the
    per-device state vectors; the ``RING_FIELDS`` are flat
    ``(B * static.cap,)`` buffers, lane i's ring at slots
    ``[i * cap, (i + 1) * cap)``), ``step`` maps carry -> carry for one
    loop iteration, and ``static`` is the ``JaxSimStatic`` recompile
    key; ``jnp.any(state["active"])`` is the loop condition the core
    uses.
    """
    static, params, srv, arrays, _, _ = _prepare(
        specs, streams, dev_latency, slo, servers, tier_ids, c_upper,
        offline_start, offline_for, join_t, leave_t)
    st0, body, _ = _batched_engine(
        static, jax.device_put(params), jax.device_put(srv),
        *(jax.device_put(a) for a in arrays))
    return st0, jax.jit(body), static


run_jit = run  # the inner core is jitted and cached per static structure
