"""Driver of the sim cells: a unit is one ``jaxsim.run_sweep`` call over
every lane of the traffic (schedulers x fleet sizes x stream seeds),
with its inputs sent afresh from the host (the core donates its stream
buffers) and its outputs fetched back.

Configuration keys read besides the device and server profiles:
``samples_per_device`` and, optionally, ``latency_tiers``
(``{"tiers": [...], "jitter": [lo, hi]}``: per-device latencies in equal
thirds of the fleet; without it every device has the profile's latency).

Traffic keys: ``metric`` (the name of the end-to-end rate the cell
reports), ``schedulers``, ``fleets`` (devices per lane), ``stream_seeds``
(lanes per scheduler and fleet, each with its own streams drawn from the
run's seed), ``check_lanes`` (lanes held to the reference after the
window), ``limits`` (the numbers compared, each with its limit;
``compare`` computes more than a cell may hold to a limit) and,
optionally, ``trace_seconds`` (read by ``run.py``: how much of a unit
the traced run records) and ``reference_dtype`` (the precision the
reference computes every real number in: ``float64``, numpy's own, where
it names none; the control computes in bfloat16 either way).

Set-up warms the compiled program with inputs of the timed sweep's
shapes, every device at the slowest device's latency and every sample
confident enough to stay on its device: the program's static structure
(device padding, window count, ring size) is the same, and with no
latency jitter and no server batches the devices complete together, in
far fewer events.
"""
from __future__ import annotations

import numpy as np
from ml_dtypes import bfloat16

from perfbench.reference import cascade_sim
from perfbench.traffic import synthetic

# the program counts a forwarded sample in its window when its batch is
# launched (``sim/jaxsim.py`` documents it); the reference is held to the
# same rule, and PERF.md gives both attributions' readings
ATTRIBUTE = "launch"


def _seeds(seed: int, k: int, child: int):
    """``k`` 32-bit seeds of child ``child`` of the run's seed."""
    ss = np.random.SeedSequence(int(seed)).spawn(child + 1)[child]
    return [int(x) for x in ss.generate_state(k)]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.tr, self.seed = config, traffic, seed

    # ------------------------------------------------------------------
    def setup(self):
        import jax
        from repro.configs.cascade_tiers import ServerProfile
        from repro.sim import jaxsim
        self.jax, self.jaxsim = jax, jaxsim
        cfg, tr = self.cfg, self.tr
        dev, srv = cfg["device"], cfg["server"]
        self.server = ServerProfile(
            srv["name"], srv["model"], srv["accuracy"],
            srv["base_latency_s"], srv["max_batch"], srv["batch_scaling"])
        s = cfg["samples_per_device"]
        width = max(tr["fleets"])
        stream_seeds = _seeds(self.seed, tr["stream_seeds"], 0)
        self.static_t = synthetic.static_threshold(dev["accuracy"],
                                                   srv["accuracy"])
        gen = (synthetic.chunked_device_streams if width >= 4096
               else synthetic.batched_device_streams)
        blocks = []
        for n in tr["fleets"]:
            st = gen(stream_seeds, n, s, dev["accuracy"], [srv["accuracy"]])
            pad = [(0, 0), (0, width - n), (0, 0)]
            blocks.append({k: np.pad(v, pad + [(0, 0)] * (v.ndim - 3))
                           for k, v in st.items()})
        per_sched = {k: np.concatenate([b[k] for b in blocks])
                     for k in blocks[0]}
        self.streams = {k: np.concatenate([v] * len(tr["schedulers"]))
                        for k, v in per_sched.items()}
        self.lanes = [(sch, n, k) for sch in tr["schedulers"]
                      for n in tr["fleets"]
                      for k in range(tr["stream_seeds"])]
        lat = cfg.get("latency_tiers")
        if lat:
            self.latency = synthetic.tier_latencies(
                width, dev["latency_s"], lat["tiers"], lat["jitter"],
                seed=_seeds(self.seed, 1, 1)[0])
        else:
            self.latency = np.full(width, dev["latency_s"], np.float32)
        self.slo = np.full(width, cfg["slo_s"], np.float32)
        self.specs = [self._spec(sch, n) for sch, n, _ in self.lanes]
        warm = dict(self.streams,
                    confidence=np.ones_like(self.streams["confidence"]))
        self._sweep(self.specs, warm,
                    np.full_like(self.latency, self.latency.max()), self.slo)

    def _spec(self, scheduler: str, n: int):
        cfg = self.cfg
        return self.jaxsim.JaxSimSpec(
            scheduler=scheduler, n_devices=n,
            samples_per_device=cfg["samples_per_device"],
            window=cfg["window_s"], a=cfg["a"], sr_target=cfg["sr_target"],
            init_threshold=cfg["init_threshold"],
            static_threshold=self.static_t, extra_time=self._drain_s())

    def _drain_s(self) -> float:
        """The simulated time the program may run past the devices' last
        sample: its default 40 s, plus the server's time to serve every
        sample of the largest fleet in batches of its largest size, which
        bounds the drain of any queue. Every sample then completes, as
        the configuration states; under the default alone an overloaded
        lane stops with samples queued."""
        srv, s = self.cfg["server"], self.cfg["samples_per_device"]
        b = srv["max_batch"]
        batch_s = srv["base_latency_s"] * (1 + srv["batch_scaling"] * (b - 1))
        return 40.0 + max(self.tr["fleets"]) * s * batch_s / b

    def _sweep(self, specs, streams, latency, slo):
        out = self.jaxsim.run_sweep(specs, streams, latency, slo,
                                    (self.server,))
        return self.jax.device_get(out)

    # ------------------------------------------------------------------
    def unit(self) -> dict:
        with self.jax.profiler.TraceAnnotation("bench.unit"):
            out = self._sweep(self.specs, self.streams, self.latency,
                              self.slo)
        work = sum(n * self.cfg["samples_per_device"]
                   for _, n, _ in self.lanes)
        done = int(np.sum(out["completed"]))
        return {"out": out, "work": work, "attempted": work,
                "failed": max(work - done, 0),
                "trips": int(np.max(out["n_events"]))}

    def rates(self, units, wall: float) -> dict:
        return {self.tr["metric"]: sum(u["work"] for u in units) / wall}

    def release(self):
        self.jax.clear_caches()

    # ------------------------------------------------------------------
    def check_lanes(self):
        """Lanes held to the reference: one per scheduler, each at a
        fleet size and stream seed drawn from the run's seed, one of
        them at the largest fleet."""
        rng = np.random.default_rng(_seeds(self.seed, 1, 2)[0])
        tr = self.tr
        picks = []
        for sch in tr["schedulers"]:
            n = tr["fleets"][rng.integers(len(tr["fleets"]))]
            picks.append([sch, n, int(rng.integers(tr["stream_seeds"]))])
        picks[int(rng.integers(len(picks)))][1] = max(tr["fleets"])
        picks = picks[:tr.get("check_lanes", len(picks))]
        return [self.lanes.index(tuple(p)) for p in picks]

    def reference(self, lane: int, rnd=None, attribute: str = ATTRIBUTE):
        """The reference run of lane ``lane``; ``rnd`` rounds every real
        number it computes, through the traffic's ``reference_dtype`` if
        not given."""
        if rnd is None:
            dtype = self.tr.get("reference_dtype", "float64")
            rnd = (cascade_sim.identity if dtype == "float64"
                   else cascade_sim.rounder(np.dtype(dtype)))
        sch, n, _ = self.lanes[lane]
        cfg, srv = self.cfg, self.cfg["server"]
        server = cascade_sim.Server(srv["base_latency_s"], srv["max_batch"],
                                    srv["batch_scaling"],
                                    tuple(cfg["batch_ladder"]))
        sched = cascade_sim.make_scheduler(
            sch, n, server=server, slo=cfg["slo_s"], a=cfg["a"],
            sr_target=cfg["sr_target"], init_threshold=cfg["init_threshold"],
            static_threshold=self.static_t, rnd=rnd)
        s = self.streams
        return cascade_sim.run(
            s["confidence"][lane, :n], s["correct_light"][lane, :n],
            s["correct_heavy"][lane, :n, :, 0], self.latency[:n],
            self.slo[:n], server, sched, window=cfg["window_s"], rnd=rnd,
            attribute=attribute)

    def check(self, units, control: bool = False) -> dict:
        """Every number compared, with its limit (the traffic's
        ``limits`` name them)."""
        lim = self.tr["limits"]
        return {k: (v, float(lim[k]))
                for k, v in self.numbers(units, control).items() if k in lim}

    def numbers(self, units, control: bool = False) -> dict:
        """Every number the check can compare: each unit's outputs equal
        to the first's, every sample of every lane completed, and the
        check lanes against the reference. ``control`` puts the
        reference, computed in bfloat16, in the program's place."""
        lanes = self.check_lanes()
        if control:
            rnd = cascade_sim.rounder(bfloat16)
            out = as_outputs({i: self.reference(i, rnd) for i in lanes})
            nums = {}
        else:
            out = units[-1]["out"]
            s = self.cfg["samples_per_device"]
            nums = {
                "units_differ": float(sum(
                    not _same(u["out"], units[0]["out"]) for u in units)),
                "lost_samples": float(sum(
                    abs(n * s - int(out["completed"][i]))
                    + abs(int(out["queue_left"][i]))
                    for i, (_, n, _) in enumerate(self.lanes)))}
        for lane in lanes:
            for k, v in compare(out, lane, self.reference(lane)).items():
                nums[k] = max(nums.get(k, 0.0), v)
        return nums


def _same(a, b) -> bool:
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
               for x, y in zip(la, lb))


def as_outputs(results: dict) -> dict:
    """Reference ``Result``s by lane, laid out as a sweep's outputs."""
    keys = {"sr": "sr", "accuracy": "accuracy", "forwarded_frac":
            "forwarded_frac", "throughput": "throughput",
            "completed": "completed", "per_device_sr": "per_device_sr",
            "per_device_acc": "per_device_acc"}
    out = {k: {i: getattr(r, a) for i, r in results.items()}
           for k, a in keys.items()}
    out["traces"] = {k: {i: getattr(r, f"trace_{k}")
                         for i, r in results.items()}
                     for k in ("sr", "acc", "fwd", "thresh")}
    return out


def _mean_gap(got, want) -> float:
    """Mean absolute gap of two per-device vectors; infinite where the
    program reports another number of devices."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.mean(np.abs(got - want)))


def compare(out: dict, lane: int, ref) -> dict:
    """Gaps between lane ``lane`` of a sweep's outputs and the reference
    run of the same lane (``cascade_sim.Result``)."""
    tr = out["traces"]
    keep = ~np.isnan(np.asarray(tr["fwd"][lane]))
    w = min(int(keep.sum()), len(ref.trace_fwd))
    got = {k: np.asarray(tr[k][lane])[keep][:w]
           for k in ("sr", "acc", "fwd", "thresh")}
    n = len(ref.per_device_sr)
    fwd_tot = max(ref.forwarded_frac * ref.completed, 1.0)
    nums = {
        "completed": float(abs(int(out["completed"][lane]) - ref.completed)),
        "sr": abs(float(out["sr"][lane]) - ref.sr),
        "accuracy": abs(float(out["accuracy"][lane]) - ref.accuracy),
        "forwarded": abs(float(out["forwarded_frac"][lane])
                         - ref.forwarded_frac),
        "throughput_rel": abs(float(out["throughput"][lane])
                              - ref.throughput) / ref.throughput,
        "device_sr": _mean_gap(out["per_device_sr"][lane][:n],
                               ref.per_device_sr),
        "device_acc": _mean_gap(out["per_device_acc"][lane][:n],
                                ref.per_device_acc),
        "windows": float(abs(int(keep.sum()) - len(ref.trace_fwd))),
    }
    if w < 2:   # no window closed: nothing to compare the traces with
        return dict(nums, trace_sr=np.inf, trace_acc=np.inf,
                    trace_fwd=np.inf, trace_thresh=np.inf)
    return dict(
        nums,
        trace_sr=float(np.mean(np.abs(got["sr"] - ref.trace_sr[:w]))),
        trace_acc=float(np.max(np.abs(got["acc"][1:]
                                      - ref.trace_acc[1:w]))),
        trace_fwd=float(np.max(np.abs(got["fwd"] - ref.trace_fwd[:w]))
                        / fwd_tot),
        trace_thresh=float(np.nanmean(np.abs(
            got["thresh"] - ref.trace_thresh[:w]))))
