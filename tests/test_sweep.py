"""Batched sweep engine tests: run_sweep == serial run (bitwise), batched
stream generation, static/traced recompile behaviour."""
import compile_guard
import jax.numpy as jnp
import numpy as np
import pytest
from lane_utils import assert_lane_bitwise

from repro.configs.cascade_tiers import DEVICE_PROFILES, SERVER_PROFILES
from repro.sim import jaxsim, synthetic

DP = DEVICE_PROFILES["low"]
SP = SERVER_PROFILES["inceptionv3"]
SEEDS = (0, 1, 2)
N, SAMPLES = 8, 120


def _args(n=N):
    return np.full(n, DP.latency), np.full(n, 0.15)


def test_batched_streams_match_per_seed():
    batched = synthetic.batched_device_streams(SEEDS, N, SAMPLES,
                                               DP.accuracy, SP.accuracy)
    assert batched["confidence"].shape == (len(SEEDS), N, SAMPLES)
    for i, seed in enumerate(SEEDS):
        single = synthetic.device_streams(N, SAMPLES, DP.accuracy,
                                          SP.accuracy, seed)
        for k in ("confidence", "correct_light", "correct_heavy"):
            np.testing.assert_array_equal(batched[k][i], single[k], err_msg=k)


@pytest.mark.parametrize("light,heavy", [
    (0.72, 0.78),                                   # scalar accs
    (np.linspace(0.6, 0.8, N), [0.78, 0.84]),       # per-device + 2 servers
])
def test_vectorized_streams_match_loop_reference(light, heavy):
    """The single-pass generation (batched bisection alpha-fit + block
    draws) is bitwise-identical to its per-seed/per-device loop spec."""
    vec = synthetic.batched_device_streams(SEEDS, N, SAMPLES, light, heavy)
    ref = synthetic._reference_stream_blocks(SEEDS, N, SAMPLES, light,
                                             heavy)
    for k in ("confidence", "correct_light", "correct_heavy"):
        np.testing.assert_array_equal(vec[k], ref[k], err_msg=k)


def test_seed_derivation_no_cross_seed_collision():
    """Regression for the v1 ``seed*1000 + i`` derivation: sweep seed 0's
    device 1000 replayed sweep seed 1's device 0. SeedSequence-keyed
    block draws (fixture v2) must keep large fleets independent."""
    n, m = 1001, 8
    s0 = synthetic.device_streams(n, m, 0.72, 0.8, 0)
    s1 = synthetic.device_streams(n, m, 0.72, 0.8, 1)
    assert not np.array_equal(s0["confidence"][1000], s1["confidence"][0])
    # and a sanity check that the fixture version is declared
    assert synthetic.STREAM_FIXTURE_VERSION >= 2


@pytest.mark.parametrize("sched", ["multitasc++", "multitasc", "static"])
def test_sweep_matches_serial_bitwise(sched):
    lat, slo = _args()
    spec = jaxsim.JaxSimSpec(scheduler=sched, n_devices=N,
                             samples_per_device=SAMPLES,
                             static_threshold=0.6)
    batched = synthetic.batched_device_streams(SEEDS, N, SAMPLES,
                                               DP.accuracy, SP.accuracy)
    sweep = jaxsim.run_sweep(spec, batched, lat, slo, (SP,))
    for i, seed in enumerate(SEEDS):
        streams = synthetic.device_streams(N, SAMPLES, DP.accuracy,
                                           SP.accuracy, seed)
        serial = jaxsim.run(spec, streams, lat, slo, (SP,))
        for k in ("sr", "accuracy", "throughput"):
            assert float(serial[k]) == float(sweep[k][i]), (k, seed)
        np.testing.assert_array_equal(
            np.asarray(serial["per_device_sr"]),
            np.asarray(sweep["per_device_sr"][i]))


# lanes of one sweep that differ in scheduler and device count
RING_LANES = (("multitasc++", 8), ("multitasc", 5), ("static", 3),
              ("multitasc++", 6))


@pytest.mark.parametrize("queue_cap,frontier_seg", [
    (None, False),     # the default ring: no lane wraps
    (65, False),       # every lane wraps its ring two to six times
    (65, True),        # the same through the segmented frontier
])
def test_sweep_ring_lanes_match_serial_bitwise(queue_cap, frontier_seg):
    """The queue ring is one flat buffer over the lanes: a lane's
    appends, wraps and pops stay inside its own slots, so every lane of
    a mixed sweep equals its own serial run bitwise."""
    lat, slo = _args()
    specs = [jaxsim.JaxSimSpec(scheduler=sched, n_devices=n,
                               samples_per_device=SAMPLES,
                               init_threshold=0.9, static_threshold=0.9,
                               queue_cap=queue_cap)
             for sched, n in RING_LANES]
    seeds = tuple(range(len(specs)))
    batched = synthetic.batched_device_streams(seeds, N, SAMPLES,
                                               DP.accuracy, SP.accuracy)
    sweep = jaxsim.run_sweep(specs, batched, lat, slo, (SP,),
                             frontier_seg=frontier_seg)
    for i, spec in enumerate(specs):
        n = spec.n_devices
        streams = {k: v[i, :n] for k, v in batched.items()}
        serial = jaxsim.run(spec, streams, lat[:n], slo[:n], (SP,),
                            frontier_seg=frontier_seg)
        assert_lane_bitwise(sweep, i, serial, n)
        if queue_cap is not None:
            # no lane overruns its ring, and every lane wraps it
            forwarded = float(sweep["forwarded_frac"][i]) \
                * int(sweep["completed"][i])
            assert int(sweep["queue_peak"][i]) < queue_cap
            assert forwarded > 2 * queue_cap, (i, forwarded)


def test_one_compile_serves_many_traced_scalars():
    # unique static shape so the first call really does compile
    n, samples = 7, 90
    lat, slo = _args(n)
    streams = synthetic.batched_device_streams((0,), n, samples,
                                               DP.accuracy, SP.accuracy)

    def sweep(**kw):
        kw.setdefault("scheduler", "multitasc++")
        spec = jaxsim.JaxSimSpec(n_devices=n, samples_per_device=samples,
                                 **kw)
        out = jaxsim.run_sweep(spec, streams, lat, slo, (SP,))
        return float(np.asarray(out["sr"])[0])

    sweep()
    with compile_guard.no_recompiles():
        for kw in (dict(a=0.01), dict(static_threshold=0.9),
                   dict(a=0.02, sr_target=90.0), dict(init_threshold=0.1),
                   dict(mult_growth=0.0), dict(scheduler="multitasc"),
                   dict(scheduler="static", static_threshold=0.5)):
            sweep(**kw)


def test_distinct_structure_rejected():
    lat, slo = _args()
    streams = synthetic.batched_device_streams((0, 1), N, SAMPLES,
                                               DP.accuracy, SP.accuracy)
    specs = [
        jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=N,
                          samples_per_device=SAMPLES),
        jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=N,
                          samples_per_device=SAMPLES, window=3.0),
    ]
    with pytest.raises(ValueError, match="static structure"):
        jaxsim.run_sweep(specs, streams, lat, slo, (SP,))


def test_heterogeneous_specs_batch_in_one_call():
    """Different schedulers AND scalars per point, one call, per-point
    results (the scheduler kind is traced, so all three share a core)."""
    lat, slo = _args()
    streams = synthetic.device_streams(N, SAMPLES, DP.accuracy,
                                       SP.accuracy, 0)
    tiled = {k: np.stack([v, v, v]) for k, v in streams.items()}
    specs = [
        jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=N,
                          samples_per_device=SAMPLES, init_threshold=0.05),
        jaxsim.JaxSimSpec(scheduler="multitasc", n_devices=N,
                          samples_per_device=SAMPLES, init_threshold=0.95),
        jaxsim.JaxSimSpec(scheduler="static", n_devices=N,
                          samples_per_device=SAMPLES, static_threshold=0.7),
    ]
    out = jaxsim.run_sweep(specs, tiled, lat, slo, (SP,))
    final = np.asarray(out["final_thresh"])
    # both controllers act on the same stream but from different starts;
    # each row must match its own serial run
    for i, spec in enumerate(specs):
        serial = jaxsim.run(spec, streams, lat, slo, (SP,))
        np.testing.assert_array_equal(np.asarray(serial["final_thresh"]),
                                      final[i])


# the flat lane core reads a sample as one packed word: the confidence's
# float32 bits with correct_light in the sign bit
PACKED_CONF = (0.0, -0.0, float(np.finfo(np.float32).smallest_subnormal),
               0.5, float(np.nextafter(np.float32(1), np.float32(0))), 1.0)


@pytest.mark.parametrize("cl", [0, 1])
@pytest.mark.parametrize("conf", PACKED_CONF)
def test_stream_word_round_trips_bitwise(conf, cl):
    word = jaxsim._pack_stream_words(jnp.float32(conf), jnp.int32(cl))
    conf2, cl2 = jaxsim._unpack_stream_words(word)
    # -0.0 comes back as +0.0, every other confidence as itself
    expect = np.float32(conf) + np.float32(0.0)
    assert np.asarray(conf2).view(np.uint32) == expect.view(np.uint32)
    assert int(cl2) == cl


@pytest.mark.parametrize("sched", ["static", "multitasc++"])
def test_negative_zero_confidence_runs_as_zero(sched):
    """Every confidence -0.0 runs bitwise as +0.0: under a static
    threshold of 0 every sample stays local and its correct_light
    counts, so a sign bit leaking into it would move the accuracy."""
    lat, slo = _args()
    spec = jaxsim.JaxSimSpec(scheduler=sched, n_devices=N,
                             samples_per_device=SAMPLES,
                             static_threshold=0.0)
    streams = synthetic.device_streams(N, SAMPLES, DP.accuracy,
                                       SP.accuracy, 0)
    pos = dict(streams, confidence=np.zeros((N, SAMPLES), np.float32))
    neg = dict(streams, confidence=np.full((N, SAMPLES), -0.0, np.float32))
    out_pos = jaxsim.run(spec, pos, lat, slo, (SP,))
    out_neg = jaxsim.run(spec, neg, lat, slo, (SP,))
    assert float(out_pos["completed"]) == N * SAMPLES
    for k in ("sr", "accuracy", "throughput", "forwarded_frac",
              "completed", "n_events", "per_device_acc", "final_thresh"):
        np.testing.assert_array_equal(np.asarray(out_pos[k]),
                                      np.asarray(out_neg[k]), err_msg=k)


@pytest.mark.parametrize("key,value", [
    ("confidence", -0.25), ("confidence", np.nan), ("correct_light", 2)])
def test_sweep_refuses_streams_outside_contract(key, value):
    lat, slo = _args()
    spec = jaxsim.JaxSimSpec(scheduler="multitasc++", n_devices=N,
                             samples_per_device=SAMPLES)
    streams = synthetic.batched_device_streams(SEEDS, N, SAMPLES,
                                               DP.accuracy, SP.accuracy)
    bad = np.array(streams[key])
    bad[1, 3, 7] = value
    with pytest.raises(ValueError, match=key):
        jaxsim.run_sweep(spec, dict(streams, **{key: bad}), lat, slo,
                         (SP,))
