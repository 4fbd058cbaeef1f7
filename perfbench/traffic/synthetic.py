"""The benchmark's own copy of the calibrated sample model and its stream
generators, so that no change to the program can move the traffic.

Copied from ``repro.sim.synthetic`` (fixture ``STREAM_FIXTURE_VERSION = 2``)
and from ``benchmarks/fig_scale.py``'s latency tiers; the tests in
``perfbench/tests/test_traffic.py`` hold the copies equal to the program's
generators. Per sample j of a device:

    z_j ~ N(0, 1)                               (sample difficulty)
    P(correct_light) = sigmoid(alpha_l - BETA * z_j)
    P(correct_heavy) = sigmoid(alpha_h - BETA * z_j)
    confidence       = sigmoid(GAMMA * (alpha_l - BETA * z_j) + eps)

with alpha fitted by bisection so the marginal accuracy matches the
profile. One numpy generator per sweep seed, keyed by a spawned
``SeedSequence`` child, draws z, then u, then eps as (N, M) blocks.
"""
from __future__ import annotations

import numpy as np

BETA = 2.2
GAMMA = 2.5
CONF_NOISE = 0.6
CHUNK_DEVICES = 4096


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _sigmoid_into(x: np.ndarray) -> np.ndarray:
    np.negative(x, out=x)
    np.exp(x, out=x)
    x += 1.0
    np.reciprocal(x, out=x)
    return x


def _fit_alpha(target_acc: float, z: np.ndarray) -> float:
    lo, hi = -10.0, 10.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if _sigmoid(mid - BETA * z).mean() < target_acc:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def _fit_alpha_batched(target_acc, bz: np.ndarray, buf: np.ndarray):
    """``_fit_alpha`` over the leading axes of the pre-scaled ``bz``."""
    target = np.broadcast_to(np.asarray(target_acc, np.float64),
                             bz.shape[:-1])
    lo = np.full(target.shape, -10.0)
    hi = np.full(target.shape, 10.0)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        np.subtract(mid[..., None], bz, out=buf)
        below = _sigmoid_into(buf).mean(axis=-1) < target
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    return 0.5 * (lo + hi)


def _seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence(int(seed)).spawn(1)[0])


def _streams_of(z, u, eps, light, heavy):
    """(S, W, M) draws of one device block -> the stream tensors."""
    bz = BETA * z
    buf = np.empty_like(bz)
    a_l = _fit_alpha_batched(light[None, :], bz, buf)
    logits_l = a_l[..., None] - bz
    correct_l = (u < _sigmoid(logits_l)).astype(np.int8)
    cols = []
    for acc in heavy:
        a_h = _fit_alpha_batched(acc, bz, buf)
        np.subtract(a_h[..., None], bz, out=buf)
        cols.append((u < _sigmoid_into(buf)).astype(np.int8))
    conf = _sigmoid(GAMMA * logits_l + CONF_NOISE * eps)
    return {"confidence": conf.astype(np.float32),
            "correct_light": correct_l,
            "correct_heavy": np.stack(cols, axis=-1)}


def batched_device_streams(seeds, n_devices: int, samples: int,
                           light_acc, heavy_acc):
    """``{confidence, correct_light, correct_heavy}`` of shape
    (len(seeds), n_devices, samples[, P]) in one vectorized pass."""
    n, m, s = n_devices, samples, len(seeds)
    light = np.broadcast_to(np.asarray(light_acc, np.float64), (n,))
    heavy = np.atleast_1d(np.asarray(heavy_acc, np.float64))
    z, u, eps = (np.empty((s, n, m)) for _ in range(3))
    for i, seed in enumerate(seeds):
        rng = _seed_rng(seed)
        z[i] = rng.standard_normal((n, m))
        u[i] = rng.random((n, m))
        eps[i] = rng.standard_normal((n, m))
    return _streams_of(z, u, eps, light, heavy)


def chunked_device_streams(seeds, n_devices: int, samples: int,
                           light_acc, heavy_acc,
                           chunk_devices: int = CHUNK_DEVICES):
    """The same tensors as ``batched_device_streams``, generated one
    device chunk at a time so generation memory stays O(chunk): three
    generators per seed sit at the z, u and eps positions of the one
    seed stream and advance in lockstep."""
    n, m, s, g = n_devices, samples, len(seeds), max(1, chunk_devices)
    light = np.broadcast_to(np.asarray(light_acc, np.float64), (n,))
    heavy = np.atleast_1d(np.asarray(heavy_acc, np.float64))
    rngs = []
    for seed in seeds:
        rng_z, rng_u = _seed_rng(seed), _seed_rng(seed)
        for lo in range(0, n, g):
            rng_u.standard_normal((min(g, n - lo), m))
        rng_eps = np.random.default_rng(0)
        rng_eps.bit_generator.state = rng_u.bit_generator.state
        for lo in range(0, n, g):
            rng_eps.random((min(g, n - lo), m))
        rngs.append((rng_z, rng_u, rng_eps))
    out = {"confidence": np.empty((s, n, m), np.float32),
           "correct_light": np.empty((s, n, m), np.int8),
           "correct_heavy": np.empty((s, n, m, len(heavy)), np.int8)}
    for lo in range(0, n, g):
        hi = min(lo + g, n)
        z, u, eps = (np.empty((s, hi - lo, m)) for _ in range(3))
        for i, (rng_z, rng_u, rng_eps) in enumerate(rngs):
            z[i] = rng_z.standard_normal((hi - lo, m))
            u[i] = rng_u.random((hi - lo, m))
            eps[i] = rng_eps.standard_normal((hi - lo, m))
        for k, v in _streams_of(z, u, eps, light[lo:hi], heavy).items():
            out[k][:, lo:hi] = v
    return out


def calibration_set(light_acc: float, heavy_acc: float, n: int = 10_000,
                    seed: int = 123):
    """The offline calibration split (the paper's first 10k validation
    images): (confidence, correct_light, correct_heavy) of one device."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal(n)
    a_l = _fit_alpha(light_acc, z)
    u = rng.random(n)
    correct_l = (u < _sigmoid(a_l - BETA * z)).astype(np.int8)
    a_h = _fit_alpha(float(heavy_acc), z)
    correct_h = (u < _sigmoid(a_h - BETA * z)).astype(np.int8)
    conf = _sigmoid(GAMMA * (a_l - BETA * z)
                    + CONF_NOISE * rng.standard_normal(n))
    return conf.astype(np.float32), correct_l, correct_h


def static_threshold(light_acc: float, heavy_acc: float, *,
                     target_forward: float = 0.30,
                     max_acc_loss: float = 0.01, grid: int = 512) -> float:
    """Sec. V-A's static threshold: forward ~30% of the calibration split,
    unless that loses more than 1 pp against the best cascade accuracy,
    then the lowest threshold within 1 pp of the best."""
    conf, cl, ch = calibration_set(light_acc, heavy_acc)
    conf = conf.astype(np.float64)
    ts = np.linspace(0.0, 1.0, grid + 1)
    fwd = conf[None, :] < ts[:, None]
    accs = np.where(fwd, ch[None, :], cl[None, :]).mean(axis=1)
    fracs = fwd.mean(axis=1)
    t30 = ts[int(np.argmin(np.abs(fracs - target_forward)))]
    acc30 = np.where(conf < t30, ch, cl).mean()
    if accs.max() - acc30 <= max_acc_loss:
        return float(t30)
    ok = np.nonzero(accs.max() - accs <= max_acc_loss)[0]
    return float(ts[ok[0]]) if len(ok) else float(t30)


def tier_latencies(n: int, base: float, mults, jitter, seed: int = 1):
    """Per-device latencies: equal tiers of the fleet by index, one
    latency multiplier each, times a per-device factor drawn uniformly
    from ``jitter = (lo, hi)``, as ``benchmarks/fig_scale.py`` draws
    them (float32)."""
    rng = np.random.default_rng(seed)
    tier = (np.arange(n) * len(mults)) // n
    mult = np.asarray(mults, np.float32)[tier]
    jit = rng.uniform(jitter[0], jitter[1], n).astype(np.float32)
    return (base * mult * jit).astype(np.float32)
