"""(BB, BV) tile autotuner for the fused BvSB kernel.

Sweeps the tile grid for a representative serving shape (the largest
ladder bucket x the tier vocab), times each candidate through the same
jitted dispatch wrapper the hot path uses, and persists the winner to
``kernels/tuned_tiles.json`` keyed by backend — ``ops.bvsb_tiles()``
picks it up (and folds it into ``cache_token()``, so retuning can never
reuse an executable compiled for the old tiles).

Each candidate is sanity-checked two ways before it can win:

* **numerics** — its outputs must match the ``ref`` dispatch on the
  sweep input (a mistiled kernel loses to the gate, not to luck);
* **roofline** — the measured us/sample is reported against the memory
  bound ``B*V*4 / hbm_bw``, with the attached chip's published HBM
  bandwidth from ``roofline/analysis.PEAKS`` (a TPU whose kind is not in
  the table raises). On a CPU host the interpret-mode kernel is held
  against the v5e target's bound and sits far above it (that is
  expected and recorded, not enforced); on a TPU backend a candidate
  slower than ``max_over_bound`` x the bound is rejected as mistiled.

Tuning is explicitly offline (`python -m repro.kernels.autotune`): the
serving path never tunes implicitly, because timing noise must not pick
different tiles — and therefore different executables — run to run.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.kernels import ops
from repro.kernels.timing import time_blocked
from repro.roofline.analysis import TARGET_KIND, peaks

CANDIDATE_BB = (4, 8, 16, 32)
CANDIDATE_BV = (128, 256, 512, 1024)

# default sweep shape: the largest batch ladder bucket x tier vocab
SWEEP_B = 64
SWEEP_V = 2048

# TPU-only rejection threshold: measured / roofline-bound above this is
# a mistiled candidate, not noise
MAX_OVER_BOUND = 20.0

NUMERIC_ATOL = 2e-3


def roofline_floor_s(b: int, v: int) -> float:
    """Memory-bound floor: the kernel must at least read the logits."""
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else TARGET_KIND
    return (b * v * 4) / peaks(kind).hbm_bw


def sweep(b: int = SWEEP_B, v: int = SWEEP_V, *, mode: str = None,
          seed: int = 0):
    """Time every (BB, BV) candidate; returns a sorted result list.

    Candidates whose tiles exceed the sweep shape collapse to the same
    clamped tiling (kernels/bvsb.py clamps), so they are skipped after
    the first equivalent entry.
    """
    if mode is None:
        mode = ops.dispatch_mode()
    if mode == "ref":
        raise ValueError("cannot tune tiles in ref mode (no tiling)")
    rng = np.random.default_rng(seed)
    logits = jax.device_put(
        rng.standard_normal((b, v)).astype(np.float32) * 4.0)
    want_conf, want_top1 = ops._bvsb_dispatch(logits, mode="ref",
                                              bb=0, bv=0)
    want_conf = np.asarray(want_conf)
    want_top1 = np.asarray(want_top1)
    floor = roofline_floor_s(b, v)

    results, seen = [], set()
    for bb in CANDIDATE_BB:
        for bv in CANDIDATE_BV:
            eff = (min(bb, b), min(bv, v))
            if eff in seen:
                continue
            seen.add(eff)
            conf, top1 = ops._bvsb_dispatch(logits, mode=mode,
                                            bb=bb, bv=bv)
            max_err = float(np.max(np.abs(np.asarray(conf) - want_conf)))
            mismatch = int(np.sum(np.asarray(top1) != want_top1))
            ok = max_err <= NUMERIC_ATOL and mismatch == 0

            def run(x=logits, bb=bb, bv=bv):
                out = ops._bvsb_dispatch(x, mode=mode, bb=bb, bv=bv)
                jax.block_until_ready(out)

            per_call, wall, reps = time_blocked(run)
            results.append({
                "bb": bb, "bv": bv, "mode": mode,
                "us_per_call": per_call * 1e6,
                "us_per_sample": per_call * 1e6 / b,
                "over_bound": per_call / floor,
                "block_wall_s": wall, "reps": reps,
                "max_err": max_err, "top1_mismatch": mismatch,
                "numerics_ok": ok,
            })
    results.sort(key=lambda r: r["us_per_call"])
    return results


def pick(results, *, backend: str = None):
    """The fastest candidate that passed numerics (and, on TPU, the
    roofline rejection). Returns None if every candidate failed."""
    if backend is None:
        backend = jax.default_backend()
    for r in results:
        if not r["numerics_ok"]:
            continue
        if backend == "tpu" and r["over_bound"] > MAX_OVER_BOUND:
            continue
        return r
    return None


def persist(winner, *, backend: str = None,
            path: str = ops.TUNED_TILES_PATH) -> dict:
    if backend is None:
        backend = jax.default_backend()
    tiles = {}
    if os.path.exists(path):
        try:
            with open(path, encoding="utf-8") as f:
                tiles = json.load(f)
        except ValueError:
            tiles = {}
    tiles[backend] = {
        "bb": winner["bb"], "bv": winner["bv"], "mode": winner["mode"],
        "sweep_b": SWEEP_B, "sweep_v": SWEEP_V,
        "us_per_sample": round(winner["us_per_sample"], 3),
        "over_roofline_bound": round(winner["over_bound"], 1),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(tiles, f, indent=2, sort_keys=True)
        f.write("\n")
    ops.reload_tiles()
    return tiles


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--b", type=int, default=SWEEP_B)
    ap.add_argument("--v", type=int, default=SWEEP_V)
    ap.add_argument("--mode", default=None,
                    help="pallas|interpret (default: current dispatch)")
    ap.add_argument("--dry-run", action="store_true",
                    help="sweep and report without persisting")
    args = ap.parse_args(argv)

    results = sweep(args.b, args.v, mode=args.mode)
    for r in results:
        flag = "" if r["numerics_ok"] else "  [NUMERICS FAIL]"
        print(f"  bb={r['bb']:>3} bv={r['bv']:>5}  "
              f"{r['us_per_sample']:8.2f} us/sample  "
              f"{r['over_bound']:8.1f}x bound{flag}")
    winner = pick(results)
    if winner is None:
        print("autotune: every candidate failed numerics/roofline")
        return 1
    print(f"winner: bb={winner['bb']} bv={winner['bv']} "
          f"({winner['us_per_sample']:.2f} us/sample)")
    if not args.dry_run:
        persist(winner)
        print(f"persisted to {ops.TUNED_TILES_PATH}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
