"""Benchmark harness: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV. ``--only fig4`` runs a subset;
``--quick`` shrinks seeds/samples for smoke runs.

``--mesh-shape 4`` (or ``2,2``) shards every figure's sweep axis over a
host mesh via ``run_sweep_sharded`` — emulate hosts on one machine with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (must be set
before jax initializes; CI runs exactly this).

``--json PATH`` (default ``BENCH_jaxsim.json`` under ``--quick``) records
``{figure: {wall_s, n_points, n_compiles, n_events, n_shards,
n_points_sharded}}`` per executed figure plus a top-level ``_schema``
version, so the perf trajectory of the sweep engine stays measurable
across PRs (``n_events`` = event-jump loop iterations: the quantity
wall time is proportional to; ``n_shards`` = mesh lanes the sweep axis
was sharded over).

``tools/check_bench.py`` compares a fresh ``--json`` against the
committed baseline (CI runs it on every push) and rejects runs whose
``_schema`` doesn't match its own ``BENCH_SCHEMA`` — bump BOTH (here
and there) when a field changes meaning, and re-capture the baseline.
"""
import argparse
import json
import sys
import time

# version of the per-figure json row layout; tools/check_bench.py
# asserts it before comparing (keep the two constants in lockstep —
# tests/test_system.py pins them equal)
BENCH_SCHEMA = 2


def main() -> None:
    ap = argparse.ArgumentParser(
        description="paper-figure benchmark harness; prints "
                    "name,us_per_call,derived CSV rows")
    ap.add_argument("--only", default=None, metavar="FIGURE",
                    help="run one figure (exact key, e.g. fig11 or"
                         " fig_churn) or a substring match")
    ap.add_argument("--quick", action="store_true",
                    help="smoke settings: 1 seed, 200 samples/device,"
                         " 3 fleet sizes; implies --json"
                         " BENCH_jaxsim.json unless --json given")
    ap.add_argument("--mesh-shape", default=None, metavar="N[,M]",
                    help="shard every figure's sweep axis over a host"
                         " mesh of this shape (e.g. 4 or 2,2); needs >="
                         " that many jax devices — emulate with XLA_FLAGS"
                         "=--xla_force_host_platform_device_count=N")
    ap.add_argument("--json", nargs="?", const="BENCH_jaxsim.json",
                    default=None, metavar="PATH",
                    help="write per-figure {wall_s, n_points, n_compiles,"
                         " n_events, n_shards, n_points_sharded} plus the"
                         " _schema version (default on for --quick)")
    args = ap.parse_args()

    from benchmarks import common
    common.use_compile_cache()
    if args.quick:
        common.SEEDS = (0,)
        common.SAMPLES = 200
        common.DEVICE_COUNTS = (2, 25, 100)
        if args.json is None:
            args.json = "BENCH_jaxsim.json"
    n_shards = 1
    if args.mesh_shape:
        from repro.launch.mesh import make_sweep_mesh, n_lanes
        shape = tuple(int(s) for s in args.mesh_shape.split(","))
        common.MESH = make_sweep_mesh(shape)
        n_shards = n_lanes(common.MESH)
        print(f"# sweep mesh {shape}: {n_shards} shards", file=sys.stderr)

    from benchmarks import (ablation_components, fig4_homogeneous,
                            fig7_heavy_server, fig10_convergence,
                            fig11_heterogeneous, fig11_lanes,
                            fig11_scaleout, fig15_transformers,
                            fig17_switching, fig19_intermittent,
                            fig_async, fig_churn, fig_scale,
                            fig_serving, kernels_bench)
    from repro.sim import jaxsim
    modules = {
        "fig4": fig4_homogeneous,
        "fig7": fig7_heavy_server,
        "fig10": fig10_convergence,
        "fig11": fig11_heterogeneous,
        "fig11_scaleout": fig11_scaleout,
        "fig11_lanes": fig11_lanes,
        "fig15": fig15_transformers,
        "fig17": fig17_switching,
        "fig19": fig19_intermittent,
        "fig_churn": fig_churn,
        "fig_scale": fig_scale,
        "fig_serving": fig_serving,
        "fig_async": fig_async,
        "ablation": ablation_components,
        "kernels": kernels_bench,
    }
    bench = {}
    print("name,us_per_call,derived")
    for key, mod in modules.items():
        # an exact figure name selects just that figure ("--only fig11"
        # must not drag in fig11_scaleout); otherwise substring-match
        if args.only and (key != args.only if args.only in modules
                          else args.only not in key):
            continue
        before = jaxsim.stats_snapshot()
        t0 = time.perf_counter()
        rows = mod.run()
        wall = time.perf_counter() - t0
        after = jaxsim.stats_snapshot()
        if not rows:
            # the module declined to run in this environment (e.g.
            # fig11_lanes on a partitioned host); leaving the row out
            # makes check_bench warn, not fail, on the missing figure
            continue
        bench[key] = {
            "wall_s": round(wall, 3),
            "n_points": after["points"] - before["points"],
            "n_compiles": after["backend_compiles"] - before["backend_compiles"],
            "n_events": after["events"] - before["events"],
            "n_shards": n_shards,
            # points that actually executed on a >1-lane sharded core
            # (B=1 sweeps fall back to the local path even with a mesh)
            "n_points_sharded": after["sharded_points"]
                                - before["sharded_points"],
        }
        # figure-specific gated metrics (e.g. fig11_lanes' wall-per-
        # point ratios) ride the same json row
        bench[key].update(getattr(mod, "EXTRA_JSON", {}))
        for row in rows:
            print(row.csv())
            sys.stdout.flush()
    if args.json:
        bench["_schema"] = BENCH_SCHEMA
        with open(args.json, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
