"""Benchmark harness — one module per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (and progress to stderr-ish
stdout), and persists the same rows machine-readably to
``benchmarks/results/BENCH_batch.json`` so the perf trajectory accumulates
across PRs.  Full suite:

    PYTHONPATH=src:. python -m benchmarks.run [--only solvers,kernels,...]

Tables:
  solvers       — method comparison across instance families (core claim)
  conditioning  — gamma -> 1 sweep (Krylov-iPI vs VI iteration growth)
  kernels       — fused Bellman backup vs unfused reference
  scaling       — 1 vs 8 device distributed solve
  batch         — fleet solve_many vs sequential loop (>= 3x claim)
  fleet         — fleet-sharded layout: per-device memory ~B/fleet_size of
                  the replicated layout + weak scaling (needs multi-device,
                  e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8)
  api           — session-layer dispatch overhead (<5% warm) +
                  from_functions million-state construction
  serve         — batched serving vs sequential solves (>= 2x claim) +
                  Poisson-arrival latency quantiles
  adaptive      — -method auto vs fixed methods (within 1.3x of best) +
                  preconditioned-vs-plain GMRES on the outliers
  lm_substrate  — per-arch smoke train-step timing
(roofline terms live in benchmarks/roofline.py -> results/roofline.json)
"""

import argparse
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: solvers,conditioning,kernels,scaling,"
                         "batch,fleet,api,serve,adaptive,lm_substrate")
    ap.add_argument("--json-out", default=None,
                    help="path for the machine-readable results "
                         "(default: benchmarks/results/BENCH_batch.json)")
    args = ap.parse_args()

    from benchmarks import (bench_adaptive, bench_api, bench_batch,
                            bench_conditioning, bench_fleet, bench_kernels,
                            bench_lm_substrate, bench_scaling, bench_serve,
                            bench_solvers)
    suites = {
        "solvers": bench_solvers.run,
        "conditioning": bench_conditioning.run,
        "kernels": bench_kernels.run,
        "scaling": bench_scaling.run,
        "batch": bench_batch.run,
        "fleet": bench_fleet.run,
        "api": bench_api.run,
        "serve": bench_serve.run,
        "adaptive": bench_adaptive.run,
        "lm_substrate": bench_lm_substrate.run,
    }
    pick = args.only.split(",") if args.only else list(suites)
    rows = []
    for name in pick:
        print(f"== bench:{name} ==", flush=True)
        try:
            suites[name](rows)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"  [FAIL] {name}: {type(e).__name__}: {e}", flush=True)
            rows.append((f"{name}/SUITE_FAILED", -1, str(e)[:80]))
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    out = os.path.abspath(args.json_out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results",
        "BENCH_batch.json"))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # merge by row name: a partial (--only ...) run refreshes its own rows
    # without clobbering the others, so the file accumulates the trajectory
    merged = {}
    if os.path.exists(out):
        try:
            with open(out) as f:
                merged = {r["name"]: r for r in json.load(f)}
        except (json.JSONDecodeError, KeyError, TypeError):
            merged = {}
    for name, us, derived in rows:
        merged[name] = {"name": name, "us_per_call": us, "derived": derived}
    # a suite that ran clean this time retires its stale failure marker
    failed = {name for name, _, _ in rows if name.endswith("/SUITE_FAILED")}
    for suite in pick:
        marker = f"{suite}/SUITE_FAILED"
        if marker not in failed:
            merged.pop(marker, None)
    with open(out, "w") as f:
        json.dump(list(merged.values()), f, indent=2)
    print(f"\n[run] wrote {len(rows)} rows ({len(merged)} total) -> {out}")


if __name__ == "__main__":
    from repro.utils import compile_cache
    compile_cache.enable()
    main()
