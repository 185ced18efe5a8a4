"""Benchmark 4 — distributed scaling (madupite's memory/compute distribution
claim).  Runs the same solve on 1 vs 8 (forced-host) devices in subprocesses
and reports wall time + per-device state bytes; the 256/512-chip scaling
artifact is the dry-run (results/dryrun_all.json).

PR 7 rows: ``-comm_overlap on`` vs ``off`` iteration throughput on the
8-fake-device stencil workload (same XLA flags both sides, bitwise-equal
results asserted in-bench), and ``async_vi`` vs synchronous ``vi``
wall-clock at equal span tolerance.  ``MADUPITE_BENCH_SCALE`` (CI: 0.02)
scales the instance sizes."""

from __future__ import annotations

import json
import os
import subprocess
import sys

SCALE = float(os.environ.get("MADUPITE_BENCH_SCALE", "1.0"))

def _round8(x: float, lo: int = 64) -> int:
    return max(lo, int(x)) // 8 * 8


# full scale: 8M-state chain_walk stencil; CI (SCALE=0.02): ~167k states
N_OVERLAP = _round8(8_388_608 * SCALE, 4096)
OVERLAP_ITERS = 20
N_GARNET = _round8(200_000 * SCALE, 8_000)

_CHILD = r"""
import os, sys, time, json
n_dev = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
import jax
jax.config.update("jax_enable_x64", True)
from repro.core import IPIOptions, generators
from repro.core.driver import solve
n = int(sys.argv[2])
mdp = generators.garnet(n, 8, 8, gamma=0.99, seed=1)
opts = IPIOptions(method="ipi_gmres", atol=1e-8, dtype="float64")
mesh = None
if n_dev > 1:
    from repro.launch.mesh import mesh_kwargs
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"), **mesh_kwargs(2))
t0 = time.time(); r = solve(mdp, opts, mesh=mesh); wall = time.time() - t0
# warm second solve (excludes compile)
t0 = time.time(); r = solve(mdp, opts, mesh=mesh); warm = time.time() - t0
print("RESULT " + json.dumps(dict(wall=wall, warm=warm,
      outer=r.outer_iterations, inner=r.inner_iterations,
      converged=bool(r.converged))))
"""

# -comm_overlap on vs off in ONE child (same XLA flags, bitwise compare).
# Fixed-iteration throughput: atol=1e-30 never trips, max_outer bounds work.
_CHILD_OVERLAP = r"""
import os, sys, time, json
n, iters = int(sys.argv[1]), int(sys.argv[2])
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    "--xla_cpu_enable_concurrency_optimized_scheduler=true "
    "--xla_cpu_enable_fast_min_max=false")
import jax
import numpy as np
from repro.core import IPIOptions, generators
from repro.core.driver import solve
from repro.launch.mesh import mesh_kwargs
mdp = generators.chain_walk(n, gamma=0.9999)
mesh = jax.make_mesh((8,), ("data",), **mesh_kwargs(1))
out = {}
res, opt, best = {}, {}, {}
for ov in ("off", "on"):
    opt[ov] = IPIOptions(method="mpi", mpi_sweeps=10, atol=1e-30,
                         max_outer=iters, dtype="float32", comm_overlap=ov)
    res[ov] = solve(mdp, opt[ov], mesh=mesh)       # compile
    best[ov] = float("inf")
for _ in range(3):          # interleave warm reps so machine drift cancels
    for ov in ("off", "on"):
        t0 = time.time()
        res[ov] = solve(mdp, opt[ov], mesh=mesh)
        best[ov] = min(best[ov], time.time() - t0)
for ov in ("off", "on"):
    out[f"itps_{ov}"] = iters / best[ov]
out["bitwise_v"] = bool(np.array_equal(
    np.asarray(res["off"].v).view(np.uint32),
    np.asarray(res["on"].v).view(np.uint32)))
out["policy_eq"] = bool(np.array_equal(np.asarray(res["off"].policy),
                                       np.asarray(res["on"].policy)))
print("RESULT " + json.dumps(out))
"""

# async_vi (k stale sweeps per exchange) vs synchronous vi, equal span
# tolerance; same-policy + certificate checked in-child.  The maze uses
# slip=0.45 (slow mixing -> 2.5x fewer exchanges, the regime async VI
# targets) and a deterministic 1e-3 cost jitter: a square maze has many
# equal-length routes whose exactly-tied Q-values would otherwise let f64
# rounding pick different (equally optimal) argmins per trajectory.
_CHILD_ASYNC = r"""
import os, sys, time, json, dataclasses
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import IPIOptions, generators
from repro.core.driver import solve
from repro.launch.mesh import mesh_kwargs
if sys.argv[1] == "chain":
    mdp, sweeps = generators.chain_walk(512, gamma=0.99), 8
else:
    mdp, sweeps = generators.maze2d(24, gamma=0.99, slip=0.45), 3
    rng = np.random.default_rng(0)
    cost = np.asarray(mdp.cost) * (1 + 1e-3 * rng.random(mdp.cost.shape))
    cost[np.asarray(mdp.cost) == 0] = 0.0          # keep the goal absorbing
    mdp = dataclasses.replace(mdp, cost=cost)
mesh = jax.make_mesh((8,), ("data",), **mesh_kwargs(1))
out, res, opt, best = {}, {}, {}, {}
for method, kw in (("vi", {}), ("async_vi", dict(async_sweeps=sweeps))):
    opt[method] = IPIOptions(method=method, atol=1e-6, dtype="float64",
                             stop_criterion="span", max_outer=4000, **kw)
    res[method] = solve(mdp, opt[method], mesh=mesh)   # compile
    best[method] = float("inf")
for _ in range(5):          # interleave warm reps so machine drift cancels
    for method in opt:
        t0 = time.time()
        res[method] = solve(mdp, opt[method], mesh=mesh)
        best[method] = min(best[method], time.time() - t0)
for method in opt:
    out[f"wall_{method}"] = best[method]
    out[f"outer_{method}"] = int(res[method].outer_iterations)
    assert res[method].converged
out["policy_eq"] = bool(np.array_equal(np.asarray(res["vi"].policy),
                                       np.asarray(res["async_vi"].policy)))
out["gap"] = float(res["async_vi"].gap_bound)
print("RESULT " + json.dumps(out))
"""


def _child(script: str, *argv: object) -> dict:
    # the children fake CPU devices; they must never take an accelerator
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script,
                          *map(str, argv)],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def run(csv_rows: list):
    for n_dev in (1, 8):
        rec = _child(_CHILD, n_dev, N_GARNET)
        csv_rows.append((f"scaling/garnet{N_GARNET//1000}k/devices={n_dev}",
                         rec["warm"] * 1e6,
                         f"outer={rec['outer']};inner={rec['inner']};"
                         f"converged={rec['converged']}"))
        print(f"  devices={n_dev}: warm={rec['warm']:.2f}s "
              f"(cold {rec['wall']:.2f}s) outer={rec['outer']}", flush=True)

    # communication-overlapped backups (PR 7 tentpole a): mpi's policy
    # sweeps each carry a value exchange, so the planner's collective
    # shrink (full all-gather -> frontier-reach ring exchange) compounds
    rec = _child(_CHILD_OVERLAP, N_OVERLAP, OVERLAP_ITERS)
    assert rec["bitwise_v"] and rec["policy_eq"], rec
    ratio = rec["itps_on"] / rec["itps_off"]
    for ov in ("off", "on"):
        csv_rows.append(
            (f"scaling/overlap_chain{N_OVERLAP}_mpi/comm_overlap={ov}",
             OVERLAP_ITERS / rec[f"itps_{ov}"] * 1e6,
             f"itps={rec[f'itps_{ov}']:.3f};bitwise_v=True;"
             f"ratio_on_off={ratio:.3f}"))
    print(f"  overlap chain n={N_OVERLAP} (mpi/10 sweeps): "
          f"off={rec['itps_off']:.2f} it/s on={rec['itps_on']:.2f} it/s "
          f"({ratio:.2f}x, bitwise-identical)", flush=True)

    # async_vi stale sweeps vs synchronous vi (PR 7 tentpole b)
    for inst, tag in (("chain", "chain512"), ("maze", "maze24_slip45")):
        rec = _child(_CHILD_ASYNC, inst)
        assert rec["policy_eq"], rec
        speedup = rec["wall_vi"] / rec["wall_async_vi"]
        for m in ("vi", "async_vi"):
            csv_rows.append((f"scaling/async_{tag}/method={m}",
                             rec[f"wall_{m}"] * 1e6,
                             f"outer={rec[f'outer_{m}']};policy_eq=True;"
                             f"gap={rec['gap']:.3e};"
                             f"speedup_async={speedup:.3f}"))
        print(f"  async {tag}: vi={rec['wall_vi']:.2f}s "
              f"({rec['outer_vi']} outers) "
              f"async_vi={rec['wall_async_vi']:.2f}s "
              f"({rec['outer_async_vi']} exchanges) {speedup:.2f}x, "
              f"same policy, gap<={rec['gap']:.2e}", flush=True)
