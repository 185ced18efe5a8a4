#!/usr/bin/env python3
"""Drive madupite's solve and serve paths once on a TPU and check the results.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips  # four chips: the sharded leg only

Phases, all through the user entry points (``repro.api.Session``,
``repro.serve.Server``) with data made from fixed seeds:

  (a) main solve — a garnet MDP with n = 2^20 states, m = 16 actions, K = 8
      successors, gamma = 0.99 (a 1.14 GB ELL table), solved by the default
      method ``ipi_gmres`` in float32 to a certified residual; the residual
      is recomputed independently with ``repro.kernels.ref.ell_backup``;
  (b) parity — one backup and one policy SpMV on that table through the
      ``auto`` kernel path and through ``xla`` must agree bit for bit, and
      so must the row-major ``blocked`` formulation on a row slice;
  (c) reference — a small garnet solved on the chip against an exact float64
      policy iteration on the host (dense direct solves);
  (d) adaptive — ``-method auto`` on a chain walk, n = 2^18, gamma = 0.999;
  (e) server — a ragged burst of garnet requests answered by a ``Server``
      bitwise equal to sequential ``Session.solve`` calls.

``--four-chips`` runs only the row-partitioned (``1d``) layout over four
chips — the garnet of phase (a) (all-gather exchange) and a banded chain walk
(``2 * reach`` ring exchange), by ``ipi_gmres`` and by ``mpi`` — each against
the ``single`` layout solve of the same instance in the same process.

Each phase prints its numbers on lines of its own.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  Without a TPU (or outside a checkout) the script exits non-zero
before doing any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# phase (a) / (b) and the four-chip garnet
GARNET = dict(n=1 << 20, m=16, k=8, gamma=0.99, seed=0)
MAIN_ATOL = 1e-3          # f32 floor at |v| ~ 50 is ~1e-5; 1e-3 certifies
MIN_TABLE_BYTES = 1 << 30
PARITY_ROWS = 1 << 16     # row slice for the row-major cross-check
# phase (c)
REFERENCE = dict(n=2048, m=8, k=8, gamma=0.99, seed=1)
REF_ATOL = 1e-4
# phase (d)
ADAPTIVE = dict(n=1 << 18, gamma=0.999)
ADAPT_ATOL = 1e-2         # values reach 1/(1-gamma) = 1000: f32 floor ~1e-4
# phase (e)
SERVE_NS = (50_000, 70_000, 50_000, 70_000, 70_000, 50_000, 70_000, 50_000)
SERVE = dict(m=8, k=8, gamma=0.95)
SERVE_ATOL = 1e-4
# four-chip chain walk
CHAIN = dict(n=1 << 20, gamma=0.99)
CHAIN_ATOL = 1e-3
FOUR_CHIP_METHODS = ("ipi_gmres", "mpi")   # the default, and a Krylov-free one
LAYOUT_BITWISE_METHODS = ("vi", "mpi")

F32_EPS = 2.0 ** -23


class SmokeFailure(Exception):
    """A phase produced a wrong or unchecked result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# --------------------------------------------------------------------------- #
# Compile accounting (jax.monitoring events, so it needs no private API)      #
# --------------------------------------------------------------------------- #

class CompileClock:
    """Seconds spent tracing, lowering and compiling, and persistent-cache
    hits, since the last :meth:`reset`."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def reset(self) -> tuple[float, int]:
        with self._lock:
            out = (self.seconds, self.cache_hits)
            self.seconds, self.cache_hits = 0.0, 0
        return out


def _bits_equal(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------- #
# Phases                                                                       #
# --------------------------------------------------------------------------- #

def phase_main(clock: CompileClock, gen: dict, atol: float,
               min_table_bytes: int = 0):
    """(a) One large garnet, solved twice (cold, then warm) by the default
    method.  Returns the core MDP and the solve result for phase (b)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import MDP, Session
    from repro.kernels import matrix_free, ops, ref

    t0 = time.perf_counter()
    mdp = MDP.from_generator("garnet", **gen)
    core = mdp.build()
    jax.block_until_ready((core.idx, core.val, core.cost))
    tbytes = matrix_free.table_bytes(gen["n"], gen["m"], gen["k"])
    say("a", instance="garnet", n=gen["n"], m=gen["m"], K=gen["k"],
        gamma=gen["gamma"], table_bytes=tbytes,
        build_s=time.perf_counter() - t0)
    check(tbytes >= min_table_bytes,
          f"table of {tbytes} bytes is below {min_table_bytes}")

    opts = {"-method": "ipi_gmres", "-dtype": "float32", "-atol": atol,
            "-verbose": False}
    impl = ops.resolve_impl()
    with Session(opts) as sess:
        clock.reset()
        t0 = time.perf_counter()
        r = sess.solve(mdp)
        cold = time.perf_counter() - t0
        compile_s, hits = clock.reset()
        t0 = time.perf_counter()
        r2 = sess.solve(mdp)
        warm = time.perf_counter() - t0
        warm_compile_s, _ = clock.reset()
    peak = _peak_bytes(jax.devices()[0])
    say("a", kernel_impl=impl, tiles="none (one fused XLA chain)"
        if impl == "xla" else f"{impl} autotuned", atol=atol)
    say("a", cold_solve_s=cold, compile_s=compile_s,
        compile_cache_hits=hits, warm_solve_s=warm,
        warm_compile_s=warm_compile_s)
    say("a", converged=r.converged, outer=r.outer_iterations,
        inner=r.inner_iterations, residual=repr(r.residual),
        gap_bound=repr(r.gap_bound), peak_bytes_in_use=peak)
    check(r.converged and r.residual <= atol,
          f"main solve not certified: {r.summary()}")
    check(r2.outer_iterations == r.outer_iterations
          and _bits_equal(r2.v, r.v), "warm solve differs from cold solve")

    # independent residual: the plain reference backup, on the same chip
    v = jnp.asarray(r.v, jnp.float32)
    tv, _ = jax.jit(ref.ell_backup)(core.idx, core.val, core.cost,
                                    core.gamma, v)
    res = float(jnp.max(jnp.abs(tv - v)))
    say("a", independent_residual=repr(res),
        agrees_bitwise=res == r.residual)
    check(res <= atol, f"independent residual {res} > atol {atol}")
    check(abs(res - r.residual) <= 1e-2 * atol,
          f"independent residual {res} != reported {r.residual}")
    return core, r


def phase_parity(core, result, rows: int) -> None:
    """(b) auto vs xla, bit for bit, on the full table; and the row-major
    blocked formulation vs xla on a row slice."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    v = jnp.asarray(result.v, jnp.float32)
    pi = jnp.asarray(result.policy, jnp.int32)
    idx, val, cost, gamma = core.idx, core.val, core.cost, core.gamma

    def policy_rows(i, w, p):
        sel = p[:, None, None]
        return (jnp.take_along_axis(i, sel, axis=1)[:, 0],
                jnp.take_along_axis(w, sel, axis=1)[:, 0])

    idx_pi, val_pi = jax.jit(policy_rows)(idx, val, pi)

    def both(impl_a, impl_b, i, w, c, ip, wp, x, block_rows=None):
        tv_a, am_a = ops.ell_backup(i, w, c, gamma, x, impl=impl_a,
                                    block_rows=block_rows)
        tv_b, am_b = ops.ell_backup(i, w, c, gamma, x, impl=impl_b)
        y_a = ops.ell_matvec(ip, wp, x, impl=impl_a, block_rows=block_rows)
        y_b = ops.ell_matvec(ip, wp, x, impl=impl_b)
        return (_bits_equal(tv_a, tv_b), _bits_equal(am_a, am_b),
                _bits_equal(y_a, y_b))

    auto = both("auto", "xla", idx, val, cost, idx_pi, val_pi, v)
    say("b", auto_impl=ops.resolve_impl("auto"), rows=idx.shape[0],
        backup_values_equal=auto[0], backup_argmin_equal=auto[1],
        spmv_equal=auto[2])
    check(all(auto), f"auto vs xla not bitwise equal: {auto}")

    # four scan chunks, fixed here rather than autotuned: the tiling must
    # not change a bit either
    r = min(rows, idx.shape[0])
    bn = max(1, r // 4)
    blk = both("blocked", "xla", idx[:r], val[:r], cost[:r], idx_pi[:r],
               val_pi[:r], v, block_rows=bn)
    say("b", cross_impl="blocked", rows=r, block_rows=bn,
        backup_values_equal=blk[0], backup_argmin_equal=blk[1],
        spmv_equal=blk[2])
    check(all(blk), f"blocked vs xla not bitwise equal: {blk}")


def exact_policy_iteration(idx, val, cost, gamma):
    """Howard's policy iteration in float64 with dense direct solves:
    the plain host reference for small instances.  Returns ``(v*, pi*, Q*)``.
    """
    import numpy as np

    n, m, _ = idx.shape
    rows = np.arange(n)
    P = np.zeros((m, n, n))
    for a in range(m):
        np.add.at(P[a], (rows[:, None], idx[:, a, :]), val[:, a, :])
    cost = cost.astype(np.float64)
    pi = np.zeros(n, np.int64)
    for _ in range(200):
        v = np.linalg.solve(np.eye(n) - gamma * P[pi, rows],
                            cost[rows, pi])
        q = cost + gamma * np.einsum("asj,j->sa", P, v)
        best = np.argmin(q, axis=1)
        # switch only on strict improvement: the loop then terminates
        better = q[rows, best] < q[rows, pi] - 1e-12 * np.abs(v).max()
        if not better.any():
            return v, pi, q
        pi = np.where(better, best, pi)
    raise SmokeFailure("reference policy iteration did not terminate")


def phase_reference(gen: dict, atol: float) -> None:
    """(c) The chip's solve against the exact float64 solution."""
    import numpy as np

    from repro.api import MDP, Session

    mdp = MDP.from_generator("garnet", **gen)
    core = mdp.build()
    with Session({"-method": "ipi_gmres", "-dtype": "float32",
                  "-atol": atol, "-verbose": False}) as sess:
        r = sess.solve(mdp)
    check(r.converged, f"reference-size solve not certified: {r.summary()}")
    t0 = time.perf_counter()
    v_star, pi_star, q_star = exact_policy_iteration(
        np.asarray(core.idx), np.asarray(core.val, np.float64),
        np.asarray(core.cost, np.float64), gen["gamma"])
    host_s = time.perf_counter() - t0
    v = np.asarray(r.v, np.float64)
    err = float(np.abs(v - v_star).max())
    # the certificate, plus f32 rounding of one backup amplified by
    # 1/(1-gamma) (the certificate is computed from f32 residuals)
    slack = 16 * F32_EPS * float(np.abs(v_star).max()) / (1 - gen["gamma"])
    tol = r.gap_bound + slack
    rows = np.arange(gen["n"])
    pi = np.asarray(r.policy)
    diff = np.flatnonzero(pi != pi_star)
    # a differing action must be a near-tie of the exact Q-values
    q_gap = q_star[rows, pi] - q_star[rows, pi_star]
    worst_tie = float(q_gap[diff].max()) if diff.size else 0.0
    say("c", n=gen["n"], m=gen["m"], K=gen["k"], outer=r.outer_iterations,
        inner=r.inner_iterations, residual=repr(r.residual),
        gap_bound=repr(r.gap_bound), value_err=repr(err), tol=repr(tol),
        policy_diffs=int(diff.size), worst_diff_q_gap=repr(worst_tie),
        host_reference_s=host_s)
    check(err <= tol, f"value error {err} > gap_bound + f32 slack {tol}")
    check(worst_tie <= 2 * tol,
          f"policy differs from exact beyond a near-tie: {worst_tie}")


def phase_adaptive(gen: dict, atol: float) -> None:
    """(d) ``-method auto``: probe, select, supervise (maybe hot-swap)."""
    from repro.api import MDP, Session

    mdp = MDP.from_generator("chain_walk", **gen)
    with Session({"-method": "auto", "-dtype": "float32", "-atol": atol,
                  "-verbose": False}) as sess:
        t0 = time.perf_counter()
        r = sess.solve(mdp)
        wall = time.perf_counter() - t0
        rep = sess.stats[-1].get("adaptive") or {}
    choice = rep.get("choice") or {}
    say("d", instance="chain_walk", n=gen["n"], gamma=gen["gamma"],
        chose=choice.get("method"), stop=choice.get("stop_criterion"),
        pc=choice.get("pc_type"), reason=repr(choice.get("reason")))
    for s in rep.get("swaps", []):
        say("d", hot_swap_at_k=s["k"], frm=s["from_method"],
            to=s["to_method"], reason=repr(s["reason"]))
    say("d", methods_run="->".join(rep.get("methods", [])),
        converged=r.converged, outer=r.outer_iterations,
        inner=r.inner_iterations, residual=repr(r.residual),
        gap_bound=repr(r.gap_bound), wall_s=wall)
    check(bool(choice), "auto recorded no method choice")
    check(r.converged, f"auto solve not certified: {r.summary()}")


def phase_server(ns, gen: dict, atol: float) -> None:
    """(e) A burst of ragged requests through ``Server``, bitwise equal to
    sequential ``Session.solve``.  A failed request raises from
    ``result()``, which fails the phase."""
    from repro.api import MDP, Session
    from repro.serve import Server

    mdps = [MDP.from_generator("garnet", n=n, seed=100 + i, **gen)
            for i, n in enumerate(ns)]
    opts = {"-method": "vi", "-dtype": "float32", "-atol": atol,
            "-verbose": False}
    t0 = time.perf_counter()
    with Server({**opts, "-serve_batch_window": 0.5}) as srv:
        reqs = [srv.submit(m) for m in mdps]
        results = [srv.result(q, timeout=900) for q in reqs]
        stats = srv.stats()
    burst = time.perf_counter() - t0
    with Session(opts) as sess:
        t0 = time.perf_counter()
        base = [sess.solve(m) for m in mdps]
        seq = time.perf_counter() - t0
    equal = [_bits_equal(a.v, b.v) and _bits_equal(a.policy, b.policy)
             and a.outer_iterations == b.outer_iterations
             for a, b in zip(results, base)]
    say("e", requests=len(ns), ns=",".join(map(str, sorted(set(ns)))),
        dispatches=stats.get("dispatches"), failed=stats.get("failed"),
        burst_s=burst, sequential_s=seq,
        bitwise_equal=sum(equal))
    check(all(r.converged for r in results), "a served solve did not converge")
    check(not stats.get("failed"), f"server failed requests: {stats}")
    check(all(equal), f"served results differ from Session.solve: {equal}")


def leg_four_chips(clock: CompileClock, garnet: dict, chain: dict) -> None:
    """The row-partitioned layout over four chips against the single-chip
    solve of the same instance: garnet (all-gather) and a banded chain walk
    (ring exchange), each by the default method and by ``mpi``."""
    import jax

    from repro.api import MDP

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    for name, gen, atol in (("garnet", garnet, MAIN_ATOL),
                            ("chain_walk", chain, CHAIN_ATOL)):
        mdp = MDP.from_generator(name, **gen)
        core = mdp.build()
        for method in FOUR_CHIP_METHODS:
            opts = {"-method": method, "-dtype": "float32", "-atol": atol,
                    "-verbose": False}
            out = {layout: _solve_on(clock, core, mdp, name, gen, opts, layout,
                                     report=method == FOUR_CHIP_METHODS[0])
                   for layout in ("single", "1d")}
            _compare_layouts(core, name, method, out["single"], out["1d"])


def _solve_on(clock, core, mdp, name, gen, opts, layout, report):
    """One solve under ``layout``; with ``report``, first print (and check)
    how the sharded table spreads over the devices."""
    import jax

    from repro.api import Session
    from repro.core import partition

    with Session({**opts, "-layout": layout}) as sess:
        mesh, lay = sess.placement()
        if mesh is not None and report:
            placed, _, _ = partition.shard_mdp(core, mesh, lay)
            n_sh = len(mesh.devices.flat)
            per_dev = {}
            for a in (placed.idx, placed.val, placed.cost):
                for s in a.addressable_shards:
                    per_dev[s.device.id] = (per_dev.get(s.device.id, 0)
                                            + s.data.nbytes)
            say("4", instance=name, layout=lay, mesh=dict(mesh.shape),
                table_bytes_per_device=per_dev,
                frontier_reach=partition.frontier_reach(placed, n_sh),
                overlap_margins=partition.overlap_margins(placed, n_sh))
            check(len(per_dev) == 4 and len(set(per_dev.values())) == 1,
                  f"table not spread evenly over 4 devices: {per_dev}")
            del placed
        clock.reset()
        t0 = time.perf_counter()
        r = sess.solve(mdp)
        wall = time.perf_counter() - t0
        compile_s, _ = clock.reset()
    say("4", instance=name, n=gen["n"], method=opts["-method"], layout=layout,
        converged=r.converged, outer=r.outer_iterations,
        inner=r.inner_iterations, residual=repr(r.residual),
        gap_bound=repr(r.gap_bound), wall_s=wall, compile_s=compile_s,
        peak_bytes_in_use={d.id: _peak_bytes(d) for d in jax.devices()})
    check(r.converged, f"{name} {layout} not certified: {r.summary()}")
    return r


def _compare_layouts(core, name, method, a, b) -> None:
    """``1d`` result ``b`` against ``single`` result ``a``.

    Backups are row-independent and the sup-norm stops use max, so ``vi``
    and ``mpi`` are bit-equal across layouts.  The Krylov dots of
    ``ipi_gmres`` sum in another order over four shards; the inner solves
    may then stop an iteration apart, so the two values agree within their
    certificates (each is within ``gap_bound`` of v*), not to the bit, and
    a row whose best actions tie within that difference may pick either.
    """
    import numpy as np

    vmax = float(np.abs(a.v).max())
    dv = float(np.abs(np.asarray(a.v, np.float64)
                      - np.asarray(b.v, np.float64)).max())
    diff = np.flatnonzero(np.asarray(a.policy) != np.asarray(b.policy))
    worst = _q_gap(core, a.v, a.policy, b.policy, diff)
    tie = 2 * dv + 4 * F32_EPS * max(vmax, 1.0)
    bitwise = _bits_equal(a.v, b.v) and _bits_equal(a.policy, b.policy)
    say("4", instance=name, method=method, bitwise_equal=bitwise,
        policy_diffs=int(diff.size), worst_diff_q_gap=repr(worst),
        tie_tol=repr(tie), value_diff=repr(dv),
        value_diff_in_f32_ulps_of_vmax=dv / (F32_EPS * vmax),
        certificates=repr(a.gap_bound + b.gap_bound),
        outer_single=a.outer_iterations, outer_1d=b.outer_iterations,
        inner_single=a.inner_iterations, inner_1d=b.inner_iterations)
    if method in LAYOUT_BITWISE_METHODS:
        check(bitwise, f"{name} {method}: 1d differs from single")
    check(worst <= tie, f"{name} {method}: 1d policy differs from single "
                        f"beyond a near-tie: {worst} > {tie}")
    check(dv <= a.gap_bound + b.gap_bound,
          f"{name} {method}: 1d values differ by {dv}, beyond both "
          f"certificates {a.gap_bound} + {b.gap_bound}")


def _q_gap(core, v, pi_a, pi_b, rows) -> float:
    """Largest ``Q(v)[s, pi_b[s]] - Q(v)[s, pi_a[s]]`` over ``rows`` (0 when
    there are none): how far from a tie the disagreeing actions are."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref

    if not rows.size:
        return 0.0
    r = jnp.asarray(rows)
    q = np.asarray(ref.ell_qvalues(core.idx[r], core.val[r], core.cost[r],
                                   core.gamma, jnp.asarray(v, jnp.float32)),
                   np.float64)
    at = np.arange(rows.size)
    return float((q[at, np.asarray(pi_b)[rows]]
                  - q[at, np.asarray(pi_a)[rows]]).max())


# --------------------------------------------------------------------------- #
# Entry point                                                                  #
# --------------------------------------------------------------------------- #

def _require_tpu():
    """The accelerator, or exit non-zero before any work."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it "
                 f"from a madupite checkout")
    sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded leg")
    args = ap.parse_args(argv)

    devs = _require_tpu()
    from repro.utils import compile_cache

    cache_dir = compile_cache.enable()
    print(f"[env] device={devs[0].device_kind} count={len(devs)} "
          f"compile_cache={cache_dir}", flush=True)
    clock = CompileClock()
    t_all = time.perf_counter()
    if args.four_chips:
        leg_four_chips(clock, GARNET, CHAIN)
    else:
        core, r = phase_main(clock, GARNET, MAIN_ATOL, MIN_TABLE_BYTES)
        phase_parity(core, r, PARITY_ROWS)
        del core
        phase_reference(REFERENCE, REF_ATOL)
        phase_adaptive(ADAPTIVE, ADAPT_ATOL)
        phase_server(SERVE_NS, SERVE, SERVE_ATOL)
    print(f"[env] total_s={time.perf_counter() - t_all}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[FAIL] {e}", file=sys.stderr, flush=True)
        sys.exit(1)
