"""Host driver: chunked, checkpointed, optionally distributed iPI solve.

This is the user-facing ``solve`` — the analogue of madupite's
``madupite.solve(mdp, options)``.  The device-side loop runs in bounded
chunks; between chunks the host persists the solver state (preemption /
node-failure tolerance) and reports progress.  Distribution wraps the same
device code in ``shard_map`` over the supplied mesh (1-D paper-faithful or
2-D state x action layout — see :mod:`repro.core.partition`).

Fleet solves — :func:`solve_many`
---------------------------------
Real workloads are *fleets* of related MDPs (seed ensembles, gamma sweeps,
scenario/robustness studies).  ``solve_many(mdps, opts)`` stacks them into
one batched container (:func:`repro.core.mdp.stack_mdps`), runs ONE compiled
chunked loop for the whole fleet (``jax.vmap`` of the outer iteration inside
the same ``lax.while_loop`` / ``shard_map`` machinery ``solve`` uses), and
returns per-instance :class:`SolveResult`\\ s.  Converged instances freeze via
a per-instance active mask, so each result carries the same ``k`` /
``inner_total`` / traces B independent ``solve`` calls would have produced —
while the fleet amortizes dispatch, compilation and kernel launches (the
``benchmarks/bench_batch.py`` claim).  Heterogeneous state counts are padded
(results are trimmed back); heterogeneous gammas run the traced-gamma path.

Under the *fleet-sharded* layouts (``layout="fleet"`` / ``"fleet2d"``) the
instance dim itself is partitioned over the mesh's leading ``fleet`` axis —
per-device fleet memory is ``B / fleet_size`` of the replicated layouts, so
fleet size scales with the mesh (``benchmarks/bench_fleet.py``).

Checkpoints are mesh-agnostic: the solver state is saved *unsharded and
unpadded* (state dims trimmed to the true ``n``, fleet dim to the true
``B``), and restore re-pads for whatever mesh the resumed job runs on — a
fleet solved on an 8-way fleet axis restores onto a 4-way one, and an
``n`` that pads differently per mesh size round-trips exactly.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ipi, methods, partition
from repro.core.comm import Axes
from repro.core.ipi import IPIOptions, SolveState
from repro.core.mdp import (DenseMDP, EllMDP, MatrixFreeMDP, MDP, gammas_of,
                            stack_mdps)
from repro.utils import checkpoint as ckpt
from repro.utils import trace


@dataclasses.dataclass
class SolveResult:
    v: np.ndarray                  # (n,) optimal values (padding trimmed)
    policy: np.ndarray             # (n,) int32 greedy policy
    residual: float                # final ||T v - v||_inf
    gap_bound: float               # ||v - v*||_inf certificate: res/(1-gamma)
                                   # (span stopping: gamma*sp/(2*(1-gamma))
                                   # on the midpoint-corrected v)
    converged: bool
    outer_iterations: int
    inner_iterations: int
    trace_residual: np.ndarray     # (outer+1,)
    trace_inner: np.ndarray        # (outer,)
    diverged: bool = False         # residual went NaN or blew past
                                   # opts.divtol * res0 — the solve stopped
                                   # early and v/policy are NOT certified
    span: float = float("inf")     # final sp(T v - v) (inf unless the stop
                                   # criterion declared needs_span)

    def summary(self) -> str:
        flag = " DIVERGED" if self.diverged else ""
        return (f"converged={self.converged} outer={self.outer_iterations} "
                f"inner={self.inner_iterations} residual={self.residual:.3e} "
                f"gap<= {self.gap_bound:.3e}{flag}")


def _get(x):
    """``x`` on the host.  A fetch of device arrays is one blocking host
    transfer, counted in :func:`repro.utils.trace.host_transfers`."""
    if any(isinstance(a, jax.Array) for a in jax.tree_util.tree_leaves(x)):
        trace.count_host_transfer()
    return jax.device_get(x)


def _result(state: SolveState, opts: IPIOptions, gamma: float,
            n_orig: int) -> SolveResult:
    k = int(_get(state.k))
    res = float(_get(state.res))
    converged = bool(_get(state.done))  # the compiled stop criterion's verdict
    span = float(_get(state.span))
    v = np.asarray(_get(state.v))[:n_orig]
    gap = res / (1.0 - gamma)
    if converged and opts.stop_criterion == "span" and gamma < 1.0:
        # Midpoint correction (Puterman §6.6): for any v with
        # d = T v - v,  T v + gamma/(1-gamma) * min(d) <= v* <=
        # T v + gamma/(1-gamma) * max(d)  (T is monotone and shifts
        # constants by gamma, for min- and max-backups alike), so the
        # midpoint-shifted T v carries the certified error bound
        # gamma * sp(d) / (2 * (1-gamma)) — the whole point of span
        # stopping, which the raw iterate (error only <= res/(1-gamma))
        # would squander.  A constant shift, so the policy is untouched.
        tv = np.asarray(_get(state.tv))[:n_orig]
        d = tv - v
        scale = gamma / (1.0 - gamma)
        v = tv + scale * (float(d.max()) + float(d.min())) / 2.0
        gap = scale * span / 2.0
    return SolveResult(
        v=v,
        policy=np.asarray(_get(state.pi))[:n_orig],
        residual=res,
        gap_bound=gap,
        converged=converged,
        outer_iterations=k,
        inner_iterations=int(_get(state.inner_total)),
        trace_residual=np.asarray(_get(state.trace_res))[:k + 1],
        trace_inner=np.asarray(_get(state.trace_inner))[:k],
        diverged=bool(_get(state.diverged)),
        span=span)


def _validate_banded(mdp, halo: int, mesh, layout: str) -> None:
    """The halo layout is only exact when every transition stays within
    +-halo of its source row (matrix bandwidth <= halo) and the halo fits in
    one shard.  Raises ``ValueError`` (not assert: must survive -O)."""
    if isinstance(mdp, MatrixFreeMDP):
        # no arrays to measure: trust (and require) the declared bandwidth
        if mdp.spec.band is None:
            raise ValueError(
                "halo>0 on a matrix-free operator needs a declared matrix "
                "bandwidth — there is no stored table to measure; pass "
                "band=... to from_functions() (max |successor - row| over "
                "all nonzero transitions) or drop to halo=0")
        band = int(mdp.spec.band)
    elif not isinstance(mdp, EllMDP):
        raise ValueError("halo>0 requires the ELL representation; DenseMDP "
                         "columns are global — drop halo or convert the MDP")
    else:
        idx = np.asarray(mdp.idx)
        rows = np.arange(mdp.n_global).reshape(-1, 1, 1)
        band = int(np.abs(idx - rows).max())
    if band > halo:
        raise ValueError(
            f"matrix bandwidth {band} exceeds halo {halo}: the banded "
            f"exchange would silently drop transitions; set halo >= {band} "
            f"or use the all-gather layout (halo=0)")
    if mesh is not None:
        n_shards = int(np.prod([
            mesh.shape[a] for a in partition.mesh_axes(mesh, layout).state]))
        n_local = -(-mdp.n_global // n_shards)
        if halo > n_local:
            raise ValueError(
                f"halo {halo} exceeds the per-shard state count {n_local} "
                f"({n_shards} shards x {mdp.n_global} states): boundary "
                f"exchange would need >1 ring hop; use fewer shards or a "
                f"smaller halo")


def _resolve_overlap(opts: IPIOptions, dev_mdp, mesh, axes: Axes) \
        -> IPIOptions:
    """Resolve ``-comm_overlap auto|on|off`` into a static interior/frontier
    plan baked into ``opts`` (compiled programs key on ``opts`` as a jit
    static, so a changed plan retraces — exactly right, the row split is a
    compile-time constant).

    ``on`` overlaps whenever a contiguous interior core exists (banded /
    stencil instances); ``auto`` additionally requires the core to cover at
    least half the local rows (hiding the gather behind a sliver of interior
    compute would not pay for the split).  Dense-random instances have no
    interior core and silently stay on the synchronous path.

    When a plan exists and the user left ``-halo 0``, the planner also
    *shrinks the collective*: :func:`partition.frontier_reach` measures how
    far outside its own block any row's nonzero successors reach, and the
    solve runs on the banded halo layout at exactly that width — the value
    exchange drops from the full ``n_global`` all-gather to a ``2 * reach``
    ring exchange (exact by construction, so no `_validate_banded` pass is
    needed).  This is where the overlapped path wins on hardware without
    async collective support; with async collectives the remaining ring
    exchange additionally hides behind the interior compute.
    """
    plan, halo = None, opts.halo
    if opts.comm_overlap != "off" and mesh is not None:
        n_shards = partition._axis_size(mesh, axes.state)
        plan = partition.overlap_margins(dev_mdp, n_shards)
        if plan is not None and opts.comm_overlap == "auto":
            n_local = dev_mdp.n_global // n_shards
            if n_local - plan[0] - plan[1] < n_local // 2:
                plan = None
        if plan is not None and opts.halo == 0:
            reach = partition.frontier_reach(dev_mdp, n_shards)
            n_local = dev_mdp.n_global // n_shards
            # ring exchange reaches one neighbour: reach must fit a shard
            # (use half — beyond that the window approaches the gather)
            if reach is not None and reach <= n_local // 2:
                halo = max(int(reach), 1)
    if plan == opts.overlap_plan and halo == opts.halo:
        return opts
    return dataclasses.replace(opts, overlap_plan=plan, halo=halo)


def _drain_monitor(mid: int, state: SolveState, done_prev, k_prev) -> None:
    """``monitor_mode="chunk"``: reconstruct this run-chunk's per-iteration
    records host-side from the device traces — record-for-record (``k`` /
    ``res`` / ``inner``) what ``"stream"`` would have emitted, without one
    ``jax.debug.callback`` host sync per outer iteration (``elapsed`` is the
    drain time).  ``done_prev`` / ``k_prev`` are the pre-chunk done mask and
    iteration counts (``done_prev=None`` for a single-instance solve)."""
    k = np.asarray(_get(state.k))
    tr = np.asarray(_get(state.trace_res))
    ti = np.asarray(_get(state.trace_inner))
    div_f = np.asarray(_get(state.diverged))
    if k.ndim == 0:
        for kk in range(int(k_prev) + 1, int(k) + 1):
            # diverged flips exactly at the iteration the loop stopped on,
            # so only the final reconstructed record can carry it — same
            # sequence the stream emits
            methods.emit_host(mid, kk, float(tr[kk]),
                              max(int(ti[kk - 1]), 0),
                              bool(div_f) and kk == int(k))
        return
    act_prev = ~np.asarray(done_prev)
    if not act_prev.any():
        return
    res_f = np.asarray(_get(state.res))
    # lockstep invariant: all active lanes share one outer index, so the
    # stream's per-iteration k_col sequence is exactly this range
    k_lo = int(np.asarray(k_prev)[act_prev].max())
    k_hi = int(k[act_prev].max())
    for kk in range(k_lo + 1, k_hi + 1):
        col = tr[:, kk]
        # frozen lanes: the stream reports their (frozen) current residual —
        # pre-chunk-done lanes override their historical trace value, lanes
        # frozen mid-chunk have an unwritten (NaN) column
        col = np.where(~act_prev | np.isnan(col), res_f, col)
        inn = ti[:, kk - 1]
        inn = np.where(~act_prev | (inn < 0), 0, inn).astype(np.int32)
        methods.emit_host(mid, kk, col, inn,
                          div_f & (kk == k) if kk == k_hi
                          else np.zeros_like(div_f))


_RUN_CHUNK_CACHE: dict = {}


def clear_run_cache() -> None:
    """Drop every cached jit'd ``run_chunk`` wrapper.

    The session layer (:mod:`repro.api.session`) owns the cache lifecycle:
    a closing session releases the compiled programs (and the device MDPs
    they pin via their sharding closures) instead of letting them accumulate
    for the life of the process.  (The module-level ``ipi.solve_chunk`` jit
    cache is left alone — other live sessions share it; it is cleared
    automatically when a registry name is replaced with ``overwrite=True``,
    see the ``_clear_compiled`` hook below.)"""
    _RUN_CHUNK_CACHE.clear()


def _clear_compiled() -> None:
    """Registry hot-swap hook: a re-registered KSP/method/stop-criterion is
    looked up at trace time, so every compiled solve program — the shard_map
    run_chunk wrappers AND the module-level single-device ``solve_chunk``
    jit cache — must be dropped or the old code keeps running."""
    _RUN_CHUNK_CACHE.clear()
    ipi.solve_chunk.clear_cache()
    ipi.init_state_jit.clear_cache()


methods.on_overwrite_clear(_clear_compiled)


def _make_runners(dev_mdp, opts: IPIOptions, mesh, axes: Axes, batch,
                  n_true=None):
    """(run_chunk, init) closures for single-device or shard_map execution.

    ``n_true`` (int, or per-instance int sequence for fleets) is the
    unpadded state count baked into the initial :class:`SolveState` — the
    span stop criterion masks mesh-pad rows with it."""
    if mesh is None:
        run_chunk = partial(ipi.solve_chunk, opts=opts, axes=axes)
        init = lambda v0: ipi.init_state_jit(dev_mdp, v0, None, n_true,
                                             opts=opts, axes=axes)
        return run_chunk, init
    # Batched fleets: the leading instance dim (and the per-instance res / k
    # / trace vectors) shard over axes.fleet — which is None (replicated)
    # for the 1d/2d layouts, keeping their previous behavior.
    lead = () if batch is None else (axes.fleet,)
    scal = P() if batch is None else P(axes.fleet)
    mdp_specs = partition.mdp_pspecs(dev_mdp, axes)
    # win: the halo window is per-shard (overlapping windows concatenate
    # along the state axis); the all-gathered window is replicated
    win_spec = P(*lead, axes.state) if opts.halo else P(*lead)
    state_specs = SolveState(
        v=P(*lead, axes.state), tv=P(*lead, axes.state),
        pi=P(*lead, axes.state),
        res=scal, k=scal, inner_total=scal, trace_res=scal,
        trace_inner=scal, res0=scal, span=scal, done=scal, diverged=scal,
        n_true=scal, win=win_spec)
    # Reuse one jit wrapper per (mesh, opts, axes, specs) so repeated solves
    # of same-shaped problems — a serving fleet, bench reps, the chunked
    # restart loop — hit jax's compilation cache instead of re-tracing a
    # fresh wrapper every call.  The specs pytree (treedef includes the MDP
    # statics) is exactly what determines the wrapped program.
    in_specs = (mdp_specs, state_specs, P(), P())   # (..., k_hi, mon_id)
    flat, treedef = jax.tree_util.tree_flatten(in_specs)
    key = (mesh, opts, axes, treedef, tuple(flat))
    run_chunk = _RUN_CHUNK_CACHE.get(key)
    if run_chunk is None:
        if len(_RUN_CHUNK_CACHE) > 64:   # bound growth: drop the oldest
            _RUN_CHUNK_CACHE.pop(next(iter(_RUN_CHUNK_CACHE)))
        run_chunk = jax.jit(
            jax.shard_map(
                partial(ipi.solve_chunk, opts=opts, axes=axes),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=state_specs, check_vma=False),
        )
        _RUN_CHUNK_CACHE[key] = run_chunk

    def init(v0):
        if v0 is None:
            f = jax.jit(
                jax.shard_map(
                    lambda m: ipi.init_state(m, axes, opts, n_true=n_true),
                    mesh=mesh, in_specs=(mdp_specs,),
                    out_specs=state_specs, check_vma=False))
            return f(dev_mdp)
        v_spec = P(*lead, axes.state)
        v0 = jax.device_put(jnp.asarray(v0), NamedSharding(mesh, v_spec))
        f = jax.jit(
            jax.shard_map(
                lambda m, v: ipi.init_state(m, axes, opts, v,
                                            n_true=n_true),
                mesh=mesh, in_specs=(mdp_specs, v_spec),
                out_specs=state_specs, check_vma=False))
        return f(dev_mdp, v0)

    return run_chunk, init


def _trim_ckpt_state(state: SolveState, n_orig: int,
                     b_orig: int | None) -> SolveState:
    """Solver state in its mesh-agnostic checkpoint form: gathered to host
    and stripped of mesh padding (state dims trimmed to the true ``n_orig``,
    fleet dim to the true ``b_orig``).  Restore re-pads for the resuming
    mesh, so a job may restart on a mesh that pads differently (elastic
    restart across device counts / fleet-axis sizes)."""
    host = _get(state)
    lead = (lambda x: np.asarray(x)[:b_orig]) if b_orig is not None \
        else np.asarray
    return SolveState(
        v=lead(host.v)[..., :n_orig], tv=lead(host.tv)[..., :n_orig],
        pi=lead(host.pi)[..., :n_orig], res=lead(host.res),
        k=lead(host.k), inner_total=lead(host.inner_total),
        trace_res=lead(host.trace_res), trace_inner=lead(host.trace_inner),
        res0=lead(host.res0), span=lead(host.span), done=lead(host.done),
        diverged=lead(host.diverged), n_true=lead(host.n_true),
        # the exchanged window is mesh-dependent derived state (invariant
        # win == gather(v)); checkpoint it empty — restore zero-fills, i.e.
        # the k=0 iterate, a valid stale async restart window
        win=lead(host.win)[..., :0])


def _pad_restored(tree, like):
    """Zero-pad a restored (unpadded) checkpoint to the current mesh's
    padded shapes.  Zero is exact, not approximate: padded states are
    absorbing zero-cost self-loops (``v == tv == 0``, greedy action 0 —
    precisely the values the solver would have computed for them), and
    padded fleet lanes get ``res == 0``, freezing them under the active
    mask from the first restored iteration."""
    def pad(a, l):
        a = np.asarray(a)
        if a.shape != l.shape:
            if len(a.shape) != len(l.shape) or \
                    any(s > t for s, t in zip(a.shape, l.shape)):
                raise ValueError(
                    f"checkpoint leaf of shape {a.shape} does not fit this "
                    f"solve's {tuple(l.shape)}: the checkpoint was written "
                    f"by a different problem or options (e.g. a larger "
                    f"max_outer, n, or fleet size); point checkpoint_dir "
                    f"at a fresh directory or re-run with the original "
                    f"settings")
            # bool leaves are the `done` flags: padded fleet lanes are dummy
            # instances and must restore as already-converged (True), not as
            # active lanes the zero-fill would wake up
            fill = True if a.dtype == np.bool_ else 0
            a = np.pad(a, [(0, t - s) for s, t in zip(a.shape, l.shape)],
                       constant_values=fill)
        return a.astype(l.dtype)
    return jax.tree_util.tree_map(pad, tree, like)


def _restore_or_init(init, v0, checkpoint_dir, verbose, expect=None):
    """``expect`` maps checkpoint-meta keys (``n`` / ``batch``) to the
    values this solve requires — a mismatch means the directory holds some
    *other* problem's checkpoint, which zero-padding would otherwise
    silently absorb."""
    if checkpoint_dir and ckpt.latest_step(checkpoint_dir) is not None:
        like = jax.eval_shape(init, v0)
        restored = ckpt.restore(checkpoint_dir, like)
        if restored is not None:
            tree, _, meta = restored
            for key, want in (expect or {}).items():
                got = meta.get(key)
                if got is not None and got != want:
                    raise ValueError(
                        f"checkpoint in {checkpoint_dir!r} was written for "
                        f"{key}={got} but this solve has {key}={want}; "
                        f"refusing to resume from another problem's state")
            tree = _pad_restored(tree, like)
            if verbose:
                print(f"[driver] resumed at outer k="
                      f"{int(np.max(np.asarray(tree.k)))}")
            return tree
    return init(v0)


def _reject_virtual(opts: IPIOptions) -> None:
    if methods.get_method(opts.method).virtual:
        raise ValueError(
            f"method {opts.method!r} is a virtual (meta) method — the "
            f"adaptive layer resolves it to a concrete solver first; use "
            f"repro.api.Session.solve (which routes -method auto "
            f"automatically) or repro.adaptive.solve_adaptive")


def solve(mdp: MDP, opts: IPIOptions = IPIOptions(), *,
          mesh=None, layout: str = "1d", v0=None,
          checkpoint_dir: str | None = None, chunk: int = 64,
          checkpoint_mode: str = "chunk",
          verbose: bool = False, monitor=None, supervisor=None) \
        -> SolveResult:
    """Solve an MDP until ``opts.stop_criterion`` is satisfied (default:
    ``||T v - v||_inf <= opts.atol``).

    ``mesh=None`` runs single-device; otherwise the MDP is padded, sharded
    onto ``mesh`` and the identical loop runs SPMD under ``shard_map``.

    ``monitor`` (requires ``opts.monitor=True``) is a callable receiving one
    dict per outer iteration — ``{"k", "res", "inner", "diverged",
    "elapsed"}`` — streamed out of the compiled loop via
    ``jax.debug.callback``; when ``opts.monitor`` is set without a callable,
    records print PETSc-style (:func:`repro.core.methods.print_monitor`).

    ``supervisor`` is a between-chunks hook for the adaptive layer: a
    callable receiving ``{"k", "res", "k_prev", "res_prev", "diverged"}``
    once per completed chunk; returning truthy interrupts the solve (the
    current state is checkpointed when ``checkpoint_dir`` is set, so the
    caller can resume it under different options — the hot-swap path).  A
    diverged state interrupts the loop on its own.

    ``checkpoint_mode`` controls when ``checkpoint_dir`` is written:
    ``"chunk"`` (default) persists after every run chunk — the
    fault-tolerance contract; ``"interrupt"`` writes only when the solve is
    interrupted mid-flight (supervisor trigger or divergence), which is all
    the adaptive hot-swap needs — supervised solves then pay zero
    checkpoint overhead on the happy path.
    """
    if mdp.batch is not None:
        raise ValueError("solve() takes one MDP instance; for a batched "
                         "fleet use solve_many()")
    _reject_virtual(opts)
    if checkpoint_mode not in ("chunk", "interrupt"):
        raise ValueError(f"checkpoint_mode={checkpoint_mode!r}: expected "
                         f"'chunk' or 'interrupt'")
    if layout in partition.FLEET_LAYOUTS:
        raise ValueError(f"layout={layout!r} shards the fleet (instance) "
                         "dim, which a single solve() does not have; use "
                         "solve_many() or layout='1d'/'2d'")
    n_orig = mdp.n_global
    if opts.halo:
        _validate_banded(mdp, opts.halo, mesh, layout)
    if mesh is None:
        axes = Axes()
        dev_mdp = mdp
    else:
        dev_mdp, axes, n_orig = partition.shard_mdp(mdp, mesh, layout,
                                                    mode=opts.mode)
        if v0 is not None:
            v0 = jnp.pad(jnp.asarray(v0),
                         (0, dev_mdp.n_global - n_orig))
    opts = _resolve_overlap(opts, dev_mdp, mesh, axes)
    run_chunk, init = _make_runners(dev_mdp, opts, mesh, axes, None,
                                    n_true=n_orig)

    with trace.span(trace.DRIVER_INIT):
        state = _restore_or_init(init, v0, checkpoint_dir, verbose,
                                 expect=dict(n=n_orig))
    save_each = bool(checkpoint_dir) and checkpoint_mode == "chunk"

    def save_state() -> None:
        ckpt.save(checkpoint_dir, int(_get(state.k)),
                  _trim_ckpt_state(state, n_orig, None),
                  meta=dict(method=opts.method, n=n_orig))

    mid = 0
    if opts.monitor:
        mid = methods.monitor_handle(monitor or methods.print_monitor)
    try:
        if mid:   # the k=0 (or resume-point) record, emitted host-side
            k0, res0 = _get((state.k, state.res))
            methods.emit_host(mid, int(k0), float(res0), 0)
        prev = None
        while True:
            # one host round-trip for the whole control tuple: separate
            # device_gets multiply the per-chunk sync latency,
            # which dominates warm small-n solves
            with trace.span(trace.DRIVER_SYNC):
                k, res, done, div = _get(
                    (state.k, state.res, state.done, state.diverged))
            k, res, done, div = int(k), float(res), bool(done), bool(div)
            if verbose:
                print(f"[driver] k={k} residual={res:.3e}"
                      + (" DIVERGED" if div else ""))
            # NaN residual (inner-solver breakdown): neither "active" on
            # device nor "converged" here — bail out, don't spin forever.
            # Likewise a diverged flag (residual past divtol * res0).
            if done or k >= opts.max_outer or np.isnan(res) or div:
                # a NaN-poisoned state is not worth persisting: the resume
                # path discards it anyway
                if div and not np.isnan(res) and checkpoint_dir \
                        and not save_each:
                    save_state()
                break
            if supervisor is not None and prev is not None and supervisor(
                    dict(k=k, res=res, k_prev=prev[0], res_prev=prev[1],
                         diverged=div)):
                if checkpoint_dir and not save_each:
                    save_state()
                break
            prev = (k, res)
            k_hi = jnp.int32(min(k + chunk, opts.max_outer))
            with trace.span(trace.DRIVER_DISPATCH):
                state = run_chunk(dev_mdp, state, k_hi, jnp.int32(mid))
            if mid and opts.monitor_mode == "chunk":
                _drain_monitor(mid, state, None, k)
            if save_each:
                save_state()
    finally:
        if mid:
            jax.effects_barrier()   # flush in-flight monitor callbacks
            methods.monitor_release(mid)

    with trace.span(trace.DRIVER_READBACK):
        if mesh is not None:
            # gather the sharded fields for the host-side result
            state = _get(state)
        return _result(state, opts, mdp.gamma, n_orig)


def solve_many(mdps: Sequence[MDP] | MDP, opts: IPIOptions = IPIOptions(), *,
               mesh=None, layout: str = "1d", v0s=None,
               pad_fleet: bool = True, origin: tuple[int, int] | None = None,
               checkpoint_dir: str | None = None, chunk: int = 64,
               verbose: bool = False, monitor=None) -> list[SolveResult]:
    """Solve a fleet of MDPs in one compiled batched program.

    ``mdps`` is a sequence of (unbatched) MDP instances — or an
    already-batched container from :func:`repro.core.mdp.stack_mdps`.  Every
    instance is solved to ``opts.atol`` exactly as an individual
    :func:`solve` call would (per-instance iteration counts and traces
    included — converged instances freeze under the batched active mask),
    but the whole fleet shares one device program: one ``lax.while_loop``,
    vmapped kernels, one ``shard_map`` when ``mesh`` is given.  Returns one
    :class:`SolveResult` per instance, padding trimmed.

    ``layout`` picks how the fleet maps onto ``mesh``:

    * ``"1d"`` / ``"2d"`` — the instance dim is *replicated*: every device
      owns its state (x action) slice of all B instances.  Simple, but
      per-device fleet memory grows with B.
    * ``"fleet"`` / ``"fleet2d"`` — the instance dim is *sharded* over the
      mesh's leading ``fleet`` axis (build one with
      :func:`repro.launch.mesh.make_fleet_mesh`); states (and actions, for
      ``"fleet2d"``) shard over the remaining axes within each fleet slice.
      Per-device fleet memory is ``B / fleet_size`` of the replicated
      layouts, so B scales with the mesh.  B is padded up to a multiple of
      the fleet-axis size with zero-cost dummy instances (trimmed from the
      results); ``pad_fleet=False`` turns the padding into a ``ValueError``
      for callers that need exact placement.

    ``v0s`` optionally warm-starts: a sequence of per-instance ``(n_i,)``
    vectors (zero-padded to the fleet width) or a stacked ``(B, n)`` array.

    ``checkpoint_dir`` persists the fleet state between chunks.  Checkpoints
    are saved **unsharded and unpadded** (true ``B`` and ``n``), so a fleet
    checkpoint is mesh-agnostic exactly like a single-instance one: a solve
    interrupted on an 8-way fleet axis resumes on a 4-way axis (or on a
    replicated layout, or single-device) bit-for-bit.

    ``origin=(B, n)`` names the *true* fleet size and state count of a
    pre-batched container that was built with mesh padding already applied
    (e.g. :func:`repro.api.place_function_fleet`): results and checkpoints
    are then trimmed to the true sizes — without it, a padded container's
    checkpoint meta would record the mesh-padded shapes and refuse an
    elastic resume on a differently-padding mesh.
    """
    _reject_virtual(opts)
    if isinstance(mdps, (EllMDP, DenseMDP, MatrixFreeMDP)):
        if mdps.batch is None:
            raise ValueError("solve_many() wants a fleet; for a single "
                             "instance use solve()")
        batched = mdps
        b_true, n_true = origin or (batched.batch, batched.n_global)
        if b_true > batched.batch or n_true > batched.n_global:
            raise ValueError(f"origin={origin} exceeds the container's "
                             f"(B={batched.batch}, n={batched.n_global})")
        n_origs = [n_true] * b_true
    else:
        if origin is not None:
            raise ValueError("origin= applies to a pre-batched container; "
                             "per-instance MDPs carry their own true n")
        mdps = list(mdps)
        n_origs = [m.n_global for m in mdps]
        batched = stack_mdps(mdps)
        b_true, n_true = batched.batch, batched.n_global
    b_orig = b_true
    gammas = gammas_of(batched)
    if layout in partition.FLEET_LAYOUTS and mesh is None:
        raise ValueError(f"layout={layout!r} shards the fleet dim over a "
                         "mesh; pass mesh=... (see "
                         "repro.launch.mesh.make_fleet_mesh)")
    if opts.halo:
        _validate_banded(batched, opts.halo, mesh, layout)

    v0 = None
    if v0s is not None:
        if isinstance(v0s, (list, tuple)):
            n_to = batched.n_local
            v0 = jnp.asarray(np.stack(
                [np.pad(np.asarray(x), (0, n_to - np.asarray(x).shape[0]))
                 for x in v0s]))
        else:
            v0 = jnp.asarray(v0s)

    if mesh is None:
        axes = Axes()
        dev_mdp = batched
    else:
        dev_mdp, axes, _ = partition.shard_mdp(batched, mesh, layout,
                                               pad_fleet=pad_fleet,
                                               mode=opts.mode)
        if v0 is not None:
            v0 = jnp.pad(v0, ((0, dev_mdp.batch - v0.shape[0]),
                              (0, dev_mdp.n_global - v0.shape[-1])))
    # per-instance unpadded state counts, 0 for padded dummy fleet lanes
    nt_vec = np.asarray(
        list(n_origs) + [0] * (dev_mdp.batch - len(n_origs)), np.int32)
    opts = _resolve_overlap(opts, dev_mdp, mesh, axes)
    run_chunk, init = _make_runners(dev_mdp, opts, mesh, axes,
                                    dev_mdp.batch, n_true=nt_vec)

    with trace.span(trace.DRIVER_INIT):
        state = _restore_or_init(init, v0, checkpoint_dir, verbose,
                                 expect=dict(n=n_true, batch=b_orig))
    mid = 0
    if opts.monitor:
        # trim=b_orig: monitor records carry the TRUE fleet rows, not the
        # mesh-padded dummy lanes
        mid = methods.monitor_handle(monitor or methods.print_monitor,
                                     trim=b_orig)
    try:
        if mid:
            k0, res0 = _get((state.k, state.res))
            methods.emit_host(mid, np.asarray(k0), np.asarray(res0),
                              np.zeros(dev_mdp.batch, np.int32))
        while True:
            # one host round-trip per chunk (see the solve() loop)
            with trace.span(trace.DRIVER_SYNC):
                k, res, crit, div = (np.asarray(x) for x in _get(
                    (state.k, state.res, state.done, state.diverged)))
            # isnan / diverged: a broken-down lane is not device-active ->
            # count it done (its result reports diverged, not converged)
            done = crit | (k >= opts.max_outer) | np.isnan(res) | div
            if verbose:
                n_act = int((~done).sum())
                print(f"[driver] fleet B={len(k)} active={n_act} "
                      f"k_max={int(k.max())} res_max={float(res.max()):.3e}")
            if done.all():
                break
            k_hi = jnp.int32(min(int(k[~done].min()) + chunk,
                                 opts.max_outer))
            with trace.span(trace.DRIVER_DISPATCH):
                state = run_chunk(dev_mdp, state, k_hi, jnp.int32(mid))
            if mid and opts.monitor_mode == "chunk":
                _drain_monitor(mid, state, done, k)
            if checkpoint_dir:
                trimmed = _trim_ckpt_state(state, n_true, b_orig)
                ckpt.save(checkpoint_dir,
                          int(np.max(np.asarray(trimmed.k))), trimmed,
                          meta=dict(method=opts.method, batch=b_orig,
                                    n=n_true, layout=layout))
    finally:
        if mid:
            jax.effects_barrier()   # flush in-flight monitor callbacks
            methods.monitor_release(mid)

    with trace.span(trace.DRIVER_READBACK):
        state = _get(state)
        out = []
        for b in range(b_orig):
            sb = jax.tree_util.tree_map(lambda x: np.asarray(x)[b], state)
            out.append(_result(sb, opts, gammas[b], n_origs[b]))
    return out
