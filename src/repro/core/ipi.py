"""Inexact policy iteration (iPI) — the paper's core algorithm.

Implements the outer loop of Gargiani et al. 2024, Algorithm 3, with the
inner policy-evaluation solve delegated to a selectable inner solver drawn
from the LIVE method/KSP registries (:mod:`repro.core.methods` — the
PETSc-KSP analogue; user solvers registered via
:func:`repro.api.register_ksp` dispatch through the same path).  The
builtin zoo maps onto one code path:

  ``vi``             value iteration          (inner = 0 Richardson sweeps)
  ``mpi``            modified policy iter.    (inner = fixed Richardson sweeps)
  ``ipi_richardson`` iPI + Richardson         (forcing-term stopping)
  ``ipi_gmres``      iPI + restarted GMRES    (the iGMRES-PI of the paper)
  ``ipi_bicgstab``   iPI + BiCGStab
  ``pi``             (near-)exact policy iteration (GMRES, tight tol)
  ``ipi_chebyshev``  iPI + Chebyshev semi-iteration (collective-free inner)
  ``ipi_anderson``   iPI + Anderson-accelerated VI

The outer stopping rule is equally pluggable (``opts.stop_criterion`` ->
the stop-criterion registry): ``atol`` (sup-norm residual), ``rtol``
(relative), ``span`` (span seminorm — certifies long-mixing VI far
earlier), or user-registered traced predicates; the chosen predicate
compiles into the ``lax.while_loop`` condition.  ``opts.monitor`` streams
one record per outer iteration out of the compiled loop via
``jax.debug.callback`` (fleet layouts gather per-instance rows and emit
exactly one host record via lead-shard gating).

Every outer iteration does exactly one Bellman backup (greedy step + residual)
and one inexact solve of ``(I - gamma P_pi) v = g_pi`` warm-started at
``T v_k``; with 0 inner iterations the update *is* ``T v_k`` so VI falls out
as the degenerate case.  A monotone safeguard (cheap, one extra backup on the
rare rejection path) falls back to the VI step whenever an inexact Krylov
step fails to reduce the sup-norm Bellman residual, which preserves global
convergence for any forcing factor.

The whole loop is device-side ``lax`` control flow; the host driver
(:mod:`repro.core.driver`) runs it in bounded *chunks* for checkpointing /
preemption tolerance.

Batched fleets
--------------
Every entry point accepts a *batched* MDP (leading ``B`` dim — see
:func:`repro.core.mdp.stack_mdps`): :func:`init_state` then returns a
batched :class:`SolveState` (per-instance residuals, iteration counters and
traces) and :func:`solve_chunk` runs ONE ``lax.while_loop`` for the whole
fleet, vmapping :func:`outer_step` over instances.  A per-instance *active
mask* (``res > atol`` and ``k < k_hi``) freezes converged instances: their
state fields stop updating, so per-instance ``k`` / ``inner_total`` / traces
are exactly what B independent solves would have produced, while the shared
loop keeps running on the instances still converging.  Homogeneous-gamma
fleets run the bit-identical static-gamma arithmetic of the unbatched path;
heterogeneous gammas thread a traced per-instance ``gamma_t`` through
:mod:`repro.core.bellman` (exact algebra, fp-level rounding differences).

Fleet-sharded layouts (``axes.fleet`` set) place only ``B / fleet_size``
instances on each shard.  Instances are independent, so the body needs no
new collectives — but the ``while_loop`` condition all-reduces the active
mask over the fleet axis (:meth:`Axes.any_fleet`) so every shard runs the
same iteration count: a shard whose lanes have all converged spins frozen
no-op iterations (the active mask keeps its state fixed) until the slowest
shard finishes, instead of desynchronizing the loop.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bellman, methods, solvers
from repro.core.comm import Axes
from repro.core.mdp import MDP, batch_parts
from repro.utils import trace

# Back-compat view of the builtin method zoo.  The zoo itself is a LIVE
# registry (repro.core.methods / repro.api.register_ksp): user-registered
# methods are equally valid IPIOptions.method values but do not appear here.
METHODS = tuple(methods.method_names(builtin_only=True))
MODES = ("mincost", "maxreward")


@dataclasses.dataclass(frozen=True)
class IPIOptions:
    """Static solver options (hashable -> usable as a jit static arg)."""

    method: str = "ipi_gmres"   # any name in the live method registry
                                # (repro.core.methods / api.register_method)
    mode: str = "mincost"       # "mincost" (argmin backup) | "maxreward"
                                # (argmax backup; cost is read as reward)
    atol: float = 1e-8          # stop when ||T v - v||_inf <= atol
    stop_criterion: str = "atol"  # outer stopping predicate compiled into
                                # the loop: atol | rtol | span | any name
                                # registered via api.register_stop_criterion
    rtol: float = 1e-4          # threshold for stop_criterion="rtol"
                                # (relative to the initial residual)
    max_outer: int = 500
    max_inner: int = 500        # inner-iteration cap per outer step
    forcing_eta: float = 0.05   # inner tol = eta * ||T v - v||_inf
    restart: int = 32           # GMRES restart length
    omega: float = 1.0          # Richardson damping
    mpi_sweeps: int = 50        # L for modified policy iteration
    anderson_window: int = 5    # AA depth for the anderson inner solver
    safeguard: bool = True      # monotone (VI-fallback) safeguard
    monitor: bool = False       # stream per-outer-iteration records out of
                                # the compiled loop (jax.debug.callback)
    deterministic_dots: bool = False  # pin the GMRES projection accumulation
                                # order (lane-at-a-time lax.map) so
                                # fleet-sharded Krylov values are bit-equal
                                # to the replicated layout
    impl: str | None = None     # kernel implementation override
    dtype: str = "float32"      # value-vector dtype; "float64" == PETSc default
                                # (requires jax_enable_x64)
    halo: int = 0               # banded layout: exchange only +-halo boundary
                                # entries instead of all-gathering v
    gather_dtype: str | None = None  # compressed (inexact) gather for INNER
                                # matvecs only; outer backups stay exact
    comm_overlap: str = "auto"  # overlap the backup's value-window movement
                                # with interior-row compute: "on" whenever an
                                # interior core exists, "auto" only when it
                                # covers >= half the local rows, "off" never
    async_sweeps: int = 1       # async_vi: local Bellman sweeps per value
                                # exchange (1 == synchronous vi)
    monitor_mode: str = "stream"  # "stream": one jax.debug.callback per
                                # outer iteration; "chunk": reconstruct the
                                # identical records host-side from the
                                # device traces once per run-chunk (no
                                # per-iteration host sync)
    overlap_plan: tuple | None = None  # resolved (f_lo, f_hi) frontier
                                # margins (driver-set from
                                # partition.overlap_margins; not a user
                                # option — compiled programs key on it)
    pc_type: str = "none"       # Krylov inner-solve preconditioner:
                                # none | jacobi (diag of I - gamma P_pi) |
                                # bjacobi (shard-local pc_block tiles)
    pc_block: int = 32          # bjacobi tile size
    divtol: float = 1e4         # declare divergence when the Bellman
                                # residual exceeds divtol * (initial
                                # residual) or goes NaN; the solve stops
                                # with SolveState.diverged set (the
                                # adaptive supervisor's hot-swap trigger)

    def __post_init__(self):
        # Raised (not assert'd): option validation must survive `python -O`.
        # Method / stop-criterion names validate against the LIVE registries
        # (user-registered solvers are first-class); error messages carry
        # close-spelling suggestions drawn from whatever is registered now.
        err = methods.check_method(self.method)
        if err:
            raise ValueError(err)
        err = methods.check_stop(self.stop_criterion)
        if err:
            raise ValueError(err)
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; "
                             f"pick one of {MODES}")
        if self.dtype not in ("float32", "float64"):
            raise ValueError(f"dtype must be 'float32' or 'float64' (PETSc "
                             f"default), got {self.dtype!r}")
        if not self.atol > 0:
            raise ValueError(f"atol must be > 0, got {self.atol}")
        if not 0.0 < self.rtol < 1.0:
            raise ValueError(f"rtol must lie in (0, 1), got {self.rtol}")
        if self.max_outer < 1:
            raise ValueError(f"max_outer must be >= 1, got {self.max_outer}")
        if self.max_inner < 0:
            raise ValueError(f"max_inner must be >= 0, got {self.max_inner}")
        if not 0.0 < self.forcing_eta < 1.0:
            raise ValueError(f"forcing_eta must lie in (0, 1) for iPI "
                             f"convergence, got {self.forcing_eta}")
        spec = methods.get_method(self.method)
        if self.deterministic_dots and spec.ksp is not None \
                and not methods.get_ksp(spec.ksp).deterministic:
            raise ValueError(
                f"deterministic_dots pins batch-invariant accumulation "
                f"orders, which ksp {spec.ksp!r} (method {self.method!r}) "
                f"does not implement — its dots would still re-associate "
                f"by lane count; use a deterministic ksp (e.g. "
                f"gmres/richardson/chebyshev) or drop the flag")
        if self.pc_type not in ("none", "jacobi", "bjacobi"):
            raise ValueError(f"pc_type must be 'none', 'jacobi' or "
                             f"'bjacobi', got {self.pc_type!r}")
        if self.pc_type != "none" and not spec.virtual:
            if spec.ksp is None:
                raise ValueError(
                    f"pc_type {self.pc_type!r} preconditions the Krylov "
                    f"inner solve, but method {self.method!r} has no inner "
                    f"KSP; pick an ipi_* method (or -method auto) or drop "
                    f"-pc_type")
            if not methods.get_ksp(spec.ksp).preconditioned:
                raise ValueError(
                    f"ksp {spec.ksp!r} (method {self.method!r}) does not "
                    f"accept a preconditioner; register it with "
                    f"preconditioned=True (and a `precond` keyword) or use "
                    f"gmres/bicgstab")
            if self.pc_type == "bjacobi" and self.deterministic_dots:
                raise ValueError(
                    "pc_type 'bjacobi' applies batched tile inverses whose "
                    "accumulation order is not lane-count-pinned; under "
                    "deterministic_dots use pc_type 'jacobi' (elementwise) "
                    "or drop the flag")
        if self.pc_block < 1:
            raise ValueError(f"pc_block must be >= 1, got {self.pc_block}")
        if not self.divtol > 1.0:
            raise ValueError(f"divtol must be > 1 (residual growth factor "
                             f"declaring divergence), got {self.divtol}")
        if self.restart < 1:
            raise ValueError(f"restart must be >= 1, got {self.restart}")
        if self.mpi_sweeps < 1:
            raise ValueError(f"mpi_sweeps must be >= 1, got {self.mpi_sweeps}")
        if self.anderson_window < 1:
            raise ValueError(f"anderson_window must be >= 1, "
                             f"got {self.anderson_window}")
        if not isinstance(self.halo, int) or self.halo < 0:
            raise ValueError(f"halo must be a non-negative int (0 disables "
                             f"the banded layout), got {self.halo!r}")
        if self.comm_overlap not in ("auto", "on", "off"):
            raise ValueError(f"comm_overlap must be 'auto', 'on' or 'off', "
                             f"got {self.comm_overlap!r}")
        if not isinstance(self.async_sweeps, int) or self.async_sweeps < 1:
            raise ValueError(f"async_sweeps must be an int >= 1 (1 == "
                             f"synchronous vi), got {self.async_sweeps!r}")
        if self.monitor_mode not in ("stream", "chunk"):
            raise ValueError(f"monitor_mode must be 'stream' or 'chunk', "
                             f"got {self.monitor_mode!r}")
        if self.overlap_plan is not None and (
                not isinstance(self.overlap_plan, tuple)
                or len(self.overlap_plan) != 2
                or not all(isinstance(x, int) and x >= 0
                           for x in self.overlap_plan)):
            raise ValueError(f"overlap_plan is driver-internal: None or a "
                             f"(f_lo, f_hi) tuple of ints >= 0, got "
                             f"{self.overlap_plan!r}")
        if self.gather_dtype is not None:
            try:
                gd = jnp.dtype(self.gather_dtype)
            except TypeError as e:
                raise ValueError(f"gather_dtype {self.gather_dtype!r} is not "
                                 f"a dtype: {e}") from None
            if not jnp.issubdtype(gd, jnp.floating):
                raise ValueError(f"gather_dtype must be a floating dtype "
                                 f"(wire format for v), got {gd}")
            if gd.itemsize > jnp.dtype(self.dtype).itemsize:
                raise ValueError(
                    f"gather_dtype {gd} is wider than the value dtype "
                    f"{self.dtype}: the compressed gather would silently "
                    f"upcast the wire format; drop gather_dtype or widen "
                    f"dtype")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SolveState:
    """Device-side solver state (a pytree; checkpointable).

    Batched fleet: every field gains a leading ``B`` dim (``res`` / ``k`` /
    ``inner_total`` become per-instance ``(B,)`` vectors — the ``res > atol``
    mask is the fleet's per-instance active mask)."""

    v: jax.Array            # (n_local,) current value iterate
    tv: jax.Array           # (n_local,) T v (one backup ahead)
    pi: jax.Array           # (n_local,) int32 greedy policy (global ids)
    res: jax.Array          # scalar f32, ||T v - v||_inf (replicated)
    k: jax.Array            # scalar int32, outer iterations done
    inner_total: jax.Array  # scalar int32, cumulative inner iterations
    trace_res: jax.Array    # (max_outer + 1,) f32, residual after k outers
    trace_inner: jax.Array  # (max_outer,) int32, inner iters per outer
    res0: jax.Array         # scalar, residual at k=0 (rtol baseline)
    span: jax.Array         # scalar, sp(T v - v) over the TRUE states (inf
                            # unless the stop criterion declared needs_span)
    done: jax.Array         # scalar bool, stop criterion satisfied
    diverged: jax.Array     # scalar bool (sticky): residual went NaN or
                            # exceeded divtol * res0 — the loop stops and
                            # the flag surfaces through SolveResult /
                            # monitor records / run stats
    n_true: jax.Array       # scalar int32, unpadded state count: mesh-pad
                            # rows are absorbing zero-cost states whose 0
                            # residual must not enter the span min
    win: jax.Array          # last exchanged value window (async methods:
                            # invariant win == gather_v(v) at outer-step
                            # boundaries); empty (0,) for synchronous
                            # methods.  Checkpointed as empty and restored
                            # as zeros — the k=0 iterate, a valid (stale)
                            # async restart window.


def _local_gamma_t(gamma_t: jax.Array | None, batch: int,
                   axes: Axes) -> jax.Array | None:
    """This shard's block of the global per-instance discount vector.

    Under a fleet-sharded layout the device-local batched MDP view carries
    ``B_local = B / fleet_size`` instances, but ``gamma`` is static global
    metadata (a length-``B`` tuple), so the traced ``(B,)`` vector
    :func:`repro.core.mdp.batch_parts` builds from it must be sliced to the
    lanes this fleet shard owns.
    """
    if gamma_t is None or gamma_t.shape[0] == batch:
        return gamma_t
    return jax.lax.dynamic_slice_in_dim(
        gamma_t, axes.fleet_index() * batch, batch)


def init_state(mdp: MDP, axes: Axes, opts: IPIOptions,
               v0: jax.Array | None = None, *,
               gamma_t: jax.Array | None = None,
               n_true=None) -> SolveState:
    if mdp.batch is not None:
        view, in_ax, g_t = batch_parts(mdp)
        g_t = gamma_t if gamma_t is not None else g_t
        g_t = _local_gamma_t(g_t, mdp.batch, axes)
        nt = None if n_true is None else _local_gamma_t(
            jnp.asarray(n_true, jnp.int32), mdp.batch, axes)
        fn = lambda m, v, gt, t: init_state(m, axes, opts, v, gamma_t=gt,
                                            n_true=t)
        return jax.vmap(fn, in_axes=(in_ax, None if v0 is None else 0,
                                     None if g_t is None else 0,
                                     None if nt is None else 0))(view, v0,
                                                                 g_t, nt)
    dt = jnp.dtype(opts.dtype)
    nt = jnp.int32(mdp.n_global if n_true is None else n_true)
    v = jnp.zeros((mdp.n_local,), dt) if v0 is None else v0.astype(dt)
    tv, pi, v_g = bellman.gather_backup(mdp, v, axes,
                                        plan=opts.overlap_plan,
                                        impl=opts.impl, halo=opts.halo,
                                        gamma_t=gamma_t, mode=opts.mode)
    tv = tv.astype(dt)
    res = axes.pmax_state(jnp.max(jnp.abs(tv - v)))
    span = _span_of(tv - v, axes, opts, nt)
    g = gamma_t if gamma_t is not None else mdp.gamma
    done = methods.stop_done(opts, res=res, span=span, res0=res,
                             k=jnp.int32(0), gamma=g)
    trace_res = jnp.full((opts.max_outer + 1,), jnp.nan, dt)
    win = v_g.astype(dt) \
        if methods.get_method(opts.method).outer is not None \
        else jnp.zeros((0,), dt)
    return SolveState(
        v=v, tv=tv, pi=pi, res=res, k=jnp.int32(0),
        inner_total=jnp.int32(0),
        trace_res=trace_res.at[0].set(res),
        trace_inner=jnp.full((opts.max_outer,), -1, jnp.int32),
        res0=res, span=span, done=done, diverged=jnp.isnan(res),
        n_true=nt, win=win)


@partial(jax.jit, static_argnames=("opts", "axes"))
def init_state_jit(mdp: MDP, v0: jax.Array | None = None,
                   gamma_t: jax.Array | None = None, n_true=None, *,
                   opts: IPIOptions = None,
                   axes: Axes = None) -> SolveState:
    """Compiled :func:`init_state` for the single-device path: the vmapped
    eager init re-traces its op graph on every call, which dominates warm
    repeated solves (a serving fleet, bench reps).  The mesh path already
    wraps its init in jit+shard_map, so jitting here keeps both paths'
    numerics aligned."""
    return init_state(mdp, axes, opts, v0, gamma_t=gamma_t, n_true=n_true)


def _span_of(d: jax.Array, axes: Axes, opts: IPIOptions,
             n_true: jax.Array) -> jax.Array:
    """Span seminorm ``sp(d) = max(d) - min(d)`` over the TRUE states —
    computed (one extra pmax pair) only when the selected stop criterion
    declared ``needs_span``; otherwise a free +inf constant so the
    monitor-disabled hot path stays untouched.

    Mesh padding appends absorbing zero-cost states whose residual is
    exactly 0; left in the min they would pin ``sp(d)`` near ``max(d)``
    and silently erase the early-certification benefit on padded layouts
    (and break replicated-vs-sharded equality for non-divisible ``n``), so
    rows at global index >= ``n_true`` are masked to -inf on both sides.
    A shard that is entirely padding contributes -inf, which the cross-
    shard pmax discards; an all-padding dummy fleet lane yields span
    -inf (trivially "converged", matching its frozen res = 0)."""
    if not methods.get_stop(opts.stop_criterion).needs_span:
        return jnp.asarray(jnp.inf, d.dtype)
    rows = axes.state_index() * d.shape[0] + jnp.arange(d.shape[0])
    ninf = jnp.asarray(-jnp.inf, d.dtype)
    valid = rows < n_true
    dmax = axes.pmax_state(jnp.max(jnp.where(valid, d, ninf)))
    dmin = -axes.pmax_state(jnp.max(jnp.where(valid, -d, ninf)))
    return dmax - dmin


@trace.scoped(trace.OUTER)
def _outer_core(mdp: MDP, state: SolveState, opts: IPIOptions,
                axes: Axes, gamma_t: jax.Array | None):
    """One outer iteration minus the k/trace bookkeeping.

    Returns ``(v1, tv1, pi1, res1, span1, inner_iters, win1)`` — shared by
    the unbatched :func:`outer_step` and the batched body of
    :func:`solve_chunk` (which does its bookkeeping fleet-wide, outside the
    vmap).  Methods with a custom ``outer`` (e.g. ``async_vi``) replace the
    inner-solve/backup core entirely; everyone else dispatches the inner
    policy-evaluation solve through the live KSP/method registry
    (:func:`repro.core.methods.inner_solve`).
    """
    spec = methods.get_method(opts.method)
    if spec.outer is not None:
        v1, tv1, pi1, res1, inner_iters, win1 = spec.outer(
            mdp, state, opts, axes, gamma_t)
        span1 = _span_of(tv1 - v1, axes, opts, state.n_true)
        return v1, tv1, pi1, res1, span1, inner_iters, win1
    rows = bellman.policy_rows(mdp, state.pi, axes)
    b = bellman.b_pi(rows, axes).astype(state.tv.dtype)
    gd = None if opts.gather_dtype is None else jnp.dtype(opts.gather_dtype)
    matvec = lambda x: bellman.a_pi_matvec(rows, x, axes, impl=opts.impl,
                                           mdp=mdp, halo=opts.halo,
                                           gather_dtype=gd, gamma_t=gamma_t)
    tol = jnp.maximum(opts.forcing_eta * state.res, jnp.float32(1e-30))
    gamma = gamma_t if gamma_t is not None else mdp.gamma
    precond = None
    if opts.pc_type != "none" and spec.ksp is not None:
        # rebuilt per outer iteration from the policy-rows transient the
        # matvec already needs — matrix-free MDPs pay no extra memory
        precond = solvers.build_precond(
            rows, axes=axes, n_local=mdp.n_local, gamma=gamma,
            pc_type=opts.pc_type, block=opts.pc_block,
            dtype=state.tv.dtype)
    v1, inner_iters, _ = methods.inner_solve(
        opts, matvec, b, state.tv, tol, axes, context=dict(gamma=gamma),
        precond=precond)

    def eval_at(v):
        # exact gather; opts.overlap_plan switches in the communication-
        # overlapped (result-identical) backup path
        tv, pi, _ = bellman.gather_backup(mdp, v, axes,
                                          plan=opts.overlap_plan,
                                          impl=opts.impl, halo=opts.halo,
                                          gamma_t=gamma_t, mode=opts.mode)
        res = axes.pmax_state(jnp.max(jnp.abs(tv - v)))
        return v, tv, pi, res

    cand = eval_at(v1)
    if opts.safeguard and spec.safeguarded and spec.ksp is not None:
        # Krylov-type steps are not contractions; reject any step that
        # increases the Bellman residual and take the (guaranteed) VI step
        # instead.  ``res`` is replicated across devices -> no control-flow
        # divergence.
        cand = jax.lax.cond(cand[3] <= state.res,
                            lambda: cand, lambda: eval_at(state.tv))
    v1, tv1, pi1, res1 = cand
    span1 = _span_of(tv1 - v1, axes, opts, state.n_true)
    return v1, tv1, pi1, res1, span1, inner_iters, state.win


def outer_step(mdp: MDP, state: SolveState, opts: IPIOptions,
               axes: Axes, *, gamma_t: jax.Array | None = None) -> SolveState:
    """One outer iPI iteration (greedy policy is already in ``state``)."""
    v1, tv1, pi1, res1, span1, inner_iters, win1 = _outer_core(
        mdp, state, opts, axes, gamma_t)
    k1 = state.k + 1
    g = gamma_t if gamma_t is not None else mdp.gamma
    done = methods.stop_done(opts, res=res1, span=span1, res0=state.res0,
                             k=k1, gamma=g)
    div1 = state.diverged | jnp.isnan(res1) | \
        (res1 > opts.divtol * jnp.maximum(state.res0, 1e-30))
    return SolveState(
        v=v1, tv=tv1, pi=pi1, res=res1, k=k1,
        inner_total=state.inner_total + inner_iters,
        trace_res=state.trace_res.at[k1].set(res1),
        trace_inner=state.trace_inner.at[state.k].set(inner_iters),
        res0=state.res0, span=span1, done=done, diverged=div1,
        n_true=state.n_true, win=win1)


def _lead_flag(axes: Axes) -> jax.Array:
    """True on exactly one mesh shard — the monitor callback fires on every
    device, so only the lead shard's (replicated) record is kept."""
    return (axes.state_index() == 0) & (axes.action_index() == 0) & \
        (axes.fleet_index() == 0)


@partial(jax.jit, static_argnames=("opts", "axes"))
def solve_chunk(mdp: MDP, state: SolveState, k_hi: jax.Array,
                mon_id: jax.Array = 0, opts: IPIOptions = None,
                axes: Axes = None) -> SolveState:
    """Run outer iterations until convergence or ``k == k_hi`` (device-side).

    With a batched ``mdp`` + batched ``state`` this is ONE while loop for the
    whole fleet: it spins while any instance is active and every iteration
    vmaps the outer-step core over instances, freezing the converged ones
    (their fields — including per-instance ``k`` / ``inner_total`` / traces —
    stop updating, so results match B independent solves).

    The fleet bookkeeping exploits a *lockstep invariant*: every state starts
    at ``k = 0`` and ``k`` only advances while a lane is active, so all
    active lanes always share one outer index.  Trace updates are therefore a
    single shared-column ``dynamic_update_slice`` instead of B per-lane
    scatters (much lighter to compile and run on every loop iteration).
    """
    if mdp.batch is None:
        def cond(s: SolveState):
            return (~s.done) & ~jnp.isnan(s.res) & (~s.diverged) & \
                (s.k < k_hi)

        def body(s: SolveState) -> SolveState:
            s1 = outer_step(mdp, s, opts, axes)
            if opts.monitor and opts.monitor_mode == "stream":
                methods.emit_monitor(mon_id, _lead_flag(axes), s1.k, s1.res,
                                     s1.inner_total - s.inner_total,
                                     s1.diverged)
            return s1

        return jax.lax.while_loop(cond, body, state)

    view, in_ax, gamma_t = batch_parts(mdp)
    gamma_t = _local_gamma_t(gamma_t, mdp.batch, axes)
    if gamma_t is not None:
        # pin the traced per-lane discounts to the solve dtype: under
        # jax_enable_x64 the vector defaults to float64 and every gamma*Pv
        # product would promote, breaking the float32 while-loop carry
        gamma_t = gamma_t.astype(jnp.dtype(opts.dtype))
    core = jax.vmap(
        lambda m, s, gt: _outer_core(m, s, opts, axes, gt),
        in_axes=(in_ax, 0, None if gamma_t is None else 0))

    def active(s: SolveState) -> jax.Array:
        return (~s.done) & ~jnp.isnan(s.res) & (~s.diverged) & (s.k < k_hi)

    def body(s: SolveState) -> SolveState:
        act = active(s)
        v1, tv1, pi1, res1, span1, inner, win1 = core(view, s, gamma_t)
        sel = lambda n, o: jnp.where(act[:, None] if n.ndim > 1 else act,
                                     n, o)
        k1 = s.k + act.astype(jnp.int32)
        g = gamma_t if gamma_t is not None else mdp.gamma
        done1 = methods.stop_done(opts, res=res1, span=span1, res0=s.res0,
                                  k=k1, gamma=g)
        div1 = s.diverged | (act & (jnp.isnan(res1) | (
            res1 > opts.divtol * jnp.maximum(s.res0, 1e-30))))
        # Lockstep: all active lanes write outer index k_col; frozen lanes
        # keep their old column value.
        k_col = jnp.max(jnp.where(act, k1, 0))
        res_col = jnp.where(act, res1, s.trace_res[:, k_col])
        inner_col = jnp.where(act, inner, s.trace_inner[:, k_col - 1])
        s1 = SolveState(
            v=sel(v1, s.v), tv=sel(tv1, s.tv), pi=sel(pi1, s.pi),
            res=sel(res1, s.res), k=k1,
            inner_total=s.inner_total + jnp.where(act, inner, 0),
            trace_res=jax.lax.dynamic_update_slice(
                s.trace_res, res_col[:, None], (jnp.int32(0), k_col)),
            trace_inner=jax.lax.dynamic_update_slice(
                s.trace_inner, inner_col[:, None], (jnp.int32(0),
                                                    k_col - 1)),
            res0=s.res0, span=sel(span1, s.span),
            done=jnp.where(act, done1, s.done), diverged=div1,
            n_true=s.n_true, win=sel(win1, s.win))
        if opts.monitor and opts.monitor_mode == "stream":
            # One fleet-wide record per outer iteration: gather the
            # per-instance rows over the fleet axis (every shard runs the
            # collective; only the lead shard's callback is kept).
            methods.emit_monitor(
                mon_id, _lead_flag(axes),
                axes.pmax_fleet(k_col), axes.allgather_fleet(s1.res),
                axes.allgather_fleet(jnp.where(act, inner, 0)),
                axes.allgather_fleet(s1.diverged))
        return s1

    # The loop condition is all-reduced over the fleet axis: every fleet
    # shard runs the same trip count (a shard whose lanes all converged
    # spins no-op iterations — `sel` keeps its state frozen), so collectives
    # may safely be added to the body later without desynchronizing SPMD
    # shards.  Identity when axes.fleet is None (replicated layouts).
    return jax.lax.while_loop(
        lambda s: axes.any_fleet(jnp.any(active(s))), body, state)
