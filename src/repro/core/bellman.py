"""Distributed Bellman operators.

All functions operate on a *local* MDP block plus the :class:`~repro.core.comm.Axes`
describing the mesh axes it is sharded over.  They are pure and jit/shard_map
friendly; with ``Axes()`` (no axes) they are the single-device reference.

Conventions
-----------
* ``v_local``  — (n_local,) owned slice of the value vector.
* ``v_global`` — (n_global,) gathered value vector (``axes.allgather_state``).
* ``pi``       — (n_local,) int32 of **global** action ids.

Batched fleets
--------------
:func:`backup` and :func:`residual_norm` accept a batched MDP (leading ``B``
dim, see :func:`repro.core.mdp.stack_mdps`) with correspondingly batched
value vectors and vmap themselves over the unbatched path.  The per-instance
operators additionally take ``gamma_t``, an optional *traced* scalar discount
override, passed straight through to the kernels — the dispatch layer traces
``gamma`` (it is not a compile-time constant), so a heterogeneous-gamma fleet
(e.g. a gamma sweep) shares one compiled kernel across instances and computes
``cost + gamma * P v`` with exactly the same rounding as a replicated solve.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.comm import Axes
from repro.core.mdp import (DenseMDP, EllMDP, MatrixFreeMDP, MDP,
                            batch_parts)
from repro.kernels import matrix_free, ops
from repro.utils import trace


# --------------------------------------------------------------------------- #
# Value-vector movement (all-gather vs banded halo exchange)                   #
# --------------------------------------------------------------------------- #

def gather_v(v_local: jax.Array, axes: Axes, *, halo: int = 0,
             dtype=None) -> jax.Array:
    """Produce the column window the local rows reference: the full gathered
    vector (``halo=0``) or the banded ``[start-halo, stop+halo)`` window."""
    if halo:
        return axes.halo_exchange(v_local, halo, dtype)
    return axes.allgather_state(v_local, dtype)


def _shift_idx(idx: jax.Array, mdp: MDP, axes: Axes, halo: int) -> jax.Array:
    """Global successor ids -> window-relative ids for the halo layout.

    Coordinates are clamped into the window: the auto-halo planner admits
    MDPs whose *zero-weight* ELL fill (padded rows, short rows) references
    columns far outside the band, and those entries must read a defined
    value so 0*v[i] stays exactly 0 instead of poisoning the row with an
    out-of-bounds gather."""
    if not halo:
        return idx
    row_start = axes.state_index() * mdp.n_local
    return jnp.clip(idx - row_start + halo, 0, mdp.n_local + 2 * halo - 1)


# --------------------------------------------------------------------------- #
# Greedy step (policy improvement)                                            #
# --------------------------------------------------------------------------- #

def backup(mdp: MDP, v_global: jax.Array, axes: Axes, *,
           impl: str | None = None, halo: int = 0,
           gamma_t: jax.Array | None = None,
           mode: str = "mincost") -> tuple[jax.Array, jax.Array]:
    """One Bellman backup: ``Tv`` and the greedy policy on local rows.

    ``v_global`` is whatever :func:`gather_v` produced (full vector or halo
    window — ``halo`` must match).  Returns ``(tv_local (n_local,) f32,
    pi_local (n_local,) int32 global ids)``.  With an action axis, the
    min/argmin is completed with a pmin reduction; ties break to the
    smallest global action id (deterministic across layouts).

    A batched ``mdp`` (with ``v_global`` batched ``(B, n)``) vmaps over the
    instance dim and returns ``(B, n)`` outputs.  ``gamma_t`` (traced scalar)
    overrides the static ``mdp.gamma`` — see the module docstring.

    ``mode="maxreward"`` reads ``cost`` as a *reward* and takes the argmax
    backup ``Tv = max_a (r + gamma P v)`` instead of the argmin.  It is
    implemented by negation — the backup runs on ``(-cost, -v)`` and the
    result is negated — so a maxreward solve is bit-for-bit the negation of
    the mincost solve on negated costs (IEEE negation is exact), and the
    action-axis pmin/tie-break reduction is reused unchanged.
    """
    if mdp.batch is not None:
        view, in_ax, g_t = batch_parts(mdp)
        g_t = gamma_t if gamma_t is not None else g_t
        fn = lambda m, vg, gt: backup(m, vg, axes, impl=impl, halo=halo,
                                      gamma_t=gt, mode=mode)
        return jax.vmap(fn, in_axes=(in_ax, 0, None if g_t is None else 0))(
            view, v_global, g_t)
    gamma = mdp.gamma if gamma_t is None else gamma_t
    neg = mode == "maxreward"
    if isinstance(mdp, MatrixFreeMDP):
        # rebuild row tiles from the constructors inside the backup; the
        # negation happens inside mf_backup (there is no stored cost to
        # flip), and the returned (vmin, amin) live in the same negated
        # min-space as the materialized branch below
        row0 = axes.state_index() * mdp.n_local
        idx_map = (lambda i: _shift_idx(i, mdp, axes, halo)) if halo \
            else None
        vmin, amin = matrix_free.mf_backup(
            mdp.spec, row0, mdp.n_local, mdp.acts, gamma, v_global,
            mode=mode, idx_map=idx_map, impl=impl)
        return _finish_argmin(vmin, amin, mdp, axes, neg)
    cost = -mdp.cost if neg else mdp.cost
    if neg:
        v_global = -v_global
    if isinstance(mdp, EllMDP):
        idx = _shift_idx(mdp.idx, mdp, axes, halo)
        vmin, amin = ops.ell_backup(idx, mdp.val, cost, gamma,
                                    v_global, impl=impl)
    else:
        assert halo == 0, "halo layout requires the ELL representation"
        vmin, amin = ops.dense_backup(mdp.p, cost, gamma,
                                      v_global, impl=impl)
    return _finish_argmin(vmin, amin, mdp, axes, neg)


def _finish_argmin(vmin: jax.Array, amin: jax.Array, mdp: MDP, axes: Axes,
                   neg: bool) -> tuple[jax.Array, jax.Array]:
    """Complete a per-shard (min, argmin) into the global ``(Tv, pi)``:
    lift local action ids to global ids, reduce over the action axis with a
    deterministic smallest-global-id tie-break, and undo the maxreward
    negation."""
    a_glob = amin + mdp.m_local * axes.action_index()
    if axes.action is None:
        return (-vmin if neg else vmin), a_glob
    tv = axes.pmin_action(vmin)
    # argmin across shards: owner shards (vmin == tv exactly, since pmin picks
    # one of the exact local minima) propose their id, others propose m_global.
    cand = jnp.where(vmin == tv, a_glob, jnp.int32(mdp.m_global))
    pi = axes.pmin_action(cand)
    return (-tv if neg else tv), pi


def gather_backup(mdp: MDP, v_local: jax.Array, axes: Axes, *,
                  plan: tuple[int, int] | None = None,
                  impl: str | None = None, halo: int = 0,
                  gamma_t: jax.Array | None = None,
                  mode: str = "mincost") -> tuple[jax.Array, jax.Array,
                                                  jax.Array]:
    """Gather the value window and run one Bellman backup; returns
    ``(tv, pi, window)``.

    ``plan=(f_lo, f_hi)`` (from :func:`repro.core.partition.overlap_margins`)
    switches to the communication-overlapped path
    (:func:`backup_overlapped`); ``plan=None`` is the synchronous
    gather-then-backup reference.  Both produce identical results — the
    overlapped path only re-routes which buffer each row reads from.
    """
    if plan is not None:
        return backup_overlapped(mdp, v_local, axes, plan=plan, impl=impl,
                                 halo=halo, gamma_t=gamma_t, mode=mode)
    w = gather_v(v_local, axes, halo=halo)
    tv, pi = backup(mdp, w, axes, impl=impl, halo=halo, gamma_t=gamma_t,
                    mode=mode)
    return tv, pi, w


def backup_overlapped(mdp: MDP, v_local: jax.Array, axes: Axes, *,
                      plan: tuple[int, int], impl: str | None = None,
                      halo: int = 0, gamma_t: jax.Array | None = None,
                      mode: str = "mincost") -> tuple[jax.Array, jax.Array,
                                                      jax.Array]:
    """Communication-overlapped Bellman backup; returns ``(tv, pi, window)``.

    Launches the value-window collective (:meth:`Axes.gather_start`), backs
    up the *interior* rows ``[f_lo, n_local - f_hi)`` — whose nonzero-weight
    successors are all locally owned — directly against ``v_local`` while
    the window is in flight, then finishes the frontier rows against the
    arrived window.  With async collectives enabled the scheduler moves the
    interior compute between the collective's start/done pair.

    The per-row kernels are row-independent and the interior rows read the
    same values through ``v_local`` as they would through the gathered
    window, so the result is identical to the synchronous
    ``backup(gather_v(v), ...)`` path (zero-weight ELL fill entries may
    index outside the owned range; they are clamped and contribute exactly
    0 on both paths).
    """
    if mdp.batch is not None:
        view, in_ax, g_t = batch_parts(mdp)
        g_t = gamma_t if gamma_t is not None else g_t
        fn = lambda m, vl, gt: backup_overlapped(
            m, vl, axes, plan=plan, impl=impl, halo=halo, gamma_t=gt,
            mode=mode)
        return jax.vmap(fn, in_axes=(in_ax, 0, None if g_t is None else 0))(
            view, v_local, g_t)
    if isinstance(mdp, MatrixFreeMDP):
        return _mf_backup_overlapped(mdp, v_local, axes, plan=plan,
                                     impl=impl, halo=halo, gamma_t=gamma_t,
                                     mode=mode)
    if not isinstance(mdp, EllMDP):
        raise ValueError("comm overlap requires the ELL representation; "
                         "DenseMDP rows always reference global columns")
    f_lo, f_hi = plan
    n_loc = mdp.n_local
    window = axes.gather_start(v_local, halo=halo)

    gamma = mdp.gamma if gamma_t is None else gamma_t
    neg = mode == "maxreward"
    cost = -mdp.cost if neg else mdp.cost
    v_own = -v_local if neg else v_local
    row_start = axes.state_index() * n_loc
    sl = lambda a, lo, hi: jax.lax.slice_in_dim(a, lo, hi, axis=0)

    parts = []
    # interior rows: no data dependence on the in-flight window
    if f_lo + f_hi < n_loc:
        idx_c = jnp.clip(sl(mdp.idx, f_lo, n_loc - f_hi) - row_start,
                         0, n_loc - 1)
        parts.append((f_lo, ops.ell_backup(
            idx_c, sl(mdp.val, f_lo, n_loc - f_hi),
            sl(cost, f_lo, n_loc - f_hi), gamma, v_own, impl=impl)))

    # frontier rows: wait for the window, then finish the edges.  Slice the
    # raw idx BEFORE shifting into window coordinates — shifting the full
    # tensor would materialize O(n_local * m * nnz) ints per backup for a
    # few frontier rows' worth of use.
    win = axes.gather_finish(window)
    v_win = -win if neg else win
    shift = lambda lo, hi: _shift_idx(sl(mdp.idx, lo, hi), mdp, axes, halo)
    if f_lo:
        parts.insert(0, (0, ops.ell_backup(
            shift(0, f_lo), sl(mdp.val, 0, f_lo), sl(cost, 0, f_lo),
            gamma, v_win, impl=impl)))
    if f_hi:
        parts.append((n_loc - f_hi, ops.ell_backup(
            shift(n_loc - f_hi, n_loc), sl(mdp.val, n_loc - f_hi, n_loc),
            sl(cost, n_loc - f_hi, n_loc), gamma, v_win, impl=impl)))

    parts.sort(key=lambda p: p[0])
    vmin = jnp.concatenate([p[1][0] for p in parts])
    amin = jnp.concatenate([p[1][1] for p in parts])
    tv, pi = _finish_argmin(vmin, amin, mdp, axes, neg)
    return tv, pi, win


def _mf_backup_overlapped(mdp: "MatrixFreeMDP", v_local: jax.Array,
                          axes: Axes, *, plan: tuple[int, int],
                          impl: str | None, halo: int,
                          gamma_t: jax.Array | None,
                          mode: str) -> tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """The interior/frontier split for the matrix-free operator: same
    structure as the materialized path above, but each part *rebuilds* its
    row range from the constructors instead of slicing stored tables.  The
    per-row math is unchanged, so the split is bitwise invisible exactly
    as for the materialized operator."""
    f_lo, f_hi = plan
    n_loc = mdp.n_local
    window = axes.gather_start(v_local, halo=halo)

    gamma = mdp.gamma if gamma_t is None else gamma_t
    neg = mode == "maxreward"
    row_start = axes.state_index() * n_loc
    spec, acts = mdp.spec, mdp.acts
    part = lambda lo, n_rows, idx_map, v: matrix_free.mf_backup(
        spec, row_start + lo, n_rows, acts, gamma, v, mode=mode,
        idx_map=idx_map, impl=impl)

    parts = []
    # interior rows: no data dependence on the in-flight window; their
    # nonzero successors are locally owned, so global ids shift by the
    # row offset (clamped: zero-weight fill contributes exactly 0)
    if f_lo + f_hi < n_loc:
        own_map = lambda i: jnp.clip(i - row_start, 0, n_loc - 1)
        parts.append((f_lo, part(f_lo, n_loc - f_lo - f_hi, own_map,
                                 v_local)))

    # frontier rows: wait for the window, then finish the edges against it
    win = axes.gather_finish(window)
    win_map = (lambda i: _shift_idx(i, mdp, axes, halo)) if halo else None
    if f_lo:
        parts.insert(0, (0, part(0, f_lo, win_map, win)))
    if f_hi:
        parts.append((n_loc - f_hi, part(n_loc - f_hi, f_hi, win_map, win)))

    parts.sort(key=lambda p: p[0])
    vmin = jnp.concatenate([p[1][0] for p in parts])
    amin = jnp.concatenate([p[1][1] for p in parts])
    tv, pi = _finish_argmin(vmin, amin, mdp, axes, neg)
    return tv, pi, win


def residual_norm(mdp: MDP, v_local: jax.Array, v_global: jax.Array,
                  axes: Axes, *, impl: str | None = None,
                  halo: int = 0,
                  gamma_t: jax.Array | None = None,
                  mode: str = "mincost") -> jax.Array:
    """Global sup-norm Bellman residual ``||T v - v||_inf`` (the optimality gap
    certificate: ``||v - v*||_inf <= residual / (1 - gamma)``).  Batched MDPs
    return per-instance residuals ``(B,)``."""
    tv, _ = backup(mdp, v_global, axes, impl=impl, halo=halo, gamma_t=gamma_t,
                   mode=mode)
    return axes.pmax_state(jnp.max(jnp.abs(tv - v_local), axis=-1))


# --------------------------------------------------------------------------- #
# Policy-restricted operators (policy evaluation)                             #
# --------------------------------------------------------------------------- #

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PolicyRows:
    """Rows of ``P_pi`` / ``g_pi`` owned by this shard, pre-masked.

    With a 2-D (state x action) layout each action shard owns the rows whose
    greedy action falls inside its slice; masked-out rows contribute zeros and
    the results are psum-reduced over the action axis (the beyond-paper 2-D
    layout; the paper-faithful 1-D layout has no action axis and the mask is
    all-ones).
    """

    idx: jax.Array | None   # (n_local, K) int32   (ELL)
    val: jax.Array | None   # (n_local, K) f32     (ELL, masked)
    p: jax.Array | None     # (n_local, n_global)  (dense, masked)
    g: jax.Array            # (n_local,) f32       (masked)
    gamma: float = dataclasses.field(metadata=dict(static=True))


def policy_rows(mdp: MDP, pi: jax.Array, axes: Axes) -> PolicyRows:
    """Extract the ``P_pi`` rows for a (global-id) policy ``pi``."""
    a_rel = pi - mdp.m_local * axes.action_index()
    own = (a_rel >= 0) & (a_rel < mdp.m_local)
    a_sel = jnp.clip(a_rel, 0, mdp.m_local - 1)
    if isinstance(mdp, MatrixFreeMDP):
        # rebuild row tiles and select the greedy action's slots in-tile:
        # the output is the same O(n_local * nnz) PolicyRows transient the
        # materialized selection produces, so the inner solvers (and their
        # halo/gather machinery) run on it completely unchanged
        row0 = axes.state_index() * mdp.n_local
        idx_pi, val_pi, g_pi = matrix_free.mf_policy_rows(
            mdp.spec, row0, mdp.n_local, mdp.acts, a_sel, own)
        return PolicyRows(idx=idx_pi, val=val_pi, p=None, g=g_pi,
                          gamma=mdp.gamma)
    if isinstance(mdp, EllMDP):
        take = lambda x: jnp.take_along_axis(
            x, a_sel[:, None, None], axis=1)[:, 0]
        idx_pi = take(mdp.idx)
        val_pi = take(mdp.val) * own[:, None].astype(mdp.val.dtype)
        g_pi = jnp.take_along_axis(mdp.cost, a_sel[:, None], axis=1)[:, 0]
        g_pi = g_pi * own.astype(g_pi.dtype)
        return PolicyRows(idx=idx_pi, val=val_pi, p=None, g=g_pi,
                          gamma=mdp.gamma)
    p_pi = jnp.take_along_axis(mdp.p, a_sel[:, None, None], axis=1)[:, 0]
    p_pi = p_pi * own[:, None].astype(mdp.p.dtype)
    g_pi = jnp.take_along_axis(mdp.cost, a_sel[:, None], axis=1)[:, 0]
    g_pi = g_pi * own.astype(g_pi.dtype)
    return PolicyRows(idx=None, val=None, p=p_pi, g=g_pi, gamma=mdp.gamma)


def _p_pi_matvec(rows: PolicyRows, x_eff: jax.Array, axes: Axes,
                 impl: str | None, idx_eff=None) -> jax.Array:
    """(P_pi @ x) on local rows, reduced over action shards."""
    if rows.idx is not None:
        idx = rows.idx if idx_eff is None else idx_eff
        y = ops.ell_matvec(idx, rows.val, x_eff, impl=impl)
    else:
        dt = jnp.result_type(jnp.float32, rows.p.dtype, x_eff.dtype)
        with trace.scope(trace.SPMV):
            y = jnp.dot(rows.p.astype(dt), x_eff.astype(dt),
                        precision=jax.lax.Precision.HIGHEST)
    return axes.psum_action(y)


def _rows_idx_eff(rows: PolicyRows, mdp: MDP, axes: Axes, halo: int):
    if not halo or rows.idx is None:
        return None
    row_start = axes.state_index() * mdp.n_local
    # clamp like _shift_idx: zero-weight fill may reference far columns
    return jnp.clip(rows.idx - row_start + halo,
                    0, mdp.n_local + 2 * halo - 1)


def t_pi(rows: PolicyRows, x_local: jax.Array, axes: Axes, *,
         impl: str | None = None, mdp: MDP | None = None, halo: int = 0,
         gather_dtype=None, gamma_t: jax.Array | None = None) -> jax.Array:
    """Policy-restricted Bellman operator ``T_pi x = g_pi + gamma P_pi x``."""
    x_eff = gather_v(x_local, axes, halo=halo, dtype=gather_dtype)
    gamma = rows.gamma if gamma_t is None else gamma_t
    y = _p_pi_matvec(rows, x_eff, axes, impl,
                     _rows_idx_eff(rows, mdp, axes, halo))
    return axes.psum_action(rows.g) + gamma * y


def a_pi_matvec(rows: PolicyRows, x_local: jax.Array, axes: Axes, *,
                impl: str | None = None, mdp: MDP | None = None,
                halo: int = 0, gather_dtype=None,
                gamma_t: jax.Array | None = None) -> jax.Array:
    """Policy-evaluation system operator ``A_pi x = (I - gamma P_pi) x``.

    This is the matvec handed to the inner (Krylov) solvers; the value
    function of ``pi`` solves ``A_pi v = g_pi``.  ``gather_dtype`` turns on
    the compressed (inexact) gather — safe here because the forcing term of
    the outer iPI loop bounds the tolerable inner-system perturbation.
    """
    x_eff = gather_v(x_local, axes, halo=halo, dtype=gather_dtype)
    gamma = rows.gamma if gamma_t is None else gamma_t
    y = _p_pi_matvec(rows, x_eff, axes, impl,
                     _rows_idx_eff(rows, mdp, axes, halo))
    return x_local - gamma * y.astype(x_local.dtype)


def b_pi(rows: PolicyRows, axes: Axes) -> jax.Array:
    """Right-hand side ``g_pi`` of the policy-evaluation system."""
    return axes.psum_action(rows.g)
