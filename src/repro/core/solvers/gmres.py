"""Restarted GMRES with CGS2 orthogonalization and Givens rotations.

This is the inner solver behind madupite's iGMRES-PI method (Gargiani et al.
2023): for stiff / weakly-diagonally-dominant ``I - gamma P_pi`` (gamma -> 1,
long mixing chains) Krylov acceleration beats Richardson sweeps by orders of
magnitude in iteration count.

Distribution notes (the PETSc-KSP -> JAX adaptation):
  * basis vectors are state-sharded rows; every inner product is a
    ``psum`` over the state axis (``axes.dot``);
  * orthogonalization is classical Gram-Schmidt with one re-orthogonalization
    pass (CGS2).  Unlike MGS, CGS2 needs only two ``(j, n_local) @ (n_local,)``
    matmuls per Arnoldi step -> two collectives instead of ``j`` of them, and
    the matmuls batch nicely on the MXU.  CGS2 is as stable as MGS in
    practice (Giraud et al. 2005).
  * the (restart+1, restart) Hessenberg solve is replicated on every device
    (it is tiny), exactly like PETSc replicates it on every rank.

Stopping is on the 2-norm residual estimate maintained by the Givens
rotations; since ``||r||_inf <= ||r||_2`` this is conservative for the
sup-norm forcing condition used by iPI.

Deterministic mode (``deterministic=True``) pins the floating-point
*accumulation order* of every projection and combination so the computed
values are independent of how many fleet lanes share a device: the batched
``V @ w`` matmuls XLA emits under ``vmap`` are free to tile (and therefore
associate) their contractions by the device-local lane count, which is
exactly the cross-layout reproducibility hazard CGS2 analyses warn about
(Giraud et al. 2005 — the *values* are equally accurate, just not
bit-equal).  In deterministic mode each projection is a lane-at-a-time
``lax.map`` of fixed-shape reductions, basis combinations are ordered AXPY
loops, and the Hessenberg solve is an explicit back-substitution — no
dot-general anywhere XLA could re-tile by batch width — so a fleet-sharded
solve is bit-identical to the replicated layout *at equal state-shard
count*.  (Across different state-shard counts the distributed sum is split
at different boundaries; no fixed elementwise order makes that invariant —
the same caveat as MPI_Allreduce reproducibility being per-communicator
in PETSc.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.comm import Axes
from repro.utils import trace

_TINY = 1e-30


def _det_dot(axes: Axes, x, y):
    """<x, y> with a batch-invariant accumulation: elementwise multiply +
    single-axis reduce (never a dot-general XLA may re-tile per vmap
    width), then one psum over the state shards."""
    return axes.psum_state(jnp.sum(x * y))


def _det_norm2(axes: Axes, x):
    return jnp.sqrt(jnp.maximum(_det_dot(axes, x, x), 0.0))


def _norm2(axes: Axes, deterministic: bool):
    return (lambda v: _det_norm2(axes, v)) if deterministic else axes.norm2


def _det_projections(axes: Axes, V, w):
    """The CGS2 projection vector ``V @ w`` computed one basis lane at a
    time (``lax.map``): every inner product is the same fixed-shape
    reduction regardless of how many fleet instances are vmapped onto this
    device, so the accumulation order — and hence the bits — match between
    the replicated and fleet-sharded layouts."""
    return axes.psum_state(jax.lax.map(lambda vj: jnp.sum(vj * w), V))


def _det_combine(h, V):
    """``h @ V`` as an ordered AXPY loop (fixed j-order accumulation)."""
    return jax.lax.fori_loop(
        0, V.shape[0], lambda j, acc: acc + h[j] * V[j],
        jnp.zeros_like(V[0]))


def _det_backsolve(R, g):
    """Upper-triangular solve by explicit back-substitution (fixed
    accumulation order; replaces the batched ``solve_triangular``)."""
    n = R.shape[0]

    def step(i, y):
        j = n - 1 - i
        # y[k] == 0 for k <= j (not yet assigned), so the full-row reduce
        # only picks up the k > j terms back-substitution needs.
        return y.at[j].set((g[j] - jnp.sum(R[j] * y)) / R[j, j])

    return jax.lax.fori_loop(0, n, step, jnp.zeros_like(g))


def _residual(matvec, b, x, norm2):
    """``r = b - A x`` and ``||r||_2``."""
    with trace.scope(trace.GMRES_RESIDUAL):
        r = b - matvec(x)
        return r, norm2(r)


@trace.scoped(trace.GMRES_CYCLE)
def _arnoldi_cycle(matvec, b, x, r=None, beta=None, run=True, *,
                   restart: int, tol, axes: Axes, deterministic: bool = False,
                   precond=None):
    """One restart cycle from ``x``.  Returns (x_new, resnorm, iters_done).

    ``r`` and ``beta`` are ``x``'s residual ``b - A x`` and its 2-norm where
    the caller has them (the first cycle takes ``gmres``'s ``r0``);
    otherwise the cycle measures them.  The Arnoldi loop stops at
    convergence: steps after the residual estimate meets ``tol`` are not
    executed, and none is where ``run`` is false.
    """
    n_local = x.shape[0]
    dt = x.dtype
    M = precond if precond is not None else (lambda v: v)
    norm2 = _norm2(axes, deterministic)
    if r is None:
        r, beta = _residual(matvec, b, x, norm2)
    v0 = r / jnp.where(beta > _TINY, beta, 1.0)

    V = jnp.zeros((restart + 1, n_local), dt).at[0].set(v0)
    R = jnp.zeros((restart, restart), dt)   # rotated (triangular) H
    cs = jnp.zeros((restart,), dt)
    sn = jnp.zeros((restart,), dt)
    g = jnp.zeros((restart + 1,), dt).at[0].set(beta)
    row_ids = jnp.arange(restart + 1)

    def more(carry):
        j, *_, done = carry
        return (j < restart) & ~done

    def body(carry):
        j, V, R, cs, sn, g, _, _ = carry
        # right preconditioning: Krylov space of A M, solution mapped back
        # through M at cycle end -> the Givens residual estimate stays the
        # TRUE residual ||b - A x||, so forcing-term semantics are unchanged
        w = matvec(M(V[j]))
        # CGS2: two masked classical GS passes (2 collectives total).  The
        # mask is cast to the solve dtype: a float32 mask would silently
        # promote (or downcast) non-f32 inner solves through h1/h2.
        mask = (row_ids <= j).astype(dt)
        if deterministic:
            h1 = mask * _det_projections(axes, V, w)
            w = w - _det_combine(h1, V)
            h2 = mask * _det_projections(axes, V, w)
            w = w - _det_combine(h2, V)
        else:
            h1 = mask * axes.psum_state(V @ w)
            w = w - h1 @ V
            h2 = mask * axes.psum_state(V @ w)
            w = w - h2 @ V
        h = h1 + h2
        hnorm = norm2(w)
        v_next = w / jnp.where(hnorm > _TINY, hnorm, 1.0)

        # Apply the j previous Givens rotations to the new column.  Rotation i
        # touches positions (i, i+1), all <= j, so position j+1 (== hnorm)
        # stays untouched.
        def rot(i, hv):
            hi, hi1 = hv[i], hv[i + 1]
            hv = hv.at[i].set(cs[i] * hi + sn[i] * hi1)
            return hv.at[i + 1].set(-sn[i] * hi + cs[i] * hi1)

        h = h.at[j + 1].set(hnorm)
        h = jax.lax.fori_loop(
            0, restart,
            lambda i, hv: jnp.where(i < j, rot(i, hv), hv), h)
        hj = jnp.take(h, j)
        hj1 = hnorm

        denom = jnp.sqrt(hj * hj + hj1 * hj1)
        safe = denom > _TINY
        c_new = jnp.where(safe, hj / jnp.where(safe, denom, 1.0), 1.0)
        s_new = jnp.where(safe, hj1 / jnp.where(safe, denom, 1.0), 0.0)
        gj = jnp.take(g, j)
        g = g.at[j + 1].set(-s_new * gj).at[j].set(c_new * gj)
        res = jnp.abs(-s_new * gj)

        # Column j of R: rotated h (positions < j already rotated; j -> denom;
        # the subdiagonal entry j+1 is annihilated by the new rotation).
        col = h.at[j].set(denom).at[j + 1].set(0.0)
        return (j + 1, V.at[j + 1].set(v_next), R.at[:, j].set(col[:restart]),
                cs.at[j].set(c_new), sn.at[j].set(s_new), g, res, res <= tol)

    init = (jnp.int32(0), V, R, cs, sn, g, beta,
            (beta <= tol) | jnp.logical_not(run))
    iters, V, R, _, _, g, res, _ = jax.lax.while_loop(more, body, init)

    # Solve the (iters x iters) triangular system; mask out unused columns.
    active = jnp.arange(restart) < iters
    diag_fix = jnp.diag(jnp.where(active, 0.0, 1.0)).astype(R.dtype)
    R_m = jnp.where(active[None, :] & active[:, None], R, 0.0) + diag_fix
    g_m = jnp.where(active, g[:restart], 0.0)
    if deterministic:
        y = _det_backsolve(R_m, g_m)
        x_new = x + M(_det_combine(y, V[:restart]))
    else:
        y = jax.scipy.linalg.solve_triangular(R_m, g_m, lower=False)
        x_new = x + M(y @ V[:restart])
    if precond is not None:
        # With an ill-conditioned M (near-singular blocks at gamma -> 1,
        # ||M|| ~ 1/(1-gamma)) the f32 rounding of x + M(V y) can leave the
        # TRUE residual orders above the Givens estimate — the solver would
        # report convergence the iPI safeguard then rejects every outer
        # step.  Measure honestly; the next cycle restarts from the true
        # residual anyway, so this self-corrects at one matvec per cycle.
        # The plain path keeps the estimate (bit-identical to no-precond).
        with trace.scope(trace.GMRES_RESIDUAL):
            res = norm2(b - matvec(x_new))
    return x_new, res, iters


def gmres(matvec, b: jax.Array, x0: jax.Array, *, tol, maxiter: int,
          axes: Axes, restart: int = 32, deterministic: bool = False,
          precond=None):
    """Restarted GMRES.  Returns ``(x, iters, resnorm_2)``.

    ``deterministic=True`` pins every accumulation order (see the module
    docstring): fleet-sharded solves become bit-identical to replicated
    ones, at the cost of serializing the CGS2 projections lane-at-a-time.

    ``precond`` is an optional right preconditioner apply ``x -> M x``
    (``M ~= A^-1``, local shard in / local shard out).  ``None`` keeps the
    plain path bit-for-bit (the identity map adds no arithmetic).

    The SpMVs it runs: ``r0``, which the first cycle starts from; one per
    executed Arnoldi step (steps after convergence are not executed); the
    residual each later cycle starts from; and with ``precond`` the true
    residual each cycle ends with.
    """
    restart = int(restart)

    def cycle(s, r=None, beta=None, run=True):
        x, res, it = s
        x, res, done_iters = _arnoldi_cycle(
            matvec, b, x, r, beta, run, restart=restart, tol=tol, axes=axes,
            deterministic=deterministic, precond=precond)
        return x, res, it + done_iters

    def cond(s):
        _, res, it = s
        return (res > tol) & (it < maxiter)

    r0, res0 = _residual(matvec, b, x0, _norm2(axes, deterministic))
    s = (x0, res0, jnp.int32(0))
    # The first cycle is traced apart from the loop, so that r0 reaches it
    # without a residual vector riding in the loop's carry: on a TPU v5e a
    # second vector there moved x out of VMEM, and the backup that reads x
    # next ran 1.6x slower (PERF.md).
    s = cycle(s, r0, res0, run=cond(s))
    x, res, iters = jax.lax.while_loop(cond, cycle, s)
    return x, iters, res
