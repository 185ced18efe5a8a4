"""Inner (Krylov/Richardson) solvers vs numpy LU, incl. hypothesis sweeps."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.comm import Axes
from repro.core.solvers import anderson, bicgstab, chebyshev, gmres, \
    richardson
from repro.core.solvers.gmres import _TINY, _det_backsolve, _det_combine, \
    _det_norm2, _det_projections

AXES = Axes()


def _mdp_like_system(n, gamma, seed):
    """A = I - gamma * P with P row-stochastic: the exact structure the
    inner solvers face (nonsymmetric, diagonally dominant for gamma < 1)."""
    rng = np.random.default_rng(seed)
    p = rng.random((n, n))
    p /= p.sum(1, keepdims=True)
    a = np.eye(n) - gamma * p
    b = rng.random(n)
    return a, b


@pytest.mark.parametrize("solver,kw", [
    (gmres, dict(restart=25)), (bicgstab, {}), (richardson, {}),
    (anderson, dict(window=5))])
@pytest.mark.parametrize("gamma", [0.5, 0.95, 0.999])
def test_solves_mdp_system(solver, kw, gamma):
    a, b = _mdp_like_system(150, gamma, seed=1)
    x_true = np.linalg.solve(a, b)
    aj = jnp.asarray(a)
    maxiter = 200000 if solver is richardson else 5000
    x, iters, res = solver(lambda v: aj @ v, jnp.asarray(b),
                           jnp.zeros(150, jnp.float64), tol=1e-10,
                           maxiter=maxiter, axes=AXES, **kw)
    assert float(res) <= 1e-10
    np.testing.assert_allclose(np.asarray(x), x_true, atol=1e-8)


@pytest.mark.parametrize("gamma", [0.5, 0.9])
def test_chebyshev_solves_mdp_system(gamma):
    """Chebyshev on [1-gamma, 1+gamma]: exact where the (near-)real-spectrum
    assumption holds (bulk eigenvalues of the dense random P are tiny at
    moderate gamma; the gamma -> 1 complex-bulk regime is covered by the
    divergence-guard test below)."""
    a, b = _mdp_like_system(150, gamma, seed=1)
    x_true = np.linalg.solve(a, b)
    aj = jnp.asarray(a)
    x, iters, res = chebyshev(lambda v: aj @ v, jnp.asarray(b),
                              jnp.zeros(150, jnp.float64), tol=1e-10,
                              maxiter=5000, axes=AXES,
                              lo=1 - gamma, hi=1 + gamma)
    assert float(res) <= 1e-10
    assert int(iters) < 5000
    np.testing.assert_allclose(np.asarray(x), x_true, atol=1e-8)


def test_chebyshev_divergence_guard_bails_early():
    """On a spectrum far outside the target interval the residual grows;
    the PETSc-style divtol must stop the sweep long before maxiter so the
    outer safeguard gets a cheap rejection."""
    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.random((40, 40)))
    # eigenvalues on a ring of radius 1 around 1: worst case for the
    # interval iteration
    ang = np.linspace(0, 2 * np.pi, 20, endpoint=False)
    blocks = [np.array([[1 + np.cos(t), -np.sin(t)],
                        [np.sin(t), 1 + np.cos(t)]]) for t in ang]
    a = q @ (np.kron(np.eye(20), np.zeros((2, 2))) +
             np.block([[blocks[i] if i == j else np.zeros((2, 2))
                        for j in range(20)] for i in range(20)])) @ q.T
    aj = jnp.asarray(a)
    b = jnp.asarray(rng.random(40))
    x, iters, res = chebyshev(lambda v: aj @ v, b,
                              jnp.zeros(40, jnp.float64), tol=1e-12,
                              maxiter=100000, axes=AXES, lo=0.9, hi=1.1,
                              divtol=1e4)
    assert int(iters) < 100000    # bailed out, did not spin to the cap


@settings(max_examples=25, deadline=None)
@given(n=st.integers(5, 60), gamma=st.floats(0.1, 0.99),
       seed=st.integers(0, 10_000))
def test_gmres_property(n, gamma, seed):
    """For any row-stochastic P and gamma<1, GMRES solves (I-gamma P)x=b."""
    a, b = _mdp_like_system(n, gamma, seed)
    aj = jnp.asarray(a)
    x, _, res = gmres(lambda v: aj @ v, jnp.asarray(b),
                      jnp.zeros(n, jnp.float64), tol=1e-9, maxiter=2000,
                      axes=AXES, restart=min(n, 30))
    true_res = np.linalg.norm(b - a @ np.asarray(x))
    assert true_res <= 1e-6 * max(1.0, np.linalg.norm(b))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(5, 60), gamma=st.floats(0.1, 0.99),
       seed=st.integers(0, 10_000))
def test_bicgstab_property(n, gamma, seed):
    a, b = _mdp_like_system(n, gamma, seed)
    aj = jnp.asarray(a)
    x, _, res = bicgstab(lambda v: aj @ v, jnp.asarray(b),
                         jnp.zeros(n, jnp.float64), tol=1e-9, maxiter=4000,
                         axes=AXES)
    true_res = np.linalg.norm(b - a @ np.asarray(x))
    assert true_res <= 1e-6 * max(1.0, np.linalg.norm(b))


def test_gmres_zero_rhs():
    aj = jnp.eye(10, dtype=jnp.float64)
    x, iters, res = gmres(lambda v: aj @ v, jnp.zeros(10, jnp.float64),
                          jnp.zeros(10, jnp.float64), tol=1e-12, maxiter=10,
                          axes=AXES, restart=5)
    assert float(res) == 0.0 and np.asarray(x).max() == 0.0


def test_warm_start_exact_solution_is_noop():
    a, b = _mdp_like_system(40, 0.9, seed=3)
    x_true = np.linalg.solve(a, b)
    aj = jnp.asarray(a)
    for solver, kw in [(gmres, dict(restart=10)), (bicgstab, {}),
                       (richardson, {})]:
        x, iters, res = solver(lambda v: aj @ v, jnp.asarray(b),
                               jnp.asarray(x_true), tol=1e-8, maxiter=100,
                               axes=AXES, **kw)
        assert int(iters) == 0, solver.__name__


# --------------------------------------------------------------------------- #
# GMRES runs only the SpMVs it uses                                            #
# --------------------------------------------------------------------------- #
#
# The oracle is GMRES as it was before the Arnoldi loop learned to stop at
# convergence: every cycle re-measures ``b - A x`` and runs all ``restart``
# steps, masking the ones after convergence.  The solver now skips both; the
# same Krylov steps run on the same vectors, so every result must match to
# the bit.


def _masked_arnoldi_cycle(matvec, b, x, *, restart, tol, axes,
                          deterministic=False, precond=None):
    """Frozen copy of the fixed-length masked cycle (the oracle)."""
    n_local = x.shape[0]
    dt = x.dtype
    M = precond if precond is not None else (lambda v: v)
    norm2 = (lambda v: _det_norm2(axes, v)) if deterministic else axes.norm2
    r = b - matvec(x)
    beta = norm2(r)
    v0 = r / jnp.where(beta > _TINY, beta, 1.0)

    V = jnp.zeros((restart + 1, n_local), dt).at[0].set(v0)
    R = jnp.zeros((restart, restart), dt)
    cs = jnp.zeros((restart,), dt)
    sn = jnp.zeros((restart,), dt)
    g = jnp.zeros((restart + 1,), dt).at[0].set(beta)
    row_ids = jnp.arange(restart + 1)

    def body(j, carry):
        V, R, cs, sn, g, res, it, done = carry
        w = matvec(M(V[j]))
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
        g_new = g.at[j + 1].set(-s_new * gj).at[j].set(c_new * gj)
        res_new = jnp.abs(-s_new * gj)
        col = h.at[j].set(denom).at[j + 1].set(0.0)
        R_new = R.at[:, j].set(col[:restart])
        V_new = V.at[j + 1].set(v_next)

        keep = lambda new, old: jax.tree_util.tree_map(
            lambda a, o: jnp.where(done, o, a), new, old)
        V, R, cs_o, sn_o, g, res, it = keep(
            (V_new, R_new, cs.at[j].set(c_new), sn.at[j].set(s_new), g_new,
             res_new, it + 1),
            (V, R, cs, sn, g, res, it))
        done = done | (res <= tol)
        return V, R, cs_o, sn_o, g, res, it, done

    init = (V, R, cs, sn, g, beta, jnp.int32(0), beta <= tol)
    V, R, _, _, g, res, iters, _ = jax.lax.fori_loop(0, restart, body, init)

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
        res = norm2(b - matvec(x_new))
    return x_new, res, iters


def masked_gmres(matvec, b, x0, *, tol, maxiter, axes, restart=32,
                 deterministic=False, precond=None):
    """Frozen copy of the restarted driver around the masked cycle."""
    def cycle(s):
        x, _, it = s
        x, res, done_iters = _masked_arnoldi_cycle(
            matvec, b, x, restart=restart, tol=tol, axes=axes,
            deterministic=deterministic, precond=precond)
        return x, res, it + done_iters

    r0 = b - matvec(x0)
    res0 = _det_norm2(axes, r0) if deterministic else axes.norm2(r0)
    x, res, iters = jax.lax.while_loop(
        lambda s: (s[1] > tol) & (s[2] < maxiter), cycle,
        (x0, res0, jnp.int32(0)))
    return x, iters, res


def garnet_system(n, gamma, seed, k=8, dtype=np.float64):
    """``I - gamma P_pi`` of a garnet policy as (matvec data, b, jacobi):
    ``k`` random successors per state with random probabilities, the ELL
    layout the solver's SpMV reads."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, (n, k)).astype(np.int32)
    cuts = np.sort(rng.random((n, k - 1)), axis=1)
    p = np.diff(np.concatenate([np.zeros((n, 1)), cuts, np.ones((n, 1))], 1),
                axis=1)
    diag = 1.0 - gamma * np.where(idx == np.arange(n)[:, None], p, 0).sum(1)
    b = rng.random(n)
    return (idx, p.astype(dtype), np.asarray(gamma, dtype), b.astype(dtype),
            (1.0 / diag).astype(dtype))


def garnet_matvec(idx, p, gamma, v):
    return v - gamma * jnp.sum(p * v[idx], axis=1)


def _gmres_pair(variant, restart, tol):
    """(new, oracle) results of one parity case on one device."""
    dtype = np.float32 if variant == "plain_f32" else np.float64
    det = variant == "deterministic"
    kw = dict(tol=tol, maxiter=400, axes=AXES, restart=restart,
              deterministic=det)

    def run(solver, idx, p, gamma, b, inv_d):
        pc = (lambda v: v * inv_d) if variant == "jacobi" else None
        return solver(lambda v: garnet_matvec(idx, p, gamma, v), b,
                      jnp.zeros_like(b), precond=pc, **kw)

    if variant == "fleet":
        # three instances that converge at different steps
        lanes = [garnet_system(768, g, seed=s)
                 for g, s in ((0.5, 11), (0.9, 12), (0.95, 13))]
        args = [np.stack(a) for a in zip(*lanes)]
        go = lambda s: jax.jit(jax.vmap(lambda *a: run(s, *a)))(*args)
    else:
        args = garnet_system(768, 0.9, seed=5, dtype=dtype)
        go = lambda s: jax.jit(lambda *a: run(s, *a))(*args)
    return go(gmres), go(masked_gmres)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
sys.path.insert(0, os.path.dirname(os.environ["PARITY_TESTS"]))
from test_linear_solvers import garnet_system, masked_gmres
from repro.core.comm import Axes
from repro.core.solvers import gmres

axes = Axes(state="data")
mesh = Mesh(np.array(jax.devices()), ("data",))
idx, p, gamma, b, _ = garnet_system(768, 0.9, seed=5)
out = {}
for restart in (32, 4):
    def run(solver, idx, p, b):
        mv = lambda v: v - gamma * jnp.sum(
            p * axes.allgather_state(v)[idx], axis=1)
        return solver(mv, b, jnp.zeros_like(b), tol=1e-8, maxiter=400,
                      axes=axes, restart=restart)
    res = []
    for solver in (gmres, masked_gmres):
        f = jax.jit(jax.shard_map(
            lambda i, q, r: run(solver, i, q, r), mesh=mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P(), P()), check_vma=False))
        res.append([np.asarray(a) for a in f(idx, p, b)])
    (x, it, r), (xo, ito, ro) = res
    out[str(restart)] = dict(
        x_equal=bool(np.array_equal(x, xo)),
        x_maxdiff=float(np.abs(x - xo).max()),
        iters=int(it), iters_oracle=int(ito),
        res_equal=bool(np.array_equal(r, ro)), res=float(r))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_parity():
    """The ``1d`` layout's parity on four virtual CPU devices (the device
    count must be set before jax initializes, so this shells out, as
    ``test_distributed.py`` does)."""
    env = dict(os.environ, PARITY_TESTS=os.path.abspath(__file__),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("restart", [32, 4])
@pytest.mark.parametrize("variant", [
    "plain", "plain_f32", "deterministic", "jacobi", "fleet", "1d"])
def test_gmres_matches_masked_cycle_bit_for_bit(variant, restart, request):
    """At ``restart`` 32 the solve ends within one cycle, at 4 it takes
    several; the new GMRES returns the oracle's ``x``, ``iters`` and
    ``res`` to the bit on every path."""
    if variant == "1d":
        r = request.getfixturevalue("sharded_parity")[str(restart)]
        assert r["x_equal"] and r["res_equal"], r
        assert r["iters"] == r["iters_oracle"], r
        assert r["res"] <= 1e-8, r
        return
    tol = 1e-4 if variant == "plain_f32" else 1e-8
    (x, it, res), (xo, ito, reso) = _gmres_pair(variant, restart, tol)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(ito))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xo))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(reso))
    assert np.all(np.asarray(res) <= tol)
    if restart == 32:
        assert np.all(np.asarray(it) <= 32)    # one cycle
    else:
        assert np.all(np.asarray(it) > 2 * restart)
    if variant == "fleet":
        assert len(set(np.asarray(it).tolist())) == 3, it


def _count_spmvs(solver, restart, precond, x0_exact=False):
    """SpMVs one jitted ``solver`` call executes, counted on the device."""
    idx, p, gamma, b, inv_d = garnet_system(512, 0.9, seed=7)
    calls = []

    def matvec(v):
        jax.debug.callback(lambda: calls.append(1), ordered=True)
        return garnet_matvec(idx, p, gamma, v)

    x0 = jnp.zeros_like(b)
    if x0_exact:
        a = np.eye(512)
        np.add.at(a, (np.repeat(np.arange(512), 8), idx.ravel()),
                  -gamma * p.ravel())
        x0 = jnp.asarray(np.linalg.solve(a, b))
    pc = (lambda v: v * inv_d) if precond else None
    out = jax.jit(lambda b, x0: solver(
        matvec, b, x0, tol=1e-8, maxiter=400, axes=AXES, restart=restart,
        precond=pc))(b, x0)
    jax.block_until_ready(out)
    jax.effects_barrier()
    return len(calls), int(out[1])


@pytest.mark.parametrize("precond", [False, True])
@pytest.mark.parametrize("case", ["one_cycle", "cycles", "converged_x0"])
def test_gmres_runs_only_the_spmvs_it_uses(case, precond):
    """``r0``, which the first cycle starts from, one per executed Arnoldi
    step, the residual each later cycle starts from and, with a
    preconditioner, the true residual each cycle ends with.  An ``x0`` that
    meets ``tol`` costs ``r0`` alone on the plain path."""
    restart = 4 if case == "cycles" else 32
    exact = case == "converged_x0"
    n, iters = _count_spmvs(gmres, restart, precond, exact)
    n_masked, iters_masked = _count_spmvs(masked_gmres, restart, precond,
                                          exact)
    assert iters == iters_masked
    if exact:
        # with a preconditioner the first cycle still measures where it
        # ended, as the oracle's cycles do
        assert (n, iters, n_masked) == (1 + int(precond), 0, 1)
        return
    # the oracle runs whole cycles: r0, then per cycle its residual, all
    # ``restart`` steps, and with a preconditioner the closing residual
    per_cycle = restart + 1 + int(precond)
    cycles, rem = divmod(n_masked - 1, per_cycle)
    assert rem == 0 and cycles >= 1
    assert (cycles == 1) == (case == "one_cycle")
    assert n == 1 + iters + (cycles - 1) + (cycles if precond else 0)
