"""Pure-jnp oracles for every kernel in this package.

These are the semantic ground truth: every other implementation matches
them bit for bit (tests/test_kernels.py and tests/test_kernels_tiled.py sweep
shapes and dtypes against them).  They are also the XLA implementations:
``ell_backup``/``ell_matvec`` are what ``-kernel_impl auto`` runs on TPU, and
the cache-blocked variants below are its choice elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _acc_dtype(*arrays):
    """Accumulation dtype: at least f32, f64 if any operand is f64 (the
    PETSc-faithful double-precision path)."""
    return jnp.result_type(jnp.float32, *(a.dtype for a in arrays))


def pin_rounding(x: jax.Array) -> jax.Array:
    """Identity that the compiler cannot see through.

    XLA:CPU may contract a multiply into a following add (FMA) in some
    fusions but not others, so eager / jit / scan-blocked / Pallas-interpret
    renderings of the same math can disagree at the last ulp.  Routing the
    product through a runtime-dependent select pins every implementation to
    the same double rounding.  (``optimization_barrier`` does not block the
    contraction, it happens during LLVM lowering inside a fusion.)
    """
    return jnp.where(x == x, x, 0.0 * x)


def ksum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Sum over a short axis (the ELL ``K`` slots) strictly left to right.

    A reduce op leaves its association order to the backend (XLA:TPU and
    XLA:CPU tree differently, and differently per layout), so the row-major
    and rows-last formulations below would disagree at the last ulp.  A
    chain of binary adds has one order everywhere, which keeps every
    implementation bit-identical on every backend.
    """
    x = jnp.moveaxis(x, axis, 0)
    acc = x[0]
    for k in range(1, x.shape[0]):
        acc = acc + x[k]
    return acc


def ell_gather_dot(idx: jax.Array, val: jax.Array, v: jax.Array, *,
                   k_axis: int = -1) -> jax.Array:
    """sum_k val[..., k] * v[idx[..., k]]  — the ELL row-gather dot.

    idx: int32 global column ids with the K slots on ``k_axis``; val: same
    shape; v: (n_cols,).  Returns idx's shape without ``k_axis``,
    accumulated in >= f32 (f64 when v is f64).
    """
    dt = _acc_dtype(val, v)
    gathered = jnp.take(v, idx, axis=0)
    prod = pin_rounding(val.astype(dt) * gathered.astype(dt))
    return ksum(prod, k_axis)


def _rows_last(x: jax.Array) -> jax.Array:
    return jnp.moveaxis(x, 0, -1)


def ell_qvalues(idx: jax.Array, val: jax.Array, cost: jax.Array, gamma: float,
                v: jax.Array) -> jax.Array:
    """Q(s, a) = g(s, a) + gamma * sum_{s'} P(s, a, s') v(s')  on an ELL block."""
    pv = ell_gather_dot(idx, val, v)
    return cost.astype(pv.dtype) + pin_rounding(gamma * pv)


def _qvalues_rows_last(idx, val, cost, gamma, v) -> jax.Array:
    """``ell_qvalues`` computed as ``(m, n)``: the row axis is moved last
    before the gather.  XLA:TPU stores an ``(n, m, K)`` table with n in the
    128-wide lane dimension, so this view is free and the gathered block
    stays lane-dense; the row-major gather's ``(n, m, K)`` output would pad
    K = 8 out to 128 lanes (16x the table's bytes at m = 16, K = 8)."""
    pv = ell_gather_dot(_rows_last(idx), _rows_last(val), v, k_axis=-2)
    return _rows_last(cost).astype(pv.dtype) + pin_rounding(gamma * pv)


def ell_backup(idx: jax.Array, val: jax.Array, cost: jax.Array, gamma: float,
               v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused Bellman backup: (min_a Q, argmin_a Q) with smallest-index
    tie-break.  Rows-last, like :func:`ell_matvec`: the one XLA chain that
    fits a real-size table on the TPU."""
    q = _qvalues_rows_last(idx, val, cost, gamma, v)
    return jnp.min(q, axis=0), jnp.argmin(q, axis=0).astype(jnp.int32)


def ell_matvec(idx: jax.Array, val: jax.Array, x: jax.Array) -> jax.Array:
    """y(s) = sum_{s'} P_pi(s, s') x(s') on policy-restricted ELL rows (n, K)."""
    return ell_gather_dot(_rows_last(idx), _rows_last(val), x, k_axis=-2)


# ---------------------------------------------------------------------------
# Cache-blocked variants.
#
# Same math as the oracles above, restructured so XLA emits a row-chunked loop
# whose per-chunk working set (idx/val/cost chunk + the gathered q block) fits
# in cache instead of streaming the whole (n, m, K) table through one fused
# expression.  Bit-identical to the plain oracles: each chunk runs the exact
# per-row computation of `ell_qvalues`, and the column-wise running min below
# reduces in the same order as `jnp.min`/`jnp.argmin` (strict `<` keeps the
# first minimum, i.e. the smallest action index).
# ---------------------------------------------------------------------------

# Rows per chunk.  At the paper's typical widths (m*K between 16 and 128
# entries/row) this keeps a chunk's table slice plus its q block well inside
# the last-level cache on common parts.
DEFAULT_BLOCK_ROWS = 125_000

# Above this action count the unrolled running min stops paying for its trace
# size; fall back to the reduction ops (same result, see module tests).
_COLMIN_UNROLL_LIMIT = 64


def rowmin_argmin(q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(min, argmin) over the trailing axis via a column-wise running min.

    Unrolled vertical selects vectorise better than the horizontal reduce on
    CPU and are bit-identical to jnp.min/jnp.argmin with first-min
    (smallest-index) tie-breaking.
    """
    m = q.shape[-1]
    if m > _COLMIN_UNROLL_LIMIT:
        return jnp.min(q, axis=-1), jnp.argmin(q, axis=-1).astype(jnp.int32)
    best = q[..., 0]
    arg = jnp.zeros(q.shape[:-1], jnp.int32)
    for a in range(1, m):
        qa = q[..., a]
        hit = qa < best
        best = jnp.where(hit, qa, best)
        arg = jnp.where(hit, jnp.int32(a), arg)
    return best, arg


def _blocked_rows(fn, chunked_args, tail_args, n, block_rows):
    """Run `fn(*chunk)` over row chunks of size block_rows with a tail chunk.

    chunked_args are split along axis 0; tail_args are closed over whole
    (e.g. the value vector v).  Results are concatenated along axis 0.
    """
    bn = max(1, min(int(block_rows), n))
    nb = n // bn
    head = nb * bn
    if nb <= 1 and head == n:
        return fn(*chunked_args, *tail_args)

    def chunk(carry, args):
        return carry, fn(*args, *tail_args)

    split = tuple(a[:head].reshape((nb, bn) + a.shape[1:]) for a in chunked_args)
    _, out = jax.lax.scan(chunk, 0, split)
    out = jax.tree_util.tree_map(
        lambda x: x.reshape((head,) + x.shape[2:]), out)
    if head < n:
        rem = fn(*(a[head:] for a in chunked_args), *tail_args)
        out = jax.tree_util.tree_map(
            lambda x, r: jnp.concatenate([x, r], axis=0), out, rem)
    return out


def ell_backup_blocked(idx: jax.Array, val: jax.Array, cost: jax.Array,
                       gamma: float, v: jax.Array,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       ) -> tuple[jax.Array, jax.Array]:
    """Cache-blocked fused Bellman backup; bit-identical to `ell_backup`."""
    n = idx.shape[0]

    def body(ib, wb, cb):
        return rowmin_argmin(ell_qvalues(ib, wb, cb, gamma, v))

    return _blocked_rows(body, (idx, val, cost), (), n, block_rows)


def ell_qvalues_blocked(idx: jax.Array, val: jax.Array, cost: jax.Array,
                        gamma: float, v: jax.Array,
                        block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """Cache-blocked Q table; bit-identical to `ell_qvalues`."""
    n = idx.shape[0]

    def body(ib, wb, cb):
        return ell_qvalues(ib, wb, cb, gamma, v)

    return _blocked_rows(body, (idx, val, cost), (), n, block_rows)


def ell_matvec_blocked(idx: jax.Array, val: jax.Array, x: jax.Array,
                       block_rows: int = DEFAULT_BLOCK_ROWS) -> jax.Array:
    """Cache-blocked policy-restricted SpMV; bit-identical to `ell_matvec`."""
    n = idx.shape[0]
    return _blocked_rows(ell_gather_dot, (idx, val), (x,), n, block_rows)


def dense_qvalues(p: jax.Array, cost: jax.Array, gamma: float,
                  v: jax.Array) -> jax.Array:
    """Dense-P Q table: cost + gamma * P @ v, >= f32 accumulation (MXU path)."""
    dt = _acc_dtype(p, v)
    pv = jax.lax.dot_general(
        p.astype(dt), v.astype(dt),
        dimension_numbers=(((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return cost.astype(dt) + gamma * pv


def dense_backup(p: jax.Array, cost: jax.Array, gamma: float,
                 v: jax.Array) -> tuple[jax.Array, jax.Array]:
    q = dense_qvalues(p, cost, gamma, v)
    return jnp.min(q, axis=-1), jnp.argmin(q, axis=-1).astype(jnp.int32)
