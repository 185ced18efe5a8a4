"""Matrix-free Bellman operator: recompute-over-store row evaluation.

The materialized path stores every MDP as an O(n*m*nnz) ELL table and
streams it through the fused backup kernels.  This module is the second
implementation of the same Bellman-operator contract: the jit-able
``from_functions`` row constructors (``P_fn(rows, a) -> (ids, probs)``,
``g_fn(rows, a) -> cost``) are **re-traced inside the backup and the
policy-row extraction**, tile by tile, so the only persistent per-shard
state is O(n) — the value/policy vectors plus a 1-byte placement tag.

Parity contract (the non-negotiable invariant)
----------------------------------------------
Every function here is bit-identical to the materialized path:

* :func:`build_rows_block` is the *same* traced builder the device
  materialization pipeline runs (``repro.api.mdp`` delegates here), so a
  rebuilt chunk equals the stored table's slice bit-for-bit;
* the per-chunk backup body runs the exact per-row math of the
  materialized kernels (``ops.ell_backup_chunk``), and that math is
  row-independent, so *any* row chunking produces identical bits —
  :func:`repro.kernels.ref._blocked_rows` chunking included;
* :func:`mf_policy_rows` replays :func:`repro.core.bellman.policy_rows`'s
  ``take_along_axis`` + ownership-mask arithmetic on rebuilt chunks, so
  the inner (Krylov) solvers consume bit-identical ``PolicyRows`` and need
  no changes at all.

Tiling mirrors ``ref.ell_backup_blocked``: a ``lax.scan`` over fixed row
chunks whose transient working set — the rebuilt ``(bn, m, nnz)`` block —
is bounded and cache-sized, which is also exactly the structure a Pallas
grid over row tiles wants (each scan body is one future grid step).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.utils import trace

from . import ops, ref

__all__ = ["RowSpec", "build_rows_block", "mf_backup", "mf_policy_rows",
           "table_bytes", "operator_bytes"]

_BIG = 1e30


@dataclasses.dataclass(frozen=True)
class RowSpec:
    """Static description of a function-backed MDP's rows — the metadata a
    matrix-free container carries instead of arrays.

    Hashable (callables compare by identity), gamma-free on purpose: a
    gamma sweep over one constructor pair shares a single spec, hence a
    single compiled program (the generator registry memoizes its closure
    helpers so constructor identity is stable across calls).

    ``band`` is the declared matrix bandwidth — ``|successor - row| <=
    band`` for every nonzero-weight successor — or ``None`` when the rows
    reach globally.  The partition planner derives the frontier margins
    and the halo width from it, since there are no arrays to measure.
    """

    p_fn: Callable
    g_fn: Callable
    n: int
    m: int
    nnz: int
    vectorized: bool
    band: int | None = None


def build_rows_block(spec, rows, acts: tuple, mode: str):
    """One traced ELL block: ``rows`` (traced global ids) x ``acts``
    (static global action ids, padding included).

    ``spec`` is duck-typed (:class:`RowSpec` or the api layer's deferred
    ``_FunctionSpec``): it needs ``p_fn``/``g_fn``/``n``/``m``/``nnz``/
    ``vectorized``.

    Mirrors the host ``MDP._block`` semantics bit-for-bit: padded states
    (``rows >= n``) are zero-cost absorbing self-loops; padded action
    columns (``a >= m``) carry the never-greedy ``±BIG`` cost of the solve
    ``mode`` and point at state 0.  Constructors see the raw row ids —
    including shard-padding ids ``>= n``, whose outputs are masked — so
    they must tolerate any int32 input (clip/where, not assert).

    Returns ``(idx, val, cost, bad)`` where ``bad`` is a per-row ``(R, 2)``
    count of validation failures over the *real* entries — successor ids
    outside ``[0, n)`` and probability rows not summing to ~1 — folded into
    the same compiled program so the host raise costs one scalar readback.
    (Matrix-free consumers drop ``bad``; dead-code elimination removes it.)
    """
    big = _BIG if mode == "mincost" else -_BIG
    K, R = spec.nnz, rows.shape[0]
    pad_row = rows >= spec.n
    bad_ids = jnp.zeros((R,), jnp.int32)
    bad_sum = jnp.zeros((R,), jnp.int32)
    self_idx = jnp.zeros((R, K), jnp.int32).at[:, 0].set(
        rows.astype(jnp.int32))
    self_val = jnp.zeros((R, K), jnp.float32).at[:, 0].set(1.0)

    def conform(what, a, arr, shape, dtype):
        arr = jnp.asarray(arr)
        if arr.shape != shape:
            raise ValueError(
                f"device {what}(rows, a={a}) must return shape {shape} "
                f"(nnz={K} slots per row — zero-pad unused slots), got "
                f"{arr.shape}")
        return arr.astype(dtype)

    cols_i, cols_v, cols_c = [], [], []
    for a in acts:
        if a >= spec.m:
            # never-greedy padded action: cost ±BIG, self-transition to 0
            cols_i.append(jnp.zeros((R, K), jnp.int32))
            cols_v.append(self_val)
            cols_c.append(jnp.full((R,), big, jnp.float32))
            continue
        if spec.vectorized:
            ids, probs = spec.p_fn(rows, int(a))
            ids = conform("P_fn", a, ids, (R, K), jnp.int32)
            probs = conform("P_fn", a, probs, (R, K), jnp.float32)
            g = jnp.broadcast_to(
                jnp.asarray(spec.g_fn(rows, int(a)), jnp.float32), (R,))
        else:
            def one(r, a=a):
                i, p = spec.p_fn(r, int(a))
                return (conform("P_fn", a, i, (K,), jnp.int32),
                        conform("P_fn", a, p, (K,), jnp.float32),
                        jnp.asarray(spec.g_fn(r, int(a)),
                                    jnp.float32).reshape(()))
            ids, probs, g = jax.vmap(one)(rows)
        real = ~pad_row
        bad_ids = bad_ids + jnp.where(
            real, ((ids < 0) | (ids >= spec.n)).sum(-1, dtype=jnp.int32), 0)
        bad_sum = bad_sum + jnp.where(
            real & (jnp.abs(probs.astype(jnp.float32).sum(-1) - 1.0) > 1e-4),
            1, 0)
        cols_i.append(jnp.where(pad_row[:, None], self_idx, ids))
        cols_v.append(jnp.where(pad_row[:, None], self_val, probs))
        cols_c.append(jnp.where(pad_row, jnp.float32(0.0), g))
    return (jnp.stack(cols_i, axis=1), jnp.stack(cols_v, axis=1),
            jnp.stack(cols_c, axis=1), jnp.stack([bad_ids, bad_sum], axis=1))


def _chunk_rows(spec, n_rows: int, acts: tuple, v, block_rows) -> int:
    """Rows per rebuild tile: explicit, else the blocked-backup autotuner
    choice (the transient table chunk has the same shape/traffic profile
    as a materialized blocked chunk, so the tuned size transfers)."""
    if block_rows:
        return int(block_rows)
    return ops.backup_block_rows(n_rows, len(acts), spec.nnz,
                                 v.shape[-1], v.dtype)


@trace.scoped(trace.BACKUP)
def mf_backup(spec, row0, n_rows: int, acts: tuple, gamma, v, *,
              mode: str = "mincost", idx_map=None, impl: str | None = None,
              block_rows: int | None = None):
    """Matrix-free fused Bellman backup over ``n_rows`` rows starting at
    (traced) global row ``row0``: rebuild each row tile from the
    constructors, run the materialized chunk kernel on it, discard it.

    ``idx_map`` (optional) maps the rebuilt *global* successor ids into
    the coordinate system of ``v`` (halo windows, interior-local reads);
    identity when ``None``.  ``mode="maxreward"`` negates internally —
    like the materialized path, the returned ``(vmin, amin)`` live in the
    *negated* min-space so the caller's ``_finish_argmin(..., neg=True)``
    completes them identically.

    Peak transient memory is one ``(block_rows, len(acts), nnz)`` table
    chunk; the persistent footprint is O(n).
    """
    neg = mode == "maxreward"
    if neg:
        v = -v
    rows = row0 + jnp.arange(n_rows, dtype=jnp.int32)

    def body(r):
        idx, val, cost, _bad = build_rows_block(spec, r, acts, mode)
        if neg:
            cost = -cost
        if idx_map is not None:
            idx = idx_map(idx)
        return ops.ell_backup_chunk(idx, val, cost, gamma, v, impl=impl)

    bn = _chunk_rows(spec, n_rows, acts, v, block_rows)
    return ref._blocked_rows(body, (rows,), (), n_rows, bn)


def mf_policy_rows(spec, row0, n_rows: int, acts: tuple, a_sel, own, *,
                   mode: str = "mincost", block_rows: int | None = None):
    """Matrix-free ``P_pi``/``g_pi`` extraction: rebuild each row tile and
    replay :func:`repro.core.bellman.policy_rows`'s exact
    ``take_along_axis`` + ownership-mask arithmetic on it.

    Returns ``(idx_pi (n, K) int32, val_pi (n, K) f32, g_pi (n,) f32)`` —
    bit-identical to selecting from the materialized table, so the inner
    solvers run unchanged on the result.  The output is O(n*nnz) (the same
    transient the materialized path's selection produces); only the
    O(n*m*nnz) full table is never held.

    ``mode`` only affects padded action columns (``a >= m``), which a
    greedy ``a_sel`` never selects on the state-sharded layouts matrix-free
    supports — passed through for exactness anyway.
    """
    rows = row0 + jnp.arange(n_rows, dtype=jnp.int32)

    def body(r, a_sel_c, own_c):
        idx, val, cost, _bad = build_rows_block(spec, r, acts, mode)
        take = lambda x: jnp.take_along_axis(
            x, a_sel_c[:, None, None], axis=1)[:, 0]
        idx_pi = take(idx)
        val_pi = take(val) * own_c[:, None].astype(val.dtype)
        g_pi = jnp.take_along_axis(cost, a_sel_c[:, None], axis=1)[:, 0]
        g_pi = g_pi * own_c.astype(g_pi.dtype)
        return idx_pi, val_pi, g_pi

    bn = block_rows or min(ref.DEFAULT_BLOCK_ROWS, max(1, n_rows))
    return ref._blocked_rows(body, (rows, a_sel, own), (), n_rows, bn)


# --------------------------------------------------------------------------- #
# Memory model (serve admission, dryrun cost model, benches, README)          #
# --------------------------------------------------------------------------- #

# O(n) iteration state per state (f32): v, tv, window/staging, residual work
ITER_BYTES = 16


def table_bytes(n: int, m: int, nnz: int) -> int:
    """Materialized ELL container bytes: idx (i32) + val (f32) per slot,
    cost (f32) per (state, action) row."""
    return n * m * (8 * nnz + 4)


def operator_bytes(n: int, nnz: int, *, krylov: bool = True) -> int:
    """Peak per-solve device bytes of the matrix-free path: the 1-byte
    placement tag + O(n) value vectors, plus — for the policy-iteration
    methods (``krylov=True``) — the transient policy-restricted rows
    ``n * (8*nnz + 4)`` the inner solvers consume.  Pure VI never
    materializes policy rows; pass ``krylov=False`` for its footprint."""
    per = 1 + ITER_BYTES
    if krylov:
        per += 8 * nnz + 4
    return n * per
