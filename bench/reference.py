"""The plain reference, and the lower-precision control.

The reference reads the benchmark's own tables, as ELL row blocks that
the cell's family (``bench/families/``) hands it, and an answer ``(v,
policy)`` that the program returned, and evaluates the answer exactly, in
float64 on the host with numpy:

* ``residual`` — ``max_s |min_a Q(v)[s, a] - v[s]|``, the Bellman residual
  that the program certifies to be at most ``atol``;
* ``greedy_gap`` — ``max_s Q(v)[s, pi(s)] - min_a Q(v)[s, a]``, how far the
  returned policy is from greedy for the returned values,

with ``Q(v)[s, a] = cost[s, a] + gamma * sum_k val[s, a, k] * v[idx[s, a, k]]``.
It imports nothing of the program.

The control puts the reference in the program's place one precision below
the configuration's float32: one Bellman backup in bfloat16 (tables, values
and arithmetic), from the same values the program answered with, gives the
answer ``(min_a Q_bf16, argmin_a Q_bf16)``, which the reference then
evaluates like any other.  A bfloat16 solver ends at such a point: its
fixed point is off by the rounding of one bfloat16 backup.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

# rows per host work item, and host threads for the f64 evaluation
BLOCK_ROWS = 1 << 15
THREADS = min(8, os.cpu_count() or 1)


def _q64(idx, val, cost, gamma, v64):
    """Q(v) in float64 for one row block (numpy arrays)."""
    pv = (val.astype(np.float64) * np.take(v64, idx)).sum(axis=-1)
    return cost.astype(np.float64) + gamma * pv


def _eval_block(idx, val, cost, gamma, v64, pi, rows):
    q = _q64(idx, val, cost, gamma, v64)
    best = q.min(axis=-1)
    chosen = np.take_along_axis(q, pi[:, None].astype(np.int64), axis=-1)[:, 0]
    residual = float(np.abs(best - v64[rows]).max())
    gap = float((chosen - best).max())
    return residual, gap


def host_blocks(table):
    """Yield ``(row0, idx, val, cost)`` numpy row blocks of a device table
    ``(idx, val, cost)`` whose rows are split over its devices, one device
    shard at a time (a sharded table never sits on the host whole)."""
    idx, val, cost = table
    for si, sv, sc in zip(idx.addressable_shards, val.addressable_shards,
                          cost.addressable_shards):
        yield si.index[0].start or 0, np.asarray(si.data), \
            np.asarray(sv.data), np.asarray(sc.data)


def evaluate(blocks, gamma: float, v, pi, n: int) -> dict:
    """``{"residual", "greedy_gap"}`` of the answer ``(v, pi)`` on the
    table given as host row blocks ``(row0, idx, val, cost)``."""
    v64 = np.asarray(v, np.float64)
    pi = np.asarray(pi)
    if v64.shape != (n,) or pi.shape != (n,):
        # an answer to another question
        return {"residual": float("inf"), "greedy_gap": float("inf")}
    residual, gap, missing = 0.0, 0.0, False
    with cf.ThreadPoolExecutor(THREADS) as pool:
        futs = []
        for row0, idx, val, cost in blocks:
            m = idx.shape[1]
            # an action that does not exist is as far from greedy as it gets
            missing |= bool((pi < 0).any() or (pi >= m).any())
            pi = np.clip(pi, 0, m - 1)
            for lo in range(0, idx.shape[0], BLOCK_ROWS):
                hi = min(lo + BLOCK_ROWS, idx.shape[0])
                rows = np.arange(row0 + lo, row0 + hi)
                futs.append(pool.submit(
                    _eval_block, idx[lo:hi], val[lo:hi], cost[lo:hi], gamma,
                    v64, pi[row0 + lo:row0 + hi], rows))
        for f in futs:
            r, g = f.result()
            residual, gap = max(residual, r), max(gap, g)
    if missing:
        gap = float("inf")
    if not np.isfinite(v64).all():
        residual = float("inf")
    return {"residual": residual, "greedy_gap": gap}


def control_answer(table, gamma: float, v):
    """The control's answer: one bfloat16 backup of ``v`` on the device
    table ``(idx, val, cost)``, per device shard, as ``(v, policy)``
    numpy arrays."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16

    def backup(idx, val, cost, vv, lo, rows):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, rows, 0)
        q = take(cost).astype(bf) + bf(gamma) * jnp.sum(
            take(val).astype(bf) * vv[take(idx)], axis=-1, dtype=bf)
        return jnp.min(q, axis=-1).astype(jnp.float32), \
            jnp.argmin(q, axis=-1).astype(jnp.int32)

    backup = jax.jit(backup, static_argnames="rows")
    idx, val, cost = table
    n = idx.shape[0]
    out_v = np.zeros(n, np.float32)
    out_pi = np.zeros(n, np.int32)
    for si, sv, sc in zip(idx.addressable_shards, val.addressable_shards,
                          cost.addressable_shards):
        row0 = si.index[0].start or 0
        vv = jax.device_put(np.asarray(v).astype(bf), si.device)
        n_local = si.data.shape[0]
        rows = min(BLOCK_ROWS * 4, n_local)
        for lo in range(0, n_local, rows):
            size = min(rows, n_local - lo)
            tv, am = backup(si.data, sv.data, sc.data, vv, np.int32(lo),
                            rows=size)
            out_v[row0 + lo:row0 + lo + size] = np.asarray(tv)
            out_pi[row0 + lo:row0 + lo + size] = np.asarray(am)
    return out_v, out_pi
