"""Pallas TPU kernel: tiled streaming fused ELL Bellman backup.

The solver's hot spot (one per outer iteration, and the entire inner loop of
VI).  Fuses gather -> weighted-sum -> +cost -> min/argmin over actions so the
(n, m) Q-table never round-trips to HBM — on the XLA path the Q-table is a
materialized intermediate, which at n=10^7, m=256 is a 10 GB HBM write+read
per backup.  TPU adaptation of madupite's CSR row kernels (see DESIGN.md A1).

Unlike the first-generation kernel (whole value vector resident in VMEM,
one grid dimension over row tiles), this version runs a 2-D grid

    grid = (row tiles, action tiles * value-window tiles)

and streams *both* the table and the value vector:

  * idx/val/cost arrive in ``(TILE_N, TILE_M, K)`` / ``(TILE_N, TILE_M)``
    blocks — one action tile at a time, so wide-action MDPs no longer pull
    ``m`` whole action columns per row tile.
  * ``v`` arrives in ``(TILE_V,)`` windows.  Each window contributes the
    entries of the gathered dot whose column ids fall inside the window; a
    VMEM scratch block holds the per-(row, action, k) partials, so the final
    K-sum reduces in exactly ref.py's order (bit-identical accumulation).
    VMEM budget is now O(TILE_V + TILE_N * TILE_M * K) instead of O(n_cols).
  * running (min, argmin) scratch carries the best action across action
    tiles with a strict ``<`` — first minimum wins, preserving the exact
    smallest-index tie-break across tile boundaries.

The second grid dimension is the flattened (action tile, value window) pair
with the value window fastest, so each action tile's partial-dot scratch is
completed (all value windows) before the running min consumes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

DEFAULT_TILE_N = 256
DEFAULT_TILE_M = 16
DEFAULT_TILE_V = 128 * 1024


def _backup_kernel(gamma_ref, idx_ref, val_ref, cost_ref, v_ref,
                   out_v_ref, out_pi_ref,
                   part_ref, best_ref, arg_ref,
                   *, a_tiles: int, v_tiles: int, tile_m: int, tile_v: int):
    c = pl.program_id(1)
    a = c // v_tiles           # action tile
    j = c % v_tiles            # value window
    idx = idx_ref[...]
    val = val_ref[...]
    tn, tm, k = idx.shape
    dt = part_ref.dtype

    @pl.when(j == 0)
    def _init_partials():
        part_ref[...] = jnp.zeros_like(part_ref)

    # Accumulate this value window's share of the gathered dot.  Every
    # (row, action, k) slot is owned by exactly one window (the one holding
    # its column id), so `where` never double-counts and the K-sum below
    # reduces in ref.py's exact order.
    lo = j * tile_v
    local = idx - lo
    in_window = (local >= 0) & (local < tile_v)
    vblk = v_ref[...]
    safe = jnp.clip(local, 0, tile_v - 1)
    gathered = jnp.take(vblk, safe.reshape(tn, tm * k), axis=0).reshape(
        tn, tm, k)
    contrib = val.astype(dt) * gathered.astype(dt)
    part_ref[...] = jnp.where(in_window, contrib, part_ref[...])

    @pl.when(j == v_tiles - 1)
    def _reduce_actions():
        gamma = gamma_ref[0, 0]
        pv = ref.ksum(part_ref[...])
        # pin_rounding matches ref.ell_qvalues' pinned double rounding of
        # cost + gamma*pv (see ref.py); plain jnp ops, so it lowers on every
        # Pallas backend.
        q = cost_ref[...].astype(dt) + ref.pin_rounding(gamma * pv)
        tile_best = jnp.min(q, axis=-1)
        tile_arg = jnp.argmin(q, axis=-1).astype(jnp.int32) + a * tile_m

        @pl.when(a == 0)
        def _():
            best_ref[...] = tile_best
            arg_ref[...] = tile_arg

        @pl.when(a > 0)
        def _():
            hit = tile_best < best_ref[...]
            best_ref[...] = jnp.where(hit, tile_best, best_ref[...])
            arg_ref[...] = jnp.where(hit, tile_arg, arg_ref[...])

        @pl.when(a == a_tiles - 1)
        def _():
            out_v_ref[...] = best_ref[...]
            out_pi_ref[...] = arg_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tile_n", "tile_m", "tile_v"))
def ell_backup(idx, val, cost, gamma, v, *, interpret: bool = False,
               tile_n: int = DEFAULT_TILE_N, tile_m: int = DEFAULT_TILE_M,
               tile_v: int = DEFAULT_TILE_V):
    """Fused backup on an ELL block -> ``(min_a Q (n,), argmin_a Q (n,) i32)``."""
    n, m, k = idx.shape
    n_cols = v.shape[0]
    tn = min(tile_n, n)
    tm = min(tile_m, m)
    tv = min(tile_v, n_cols)
    dt = jnp.result_type(jnp.float32, val.dtype, v.dtype)

    pad_n = (-n) % tn
    pad_m = (-m) % tm
    pad_v = (-n_cols) % tv
    if pad_n or pad_m:
        idx = jnp.pad(idx, ((0, pad_n), (0, pad_m), (0, 0)))
        val = jnp.pad(val, ((0, pad_n), (0, pad_m), (0, 0)))
        # Padded action columns get +inf cost so they can never win the min;
        # padded rows are sliced off below.
        cost = jnp.pad(cost, ((0, pad_n), (0, pad_m)),
                       constant_values=jnp.inf)
    if pad_v:
        v = jnp.pad(v, (0, pad_v))
    n_pad, m_pad, v_pad = n + pad_n, m + pad_m, n_cols + pad_v

    a_tiles = m_pad // tm
    v_tiles = v_pad // tv
    gamma_arr = jnp.full((1, 1), gamma, dt)
    out_v, out_pi = pl.pallas_call(
        functools.partial(_backup_kernel, a_tiles=a_tiles, v_tiles=v_tiles,
                          tile_m=tm, tile_v=tv),
        grid=(n_pad // tn, a_tiles * v_tiles),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, c: (0, 0)),
            pl.BlockSpec((tn, tm, k),
                         lambda i, c, vt=v_tiles: (i, c // vt, 0)),
            pl.BlockSpec((tn, tm, k),
                         lambda i, c, vt=v_tiles: (i, c // vt, 0)),
            pl.BlockSpec((tn, tm), lambda i, c, vt=v_tiles: (i, c // vt)),
            pl.BlockSpec((tv,), lambda i, c, vt=v_tiles: (c % vt,)),
        ],
        out_specs=[
            pl.BlockSpec((tn,), lambda i, c: (i,)),
            pl.BlockSpec((tn,), lambda i, c: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), dt),
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, tm, k), dt),
            pltpu.VMEM((tn,), dt),
            pltpu.VMEM((tn,), jnp.int32),
        ],
        interpret=interpret,
    )(gamma_arr, idx, val, cost, v)
    return out_v[:n], out_pi[:n]


def ell_qvalues(idx, val, cost, gamma, v, *, interpret: bool = False,
                tile_n: int = DEFAULT_TILE_N, tile_v: int = DEFAULT_TILE_V):
    """Q-table variant (kept for parity with ref; the fused form is preferred)."""
    from repro.kernels import spmv_ell
    n, m, k = idx.shape
    pv = spmv_ell.ell_matvec(idx.reshape(n * m, k), val.reshape(n * m, k), v,
                             interpret=interpret, tile_n=tile_n,
                             tile_v=tile_v)
    return cost.astype(pv.dtype) + gamma * pv.reshape(n, m)
