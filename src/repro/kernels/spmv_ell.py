"""Pallas TPU kernel: tiled streaming ELL SpMV (policy-restricted matvec).

The inner-solver hot spot: every Richardson sweep / Krylov iteration applies
``A_pi x = x - gamma * P_pi x`` and ``P_pi x`` is this kernel.  Same tiling
strategy as :mod:`repro.kernels.bellman_ell` — a 2-D grid over (row tiles,
value windows) streams both the (n, K) table and ``x`` through VMEM instead
of staging ``x`` whole.  A VMEM scratch block holds per-(row, k) partials so
the final K-sum reduces in ref.py's exact order (bit-identical accumulation);
each (row, k) slot is owned by exactly one value window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

DEFAULT_TILE_N = 512
DEFAULT_TILE_V = 128 * 1024


def _spmv_kernel(idx_ref, val_ref, x_ref, out_ref, part_ref,
                 *, v_tiles: int, tile_v: int):
    j = pl.program_id(1)
    idx = idx_ref[...]
    val = val_ref[...]
    tn, k = idx.shape
    dt = part_ref.dtype

    @pl.when(j == 0)
    def _init_partials():
        part_ref[...] = jnp.zeros_like(part_ref)

    lo = j * tile_v
    local = idx - lo
    in_window = (local >= 0) & (local < tile_v)
    xblk = x_ref[...]
    safe = jnp.clip(local, 0, tile_v - 1)
    gathered = jnp.take(xblk, safe.reshape(tn * k), axis=0).reshape(tn, k)
    contrib = val.astype(dt) * gathered.astype(dt)
    part_ref[...] = jnp.where(in_window, contrib, part_ref[...])

    @pl.when(j == v_tiles - 1)
    def _reduce():
        out_ref[...] = ref.ksum(part_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("interpret", "tile_n", "tile_v"))
def ell_matvec(idx, val, x, *, interpret: bool = False,
               tile_n: int = DEFAULT_TILE_N, tile_v: int = DEFAULT_TILE_V):
    """``y[i] = sum_k val[i, k] * x[idx[i, k]]`` for (n, K) ELL rows."""
    n, k = idx.shape
    n_cols = x.shape[0]
    tn = min(tile_n, n)
    tv = min(tile_v, n_cols)
    pad_n = (-n) % tn
    pad_v = (-n_cols) % tv
    if pad_n:
        idx = jnp.pad(idx, ((0, pad_n), (0, 0)))
        val = jnp.pad(val, ((0, pad_n), (0, 0)))
    if pad_v:
        x = jnp.pad(x, (0, pad_v))
    n_pad, v_pad = n + pad_n, n_cols + pad_v
    v_tiles = v_pad // tv
    dt = jnp.result_type(jnp.float32, val.dtype, x.dtype)
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, v_tiles=v_tiles, tile_v=tv),
        grid=(n_pad // tn, v_tiles),
        in_specs=[
            pl.BlockSpec((tn, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tv,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((tn,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), dt),
        scratch_shapes=[pltpu.VMEM((tn, k), dt)],
        interpret=interpret,
    )(idx, val, x)
    return out[:n]
