"""Jit'd dispatch layer over the kernels.

Implementations:
  * ``"auto"``             — pick per backend: ``xla`` on TPU, ``blocked``
                             elsewhere; the blocked scan chunk comes from the
                             autotuner (:mod:`repro.kernels.tuning`).  The
                             default.
  * ``"xla"``              — pure-jnp reference (ref.py), one fused chain,
                             rows-last so the TPU keeps it lane-dense.
  * ``"blocked"``          — cache-blocked XLA: row-chunked scan whose chunk
                             working set stays in cache (ref.py blocked
                             variants; bit-identical to ``xla``).
  * ``"pallas"``           — Pallas TPU kernels.  Mosaic refuses them on a
                             real TPU (their in-kernel ``jnp.take`` is a 1-D
                             gather, Mosaic lowers only 2-D ones), and the
                             refusal is raised as is: nothing falls back.
  * ``"pallas_interpret"`` — Pallas kernels run through the interpreter
                             (CPU-correctness validation; used by tests).

The distributed solver calls these entry points; switching ``impl`` swaps the
compute engine without touching solver logic.  Tile sizes (the scan chunk of
``blocked``, the Pallas grid tiles) are Python ints resolved at trace time:
explicit keyword > autotuner cache > default.

Batched fleets: every entry point also accepts a leading batch dim ``B`` on
its table arguments (``val``/``cost``/``p`` rank +1; ``idx`` batched or
shared across instances; ``v``/``x`` batched ``(B, n)`` or shared ``(n,)``)
and vmaps the per-instance kernel — so the same Pallas/XLA kernels serve
multi-instance solves without a batched reimplementation.  A size-1 batch
dim — the common device-local shape under the fleet-sharded layouts, where
each fleet shard owns ``B / fleet_size`` instances — is squeezed and run
through the unbatched kernel directly instead of a 1-lane vmap.  The
autotuner sees the device-local (post-squeeze / per-lane) shape, so fleet
layouts resolve the same tiles as a single-instance solve of the same size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import trace

from . import ref, tuning

_DEFAULT_IMPL = "auto"
_VALID = ("auto", "xla", "blocked", "pallas", "pallas_interpret")

# What "auto" runs, per backend.  TPU: the blocked scan slices rows off the
# lane axis of the TPU's (n, m, K) layout and pads each chunk 16x, which
# does not fit a real table; the rows-last fused chain does.
AUTO_IMPL = {"tpu": "xla"}
AUTO_IMPL_DEFAULT = "blocked"

# Scan-chunk candidates for the blocked implementation (rows per chunk).
BLOCK_ROWS_CANDIDATES = (31_250, 62_500, 125_000, 250_000, 500_000)

# Cap on synthetic tuning data (elements), so tuning a huge solve does not
# allocate a huge benchmark table; block_rows choices transfer downward.
_MAX_BENCH_ELEMS = 1 << 26


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in _VALID, impl
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    return _DEFAULT_IMPL


def resolve_impl(impl: str | None = None) -> str:
    """The implementation ``impl`` (default: the process default) runs as
    on this backend."""
    impl = impl or _DEFAULT_IMPL
    assert impl in _VALID, impl
    if impl == "auto":
        return AUTO_IMPL.get(jax.default_backend(), AUTO_IMPL_DEFAULT)
    return impl


def _ax(arr, batched_ndim: int):
    """vmap in_axis for an optionally-batched operand."""
    return 0 if arr.ndim == batched_ndim else None


def _sq(arr, batched_ndim: int):
    """Squeeze a (size-1) leading batch dim off an optionally-batched
    operand — the B_local == 1 fast path of the fleet-sharded layouts."""
    return arr[0] if arr.ndim == batched_ndim else arr


# ---------------------------------------------------------------------------
# Trace-time tile resolution
# ---------------------------------------------------------------------------


def _backend() -> str:
    return jax.default_backend()


def _bench_shape(n: int, m: int, k: int) -> int:
    """Benchmark row count: the real n, capped so synthetic data stays small."""
    per_row = max(1, m * k)
    return max(1, min(n, _MAX_BENCH_ELEMS // per_row))


def _block_rows_default(n: int) -> int:
    return min(ref.DEFAULT_BLOCK_ROWS, max(1, n))


def _tuned_block_rows(kernel: str, n: int, m: int, k: int, n_cols: int,
                      dtype, bench_builder) -> int:
    """Resolve the blocked-impl scan chunk: autotuner cache, else timed
    search over BLOCK_ROWS_CANDIDATES, else the default."""
    n_bench = _bench_shape(n, m, k)
    cands = sorted({c for c in BLOCK_ROWS_CANDIDATES if c <= n_bench}
                   | {_block_rows_default(n_bench)})
    bench = None
    if tuning.enabled() and n * m * k >= tuning.MIN_TUNE_ELEMS:
        bench = bench_builder(n_bench, m, k, min(n_cols, n_bench), dtype)
    choice = tuning.tune(kernel, _backend(), n, m, k, np.dtype(dtype).name,
                         cands, _block_rows_default(n), bench)
    return int(min(choice, n)) if n else 1


def _make_backup_bench(n, m, k, n_cols, dtype):
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, n_cols, (n, m, k)).astype(np.int32))
    val = jnp.asarray(rng.random((n, m, k)).astype(dtype))
    cost = jnp.asarray(rng.random((n, m)).astype(dtype))
    v = jnp.asarray(rng.random(n_cols).astype(dtype))

    def bench(block_rows):
        fn = jax.jit(functools.partial(ref.ell_backup_blocked,
                                       block_rows=int(block_rows)))
        return tuning.measure(lambda: fn(idx, val, cost, 0.99, v))

    return bench


def _make_matvec_bench(n, k, _unused_m, n_cols, dtype):
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, n_cols, (n, k)).astype(np.int32))
    val = jnp.asarray(rng.random((n, k)).astype(dtype))
    x = jnp.asarray(rng.random(n_cols).astype(dtype))

    def bench(block_rows):
        fn = jax.jit(functools.partial(ref.ell_matvec_blocked,
                                       block_rows=int(block_rows)))
        return tuning.measure(lambda: fn(idx, val, x))

    return bench


def backup_block_rows(n: int, m: int, k: int, n_cols: int, dtype) -> int:
    """Trace-time scan-chunk choice for the blocked fused backup."""
    return _tuned_block_rows("ell_backup_blocked", n, m, k, n_cols, dtype,
                             _make_backup_bench)


def matvec_block_rows(n: int, k: int, n_cols: int, dtype) -> int:
    """Trace-time scan-chunk choice for the blocked policy SpMV."""
    return _tuned_block_rows(
        "ell_matvec_blocked", n, 1, k, n_cols, dtype,
        lambda nb, _m, kb, nc, dt: _make_matvec_bench(nb, kb, _m, nc, dt))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _ell_backup(idx, val, cost, gamma, v, impl, block_rows):
    if impl == "xla":
        return ref.ell_backup(idx, val, cost, gamma, v)
    if impl == "blocked":
        n, m, k = idx.shape
        bn = block_rows or backup_block_rows(n, m, k, v.shape[0], val.dtype)
        return ref.ell_backup_blocked(idx, val, cost, gamma, v,
                                      block_rows=bn)
    from . import bellman_ell
    return bellman_ell.ell_backup(idx, val, cost, gamma, v,
                                  interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
@trace.scoped(trace.BACKUP)
def ell_backup(idx, val, cost, gamma, v, *, impl: str | None = None,
               block_rows: int | None = None):
    """Fused Bellman backup on an ELL block -> (v_new (n,), argmin (n,) int32)."""
    impl = resolve_impl(impl)
    if val.ndim == 4:
        if val.shape[0] == 1:
            tv, am = _ell_backup(_sq(idx, 4), val[0], cost[0], gamma,
                                 _sq(v, 2), impl, block_rows)
            return tv[None], am[None]
        fn = lambda i, vl, c, vv: _ell_backup(i, vl, c, gamma, vv, impl,
                                              block_rows)
        return jax.vmap(fn, in_axes=(_ax(idx, 4), 0, 0, _ax(v, 2)))(
            idx, val, cost, v)
    return _ell_backup(idx, val, cost, gamma, v, impl, block_rows)


def ell_backup_chunk(idx, val, cost, gamma, v, *, impl: str | None = None):
    """Un-jitted fused backup on ONE row chunk — the matrix-free tile body.

    The matrix-free operator rebuilds row tiles inside an already-traced
    scan, so this entry point skips the jit wrapper and the chunk-level
    re-blocking of :func:`ell_backup` (the caller owns the tiling) while
    dispatching to the same per-implementation math:

    * ``"xla"``     — ``ref.ell_backup`` (jnp.min/argmin chain);
    * ``"blocked"`` — the exact per-chunk body of ``ref.ell_backup_blocked``
      (``rowmin_argmin`` over ``ell_qvalues`` — bit-identical to ``"xla"``);
    * ``"pallas"``/``"pallas_interpret"`` — the Pallas kernel on the chunk.

    Bit-identical to running the materialized kernel over the same rows:
    the math is row-independent, so any chunking yields the same bits.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.ell_backup(idx, val, cost, gamma, v)
    if impl == "blocked":
        return ref.rowmin_argmin(ref.ell_qvalues(idx, val, cost, gamma, v))
    from . import bellman_ell
    return bellman_ell.ell_backup(idx, val, cost, gamma, v,
                                  interpret=(impl == "pallas_interpret"))


def _ell_qvalues(idx, val, cost, gamma, v, impl, block_rows):
    if impl == "xla":
        return ref.ell_qvalues(idx, val, cost, gamma, v)
    if impl == "blocked":
        n, m, k = idx.shape
        bn = block_rows or backup_block_rows(n, m, k, v.shape[0], val.dtype)
        return ref.ell_qvalues_blocked(idx, val, cost, gamma, v,
                                       block_rows=bn)
    from . import bellman_ell
    return bellman_ell.ell_qvalues(idx, val, cost, gamma, v,
                                   interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
@trace.scoped(trace.BACKUP)
def ell_qvalues(idx, val, cost, gamma, v, *, impl: str | None = None,
                block_rows: int | None = None):
    impl = resolve_impl(impl)
    if val.ndim == 4:
        if val.shape[0] == 1:
            return _ell_qvalues(_sq(idx, 4), val[0], cost[0], gamma,
                                _sq(v, 2), impl, block_rows)[None]
        fn = lambda i, vl, c, vv: _ell_qvalues(i, vl, c, gamma, vv, impl,
                                               block_rows)
        return jax.vmap(fn, in_axes=(_ax(idx, 4), 0, 0, _ax(v, 2)))(
            idx, val, cost, v)
    return _ell_qvalues(idx, val, cost, gamma, v, impl, block_rows)


def _ell_matvec(idx, val, x, impl, block_rows):
    if impl == "xla":
        return ref.ell_matvec(idx, val, x)
    if impl == "blocked":
        n, k = idx.shape
        bn = block_rows or matvec_block_rows(n, k, x.shape[0], val.dtype)
        return ref.ell_matvec_blocked(idx, val, x, block_rows=bn)
    from . import spmv_ell
    return spmv_ell.ell_matvec(idx, val, x,
                               interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_rows"))
@trace.scoped(trace.SPMV)
def ell_matvec(idx, val, x, *, impl: str | None = None,
               block_rows: int | None = None):
    """Policy-restricted SpMV y = P_pi @ x on (n, K) ELL rows."""
    impl = resolve_impl(impl)
    if val.ndim == 3:
        if val.shape[0] == 1:
            return _ell_matvec(_sq(idx, 3), val[0], _sq(x, 2), impl,
                               block_rows)[None]
        fn = lambda i, vl, xx: _ell_matvec(i, vl, xx, impl, block_rows)
        return jax.vmap(fn, in_axes=(_ax(idx, 3), 0, _ax(x, 2)))(idx, val, x)
    return _ell_matvec(idx, val, x, impl, block_rows)


def _dense_backup(p, cost, gamma, v, impl):
    # The dense path has no blocked variant; cache-blocking a dense matmul is
    # XLA's own job, so "blocked" falls back to the reference chain.
    if impl in ("xla", "blocked"):
        return ref.dense_backup(p, cost, gamma, v)
    from . import dense_backup as dense_backup_kernel
    return dense_backup_kernel.dense_backup(p, cost, gamma, v,
                                            interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
@trace.scoped(trace.BACKUP)
def dense_backup(p, cost, gamma, v, *, impl: str | None = None):
    impl = resolve_impl(impl)
    if p.ndim == 4:
        if p.shape[0] == 1:
            tv, am = _dense_backup(p[0], cost[0], gamma, _sq(v, 2), impl)
            return tv[None], am[None]
        fn = lambda pp, c, vv: _dense_backup(pp, c, gamma, vv, impl)
        return jax.vmap(fn, in_axes=(0, 0, _ax(v, 2)))(p, cost, v)
    return _dense_backup(p, cost, gamma, v, impl)
