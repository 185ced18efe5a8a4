"""Instance tables made on the device from the benchmark's seed.

The benchmark makes its own data so that the reference can read the same
tables without taking anything the program made.  A family
(``bench/families/<family>.py``) says how the rows of its table are drawn;
this module makes the whole table from that on the device, in one
compiled program, rows split over every axis of a mesh when one is given
(the ``1d`` layout's placement).

A family draws every row from a counter-based key, ``fold_in(key, row)``,
so a row's content does not depend on how the rows are split into chunks
or over devices.  The seeds enter as traced 32-bit words: one compiled
program serves every seed, so set-up finds it in the persistent cache
whatever ``--seed`` the run gets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# rows made per loop step: bounds the generator's temporaries to one
# chunk, so the peak device memory of a run is the table plus the solve
CHUNK_ROWS = 1 << 16


def seed_words(seed: int, stream: int = 0) -> tuple[np.ndarray, ...]:
    """``(lo, hi, stream)`` as uint32 scalars: the 64-bit seed split into
    words (``PRNGKey`` keeps only the low 32 bits of a Python int)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    return (np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32),
            np.uint32(stream))


def key(lo, hi, stream):
    """The PRNG key of ``seed_words``' three words (traced or not)."""
    k = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(k, stream)


def _block(rows_of, row0, n_rows: int):
    """Rows ``[row0, row0 + n_rows)`` of ``rows_of`` (int32 global row ids
    -> a tuple of arrays, one entry per row), made ``CHUNK_ROWS`` at a time
    into buffers updated in place."""
    chunk = min(CHUNK_ROWS, n_rows)
    if n_rows % chunk:
        raise ValueError(f"{n_rows} rows do not split into chunks of {chunk}")
    shapes = jax.eval_shape(rows_of, jnp.zeros(chunk, jnp.int32))
    out = tuple(jnp.zeros((n_rows,) + s.shape[1:], s.dtype) for s in shapes)

    def body(c, bufs):
        start = c * chunk
        rows = row0 + start + jnp.arange(chunk, dtype=jnp.int32)
        part = rows_of(rows)
        return tuple(jax.lax.dynamic_update_slice_in_dim(b, p, start, 0)
                     for b, p in zip(bufs, part))

    return jax.lax.fori_loop(0, n_rows // chunk, body, out)


@functools.lru_cache(maxsize=None)
def _program(make, n: int, mesh):
    """The compiled table of ``make``: ``make(*words)`` (called inside the
    program, on each device) returns the ``rows_of`` of ``_block``."""
    if mesh is None:
        return jax.jit(lambda *words: _block(make(*words), jnp.int32(0), n))
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if n % n_shards:
        raise ValueError(f"n={n} does not split over {n_shards} devices")
    n_local = n // n_shards

    def local(*words):
        idx = jnp.int32(0)
        for a in axes:          # row-major position of this device's block
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return _block(make(*words), idx * n_local, n_local)

    def sharded(*words):
        shapes = jax.eval_shape(
            lambda *w: make(*w)(jnp.zeros(1, jnp.int32)), *words)
        rows = tuple(P(axes, *[None] * (s.ndim - 1)) for s in shapes)
        return jax.shard_map(local, mesh=mesh, in_specs=(P(),) * len(words),
                             out_specs=rows, check_vma=False)(*words)

    return jax.jit(sharded)


def table(make, n: int, words: tuple, mesh=None):
    """The ``n``-row table of ``make`` on the device, made from ``words``
    (each a tuple of ``seed_words`` or None), rows sharded over every axis
    of ``mesh`` when one is given.  ``make`` keys the compiled program, so
    a family passes the same object for the same sizes."""
    return _program(make, n, mesh)(*words)
