"""GARNET tables made on the device from the benchmark's seed.

The benchmark makes its own data so that the reference can read the same
tables without taking anything the program made.  The distribution is
GARNET's (Archibald, McKinnon & Thomas 1995), as the program's own
``garnet`` generator draws it: ``k`` successors uniform over the ``n``
states, probabilities ``u + 1e-6`` normalised per (state, action), stage
costs uniform on ``[0, 1)``, all float32, successor ids int32.

Every row is drawn from a counter-based key, ``fold_in(key, row)``, so a
row's content does not depend on how the rows are split into chunks or
over devices.  The seeds enter as traced 32-bit words: one compiled
program serves every seed, so set-up finds it in the persistent cache
whatever ``--seed`` the run gets.

With ``relabel`` the instance is the one drawn from ``seed`` and the
states are renamed by a permutation drawn from ``relabel``: state ``s``
becomes ``perm[s]``, its row moves there and every successor id ``j``
becomes ``perm[j]``.  The MDP is the same up to names, so every relabeling
takes the same iterations to the same values (permuted); only the order
of long sums changes, and with it the last bits.
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


def _key(lo, hi, stream):
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, stream)


def _rows(key, rows, n: int, m: int, k: int, relabel=None):
    """The table rows ``rows`` (int32 global ids) -> (idx, val, cost);
    ``relabel = (perm, inv)`` renames the states (``inv`` undoes ``perm``)."""
    def one(r):
        k_ids, k_val, k_cost = jax.random.split(jax.random.fold_in(key, r), 3)
        ids = jax.random.randint(k_ids, (m, k), 0, n, dtype=jnp.int32)
        raw = jax.random.uniform(k_val, (m, k), jnp.float32) + \
            jnp.float32(1e-6)
        val = raw / jnp.sum(raw, axis=-1, keepdims=True)
        cost = jax.random.uniform(k_cost, (m,), jnp.float32)
        return ids, val, cost
    if relabel is None:
        return jax.vmap(one)(rows)
    perm, inv = relabel
    ids, val, cost = jax.vmap(one)(inv[rows])
    return perm[ids], val, cost


def _relabeling(key, n: int):
    """``(perm, inv)``: a uniform permutation of the ``n`` states and its
    inverse, int32."""
    perm = jax.random.permutation(key, n).astype(jnp.int32)
    inv = jnp.zeros(n, jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))
    return perm, inv


def _block(key, row0, n_rows: int, n: int, m: int, k: int, relabel=None):
    """Rows ``[row0, row0 + n_rows)``, made ``CHUNK_ROWS`` at a time into
    buffers updated in place."""
    chunk = min(CHUNK_ROWS, n_rows)
    if n_rows % chunk:
        raise ValueError(f"{n_rows} rows do not split into chunks of {chunk}")
    out = (jnp.zeros((n_rows, m, k), jnp.int32),
           jnp.zeros((n_rows, m, k), jnp.float32),
           jnp.zeros((n_rows, m), jnp.float32))

    def body(c, bufs):
        start = c * chunk
        rows = row0 + start + jnp.arange(chunk, dtype=jnp.int32)
        part = _rows(key, rows, n, m, k, relabel)
        return tuple(jax.lax.dynamic_update_slice_in_dim(b, p, start, 0)
                     for b, p in zip(bufs, part))

    return jax.lax.fori_loop(0, n_rows // chunk, body, out)


# the key stream of the relabeling, apart from the tables' stream 0
RELABEL_STREAM = 0xFFFFFFFF


def _relabel(words, n: int):
    return None if words is None else _relabeling(_key(*words), n)


@functools.lru_cache(maxsize=None)
def _single(n: int, m: int, k: int):
    def make(words, relabel_words):
        return _block(_key(*words), jnp.int32(0), n, n, m, k,
                      _relabel(relabel_words, n))
    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _sharded(n: int, m: int, k: int, mesh):
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if n % n_shards:
        raise ValueError(f"n={n} does not split over {n_shards} devices")
    n_local = n // n_shards

    def local(words, relabel_words):
        idx = jnp.int32(0)
        for a in axes:          # row-major position of this device's block
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return _block(_key(*words), idx * n_local, n_local, n, m, k,
                      _relabel(relabel_words, n))

    row = P(axes, None, None)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(row, row, P(axes, None)),
                                 check_vma=False))


def garnet(seed: int, n: int, m: int, k: int, *,
           relabel: int | None = None, mesh=None):
    """One GARNET table ``(idx (n, m, k) int32, val (n, m, k) f32,
    cost (n, m) f32)`` on the device, its states renamed by a permutation
    drawn from ``relabel`` when one is given, rows sharded over every axis
    of ``mesh`` when one is given (the ``1d`` layout's placement)."""
    words = seed_words(seed)
    relabel_words = None if relabel is None else \
        seed_words(relabel, RELABEL_STREAM)
    fn = _single(n, m, k) if mesh is None else _sharded(n, m, k, mesh)
    return fn(words, relabel_words)
