"""GARNET, the random MDP family (Archibald, McKinnon & Thomas 1995).

The distribution is as the program's own ``garnet`` generator draws it:
``k`` successors uniform over the ``n`` states, probabilities ``u + 1e-6``
normalised per (state, action), stage costs uniform on ``[0, 1)``, all
float32, successor ids int32.  The configuration's ``instance_seed`` draws
the instance; its keys are ``n``, ``m``, ``k``.

What the run's seed does: it renames the states by a permutation drawn
from it (stream ``RELABEL_STREAM``): state ``s`` becomes ``perm[s]``, its
row moves there and every successor id ``j`` becomes ``perm[j]``.  The MDP
is the same up to names, so every seed takes the same iterations to the
same values (permuted); only the order of long sums changes, and with it
the last bits.  GARNET declares no band: its successors lie anywhere, so
the renaming takes no locality from it.

The family keeps every default of ``bench/families/__init__.py``: the ELL
``MDP.from_arrays``, ``reference.host_blocks`` and the ELL kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import gen

# the key stream of the relabeling, apart from the tables' stream 0
RELABEL_STREAM = 0xFFFFFFFF


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


@functools.lru_cache(maxsize=None)
def _make(n: int, m: int, k: int):
    def make(words, relabel_words):
        relabel = None if relabel_words is None else \
            _relabeling(gen.key(*relabel_words), n)
        return functools.partial(_rows, gen.key(*words), n=n, m=m, k=k,
                                 relabel=relabel)
    return make


def garnet(seed: int, n: int, m: int, k: int, *,
           relabel: int | None = None, mesh=None):
    """One GARNET table ``(idx (n, m, k) int32, val (n, m, k) f32,
    cost (n, m) f32)`` on the device, its states renamed by a permutation
    drawn from ``relabel`` when one is given, rows sharded over every axis
    of ``mesh`` when one is given (the ``1d`` layout's placement)."""
    words = gen.seed_words(seed)
    relabel_words = None if relabel is None else \
        gen.seed_words(relabel, RELABEL_STREAM)
    return gen.table(_make(n, m, k), n, (words, relabel_words), mesh)


def table(cfg: dict, seed: int, mesh):
    """The configuration's instance, its states renamed by ``seed``."""
    return garnet(cfg["instance_seed"], cfg["n"], cfg["m"], cfg["k"],
                  relabel=seed, mesh=mesh)
