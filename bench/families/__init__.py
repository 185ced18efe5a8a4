"""Instance families of the solve cells.

A configuration's ``family`` names ``bench/families/<family>.py``, as a
traffic mix's ``kind`` names its driver.  The module's docstring says what
the run's ``--seed`` does to the instance: a family that declares a band
keeps ``|successor - row| <= band`` on every seed (the ``1d`` halo layout
measures it from the table), and every seed gives the solver the same
work, or the docstring says why it cannot.  Renaming the states, or the
successor slots of a row, keeps the work; reordering a row's actions does
not, since where actions tie the first is greedy.  The module defines

* ``table(cfg, seed, mesh)``: the instance on the device, as a tuple of
  arrays whose leading axis is the states, rows split over every axis of
  ``mesh`` when one is given (``bench.gen.table`` makes such a table from
  a row function);

and may define, where the ELL default below does not fit its table:

* ``mdp(cfg, table)``: the ``repro.api.MDP`` handed to ``Session.solve``;
* ``host_blocks(cfg, table)``: the ``(row0, idx, val, cost)`` numpy row
  blocks that ``reference.evaluate`` reads (ELL form whatever the table's);
* ``kernel_facts(cfg, table, rows, values)``: ``{kernel: {"flops",
  "bytes"}}`` of one call of each kernel the solve runs, over ``rows``
  table rows that gather from ``values`` entries (``{}`` records none);
* ``kernel_calls(ctx, cfg, table, v, pi)``: warms each kernel of
  ``kernel_facts`` and returns the call that runs it ``KERNEL_REPEATS``
  times alone, under the jit module name ``bench_<kernel>`` (None: no
  kernel is timed alone).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

KERNEL_REPEATS = 3


@dataclasses.dataclass(frozen=True)
class Family:
    """A family's hooks, each the module's own or the ELL default."""

    name: str
    table: Callable
    mdp: Callable
    host_blocks: Callable
    kernel_facts: Callable
    kernel_calls: Callable


def load(name: str) -> Family:
    """The family ``name`` with the ELL default in every hook it leaves
    out."""
    mod = importlib.import_module(f"{__name__}.{name}")
    return Family(
        name=name, table=mod.table,
        mdp=getattr(mod, "mdp", ell_mdp),
        host_blocks=getattr(mod, "host_blocks", ell_host_blocks),
        kernel_facts=getattr(mod, "kernel_facts", ell_kernel_facts),
        kernel_calls=getattr(mod, "kernel_calls", ell_kernel_calls))


def ell_mdp(cfg: dict, table):
    from repro.api import MDP

    idx, val, cost = table
    return MDP.from_arrays(idx=idx, val=val, cost=cost, gamma=cfg["gamma"],
                           validate=False)


def ell_host_blocks(cfg: dict, table):
    from bench import reference

    return reference.host_blocks(table)


def ell_kernel_facts(cfg: dict, table, rows: int, values: int) -> dict:
    """Operations and least bytes of one call of ``ops.ell_backup`` and
    ``ops.ell_matvec`` over ``rows`` table rows that gather from a vector
    of ``values`` entries."""
    from bench import counts

    _, m, k = table[0].shape
    return {
        "backup": {"flops": counts.backup_flops(rows, m, k),
                   "bytes": counts.backup_bytes(rows, m, k, values=values)},
        "spmv": {"flops": counts.spmv_flops(rows, k),
                 "bytes": counts.spmv_bytes(rows, k, values=values)},
    }


def ell_kernel_calls(ctx, cfg: dict, table, v, pi):
    """``ops.ell_backup`` and ``ops.ell_matvec`` (on the policy's rows)
    through the dispatch point, on the cell's table, from the solve's
    answer ``(v, pi)``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    idx, val, cost = table
    gamma = cfg["gamma"]

    @jax.jit
    def policy_rows(idx, val, pi):
        sel = pi[:, None, None]
        return (jnp.take_along_axis(idx, sel, axis=1)[:, 0],
                jnp.take_along_axis(val, sel, axis=1)[:, 0])

    def bench_backup(idx, val, cost, v):
        with jax.named_scope("bench_backup"):
            return ops.ell_backup(idx, val, cost, gamma, v)

    def bench_spmv(idx_pi, val_pi, v):
        with jax.named_scope("bench_spmv"):
            return ops.ell_matvec(idx_pi, val_pi, v)

    backup, spmv = jax.jit(bench_backup), jax.jit(bench_spmv)
    vj = jnp.asarray(v, jnp.float32)
    idx_pi, val_pi = policy_rows(idx, val, jnp.asarray(pi, jnp.int32))
    jax.block_until_ready((backup(idx, val, cost, vj),
                           spmv(idx_pi, val_pi, vj)))

    def timed():
        with ctx.span("kernels"):
            for _ in range(KERNEL_REPEATS):
                jax.block_until_ready(backup(idx, val, cost, vj))
            for _ in range(KERNEL_REPEATS):
                jax.block_until_ready(spmv(idx_pi, val_pi, vj))

    return timed
