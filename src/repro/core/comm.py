"""Collective-axis abstraction for the distributed MDP solver.

madupite distributes states across MPI ranks and lets PETSc insert the
communication (VecScatter for SpMV halo exchange, MPI_Allreduce for Krylov
dot products).  The TPU adaptation expresses the same pattern with named mesh
axes inside ``shard_map``:

* ``state`` axis — states are row-partitioned; moving ``v`` is an
  ``all_gather``; norms / dots are ``psum`` / ``pmax``.
* ``action`` axis — optional 2-D layout (beyond the paper): actions are
  column-partitioned; the greedy step finishes with a min/argmin reduction.
* ``fleet`` axis — fleet-sharded batched solves: the leading instance dim of
  a :func:`repro.core.driver.solve_many` fleet is partitioned across this
  axis (each device owns ``B / fleet_size`` instances on top of its state
  slice).  The solver body needs no fleet collectives — instances are
  independent — except the loop-convergence decision, which all-reduces the
  per-instance active mask so every fleet shard runs the same number of
  ``lax.while_loop`` iterations (frozen shards spin no-op iterations).

When an axis name is ``None`` the collective degenerates to the identity, so
the identical solver code runs on a single device (tests, small problems).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.utils import trace


AxisName = Union[str, Sequence[str], None]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Axes:
    """Mesh axis names used by the solver (all static metadata)."""

    state: AxisName = dataclasses.field(default=None, metadata=dict(static=True))
    action: AxisName = dataclasses.field(default=None, metadata=dict(static=True))
    fleet: AxisName = dataclasses.field(default=None, metadata=dict(static=True))

    # ---- state-axis collectives -------------------------------------------------
    @trace.scoped(trace.EXCHANGE)
    def allgather_state(self, x: jax.Array, dtype=None) -> jax.Array:
        """Gather the value vector across state shards (PETSc VecScatter
        analogue).  ``dtype`` optionally compresses the wire format (e.g.
        bf16): the inexact-gather optimization — the iPI forcing term absorbs
        the quantization error in *inner* matvecs (EXPERIMENTS.md §Perf)."""
        if dtype is not None:
            x = x.astype(dtype)
        if self.state is None:
            return x
        return jax.lax.all_gather(x, self.state, axis=0, tiled=True)

    @trace.scoped(trace.EXCHANGE)
    def halo_exchange(self, x: jax.Array, halo: int, dtype=None) -> jax.Array:
        """Exchange ``halo`` boundary entries with ring neighbours instead of
        all-gathering the full vector — the TPU analogue of PETSc's
        VecScatter moving only the referenced columns.  Valid when the
        transition matrix is banded with bandwidth <= halo (validated at
        partition time).  Returns the local window
        ``[start - halo, stop + halo)`` (ends wrap with garbage that banded
        instances never reference).  Collective volume: 2*halo vs n_global.
        """
        if dtype is not None:
            x = x.astype(dtype)
        if halo == 0:
            return x
        if self.state is None:
            # single-shard window with the same ring semantics (edges unused)
            return jnp.concatenate([x[-halo:], x, x[:halo]], axis=0)
        n = self.state_size()
        fwd = [(i, (i + 1) % n) for i in range(n)]   # data flows ->
        bwd = [(i, (i - 1) % n) for i in range(n)]
        # my left halo = left neighbour's tail (neighbour sends forward)
        left = jax.lax.ppermute(x[-halo:], self.state, fwd)
        right = jax.lax.ppermute(x[:halo], self.state, bwd)
        return jnp.concatenate([left, x, right], axis=0)

    # ---- split-phase window movement (communication/computation overlap) --------
    def gather_start(self, x: jax.Array, *, halo: int = 0, dtype=None) -> jax.Array:
        """Issue the value-window collective (all-gather, or halo ring when
        ``halo > 0``) and return the in-flight window.

        JAX has no explicit request object; the split-phase contract is
        structural: the returned array is the *only* data dependence on the
        collective, so any compute issued between :meth:`gather_start` and
        :meth:`gather_finish` that does not touch it is free to overlap.
        With async collectives enabled (``-xla_flag_bundle
        cpu-overlap`` / ``tpu-collectives``) XLA splits the op into a
        ``-start``/``-done`` pair and the latency-hiding scheduler moves the
        independent compute between them.
        """
        if halo:
            return self.halo_exchange(x, halo, dtype=dtype)
        return self.allgather_state(x, dtype=dtype)

    def gather_finish(self, window: jax.Array) -> jax.Array:
        """Close the split-phase window started by :meth:`gather_start`.

        A no-op data-wise (the dependence edge on ``window`` is the real
        synchronization); kept as an explicit call so call sites read like
        MPI_Isend/MPI_Wait and so a future backend can hang a barrier here.
        """
        return window

    def psum_state(self, x):
        if self.state is None:
            return x
        return jax.lax.psum(x, self.state)

    def pmax_state(self, x):
        if self.state is None:
            return x
        return jax.lax.pmax(x, self.state)

    def state_index(self) -> jax.Array:
        if self.state is None:
            return jnp.int32(0)
        return jax.lax.axis_index(self.state)

    def state_size(self) -> int:
        if self.state is None:
            return 1
        if isinstance(self.state, str):
            return jax.lax.axis_size(self.state)
        out = 1
        for name in self.state:
            out *= jax.lax.axis_size(name)
        return out

    # ---- fleet-axis collectives -------------------------------------------------
    def any_fleet(self, x: jax.Array) -> jax.Array:
        """Logical OR of a boolean across fleet shards (keeps the shared
        ``lax.while_loop`` in lockstep when instances converge on some shards
        before others)."""
        if self.fleet is None:
            return x
        return jax.lax.psum(x.astype(jnp.int32), self.fleet) > 0

    def fleet_index(self) -> jax.Array:
        if self.fleet is None:
            return jnp.int32(0)
        return jax.lax.axis_index(self.fleet)

    def pmax_fleet(self, x):
        if self.fleet is None:
            return x
        return jax.lax.pmax(x, self.fleet)

    def allgather_fleet(self, x: jax.Array) -> jax.Array:
        """Gather per-instance rows across fleet shards (the monitor's
        fleet-wide record; instances are otherwise independent)."""
        if self.fleet is None:
            return x
        return jax.lax.all_gather(x, self.fleet, axis=0, tiled=True)

    # ---- action-axis collectives ------------------------------------------------
    def pmin_action(self, x):
        if self.action is None:
            return x
        return jax.lax.pmin(x, self.action)

    def psum_action(self, x):
        if self.action is None:
            return x
        return jax.lax.psum(x, self.action)

    def action_index(self) -> jax.Array:
        if self.action is None:
            return jnp.int32(0)
        return jax.lax.axis_index(self.action)

    # ---- derived linear-algebra helpers ------------------------------------------
    def dot(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """Distributed <x, y> over state shards (MPI_Allreduce analogue)."""
        return self.psum_state(jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST))

    def norm2(self, x: jax.Array) -> jax.Array:
        return jnp.sqrt(jnp.maximum(self.dot(x, x), 0.0))

    def norm_inf(self, x: jax.Array) -> jax.Array:
        return self.pmax_state(jnp.max(jnp.abs(x)))
