"""Shared pieces of the fault tests: small cells, a run on the CPU, and the
faults planted under the timed path."""

import time

import jax.numpy as jnp
import numpy as np

from bench import harness

SOLVE = ("garnet_1m.solve", {"n": 2048}, {})


def run(cell, seconds=1.0):
    workload, config, traffic = cell
    return harness.run_cell(workload, 3000000007, seconds, False,
                            t_start=time.monotonic(), require_chip=False,
                            persistent_cache=False, config_override=config,
                            traffic_override=traffic)


def state_unchanged(monkeypatch):
    from repro.core import ipi

    def frozen(mdp, state, opts, axes, gamma_t):
        return (state.v, state.tv, state.pi, state.res, state.span,
                jnp.int32(0), state.win)

    monkeypatch.setattr(ipi, "_outer_core", frozen)


def answer_altered(monkeypatch):
    from repro.core import driver

    real = driver._result

    def altered(*args, **kwargs):
        r = real(*args, **kwargs)
        v = np.array(r.v)
        v[len(v) // 2] += 0.01
        r.v = v
        return r

    monkeypatch.setattr(driver, "_result", altered)


def exchange_left_out(monkeypatch):
    """Each chip uses its own block of the values where it should have
    the whole vector (its block repeated)."""
    from repro.core.comm import Axes

    monkeypatch.setattr(Axes, "allgather_state", lambda self, x, dtype=None: (
        x if self.state is None
        else jnp.concatenate([x] * self.state_size())))


def control(monkeypatch):
    """The control in the program's place: every answer the solve returns
    is replaced by one bfloat16 backup of its values on the solved table
    (``reference.control_answer``), as a lower-precision solver would end."""
    import jax.numpy as jnp

    from bench import reference
    from repro.core import driver

    real = driver.solve

    def lower(mdp, *args, **kwargs):
        r = real(mdp, *args, **kwargs)
        n = len(r.v)
        table = tuple(jnp.asarray(a)[:n] for a in (mdp.idx, mdp.val,
                                                   mdp.cost))
        r.v, r.policy = reference.control_answer(table, mdp.gamma, r.v)
        return r

    monkeypatch.setattr(driver, "solve", lower)
