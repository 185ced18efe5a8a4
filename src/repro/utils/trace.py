"""The program's names for its own layers, as a profile sees them.

* **Device scopes** (:func:`scope`, ``jax.named_scope``) ride on the
  compiled program's ``op_name`` metadata: every HLO instruction traced
  inside one carries the scope in its name stack, and a profile's device
  rows carry that metadata.  A scope changes metadata and nothing else.
  It only names code that is traced inside a jitted program: around a
  top-level jit call the name stack does not enter the program.
* **Host spans** (:func:`span`, ``jax.profiler.TraceAnnotation``) mark
  what the host is doing, on the profiler's own clock, the clock of the
  device rows: an idle gap on the device can be put down to the span the
  host was in.
* **Host transfers**: one process-wide counter of the solve driver's
  blocking device-to-host fetches (each control fetch between run-chunks,
  each piece of the result readback).  Read it as a snapshot before and
  after a window (:func:`host_transfers`); :func:`counted_span` writes the
  delta over its span into the profile as the span's ``host_transfers``
  stat.

Every name starts with ``repro.``.  Nothing turns tracing on or off: a
scope is free at run time, and a host span costs about a microsecond
while no profile is being captured.

    with jax.profiler.trace("/tmp/profile"):
        session.solve(mdp)

records the spans and, on an accelerator, the device operations under
their scopes (``bench/scopes.py`` reduces such a profile to per-scope
device time and call counts).
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax

# device scopes
BACKUP = "repro.backup"                  # one Bellman backup
SPMV = "repro.spmv"                      # one policy SpMV (P_pi x)
GMRES_CYCLE = "repro.gmres.cycle"        # one Arnoldi cycle: CGS2, Givens,
                                         # update
GMRES_RESIDUAL = "repro.gmres.residual"  # b - A x and its norm
OUTER = "repro.outer"                    # one outer iteration: policy rows,
                                         # inner solve, evaluation
EXCHANGE = "repro.exchange"              # the value-vector exchange

# host spans
SOLVE = "repro.solve"                    # Session.solve
PROBE = "repro.probe"                    # the -method auto probe
DRIVER_INIT = "repro.driver.init"        # initial state (or restore)
DRIVER_DISPATCH = "repro.driver.dispatch"  # one run-chunk call
DRIVER_SYNC = "repro.driver.sync"        # the control fetch per chunk
DRIVER_READBACK = "repro.driver.readback"  # the result to the host

TRANSFERS_STAT = "host_transfers"


def scope(name: str):
    """A device scope: ``with scope(BACKUP): ...`` inside traced code."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator form of :func:`scope`: the function's body runs inside
    it each time the function is traced."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def span(name: str):
    """A host span: ``with span(DRIVER_SYNC): ...``."""
    return jax.profiler.TraceAnnotation(name)


_lock = threading.Lock()
_transfers = 0


def count_host_transfer() -> None:
    global _transfers
    with _lock:
        _transfers += 1


def host_transfers() -> int:
    """Blocking device-to-host fetches the solve driver has made in this
    process (all threads)."""
    return _transfers


@contextlib.contextmanager
def counted_span(name: str):
    """A host span that records the host transfers made while it was open
    as its ``host_transfers`` stat (process-wide: another thread's
    transfers in the same interval count too)."""
    before = _transfers
    with jax.profiler.TraceAnnotation(name) as ann:
        yield
        if ann.is_enabled():
            ann.set_metadata(**{TRANSFERS_STAT: _transfers - before})
