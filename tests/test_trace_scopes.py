"""The program's layer names (``repro.utils.trace``): device scopes reach
the compiled solve's ``op_name`` metadata and change nothing else, the
value exchange carries its scope on four virtual devices, and the
host-transfer counter counts the driver's blocking fetches."""

import contextlib
import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import IPIOptions, driver, generators, ipi
from repro.core.comm import Axes
from repro.utils import trace

SOLVE_SCOPES = (trace.BACKUP, trace.SPMV, trace.GMRES_CYCLE,
                trace.GMRES_RESIDUAL, trace.OUTER)


def _compiled_solve_text() -> str:
    mdp = generators.garnet(n=256, m=4, k=4, gamma=0.95, seed=0)
    opts = IPIOptions(method="ipi_gmres", atol=1e-6, dtype="float32")
    axes = Axes()
    state = ipi.init_state_jit(mdp, None, None, mdp.n_global, opts=opts,
                               axes=axes)
    return ipi.solve_chunk.lower(mdp, state, jnp.int32(64), jnp.int32(0),
                                 opts=opts, axes=axes).compile().as_text()


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


def _without_metadata(text: str) -> str:
    """The instructions of an HLO text with every ``metadata={...}`` and
    the stack-frame tables that only metadata points into removed."""
    body = text[text.index("\n%") if "\n%" in text else 0:]
    return re.sub(r",? metadata=\{[^}]*\}", "", body)


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    driver._clear_compiled()
    yield
    jax.clear_caches()
    driver._clear_compiled()


def test_solve_scopes_reach_the_compiled_program(fresh_programs):
    names = _op_names(_compiled_solve_text())
    for scope in SOLVE_SCOPES:
        assert any(scope in n.split("/") for n in names), scope
    # the SpMV runs inside the GMRES cycle, inside one outer iteration
    assert any(re.search(r"repro\.outer/.*repro\.gmres\.cycle/.*"
                         r"repro\.spmv(/|$)", n) for n in names)


def test_scopes_change_only_metadata(fresh_programs, monkeypatch):
    scoped = _compiled_solve_text()
    monkeypatch.setattr(trace, "scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    driver._clear_compiled()
    plain = _compiled_solve_text()
    assert not any("repro." in n for n in _op_names(plain))
    assert _without_metadata(scoped) == _without_metadata(plain)


_EXCHANGE = r"""
import json, os, re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.core import IPIOptions, driver, generators, partition
from repro.launch.mesh import mesh_kwargs

mdp = generators.garnet(n=256, m=4, k=4, gamma=0.95, seed=0)
opts = IPIOptions(method="ipi_gmres", atol=1e-6, dtype="float32")
mesh = jax.make_mesh((4,), ("data",), **mesh_kwargs(1))
dev, axes, n = partition.shard_mdp(mdp, mesh, "1d", mode=opts.mode)
opts = driver._resolve_overlap(opts, dev, mesh, axes)
run_chunk, init = driver._make_runners(dev, opts, mesh, axes, None, n_true=n)
text = run_chunk.lower(dev, init(None), jnp.int32(64),
                       jnp.int32(0)).compile().as_text()
gathers = [ln for ln in text.splitlines()
           if re.search(r" all-gather(-start)?\(", ln)]
print("RESULT " + json.dumps([re.search(r'op_name="([^"]*)"', ln).group(1)
                              if "op_name" in ln else "" for ln in gathers]))
"""


def test_exchange_scope_sits_on_the_all_gather():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", _EXCHANGE], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    names = json.loads(line[len("RESULT "):])
    assert names, "the 1d solve gathers the value vector"
    assert all(trace.EXCHANGE in n.split("/") for n in names), names


@pytest.fixture
def fetches(monkeypatch):
    """Blocking device-to-host fetches counted apart from the program: one
    per ``jax.device_get`` call on device arrays, plus one per device
    array read outside such a call (``int(x)``, ``np.asarray(x)``)."""
    from jax._src.array import ArrayImpl

    count = {"n": 0}
    local = threading.local()
    get, value = jax.device_get, ArrayImpl._value

    def device_get(x):
        if any(isinstance(a, jax.Array) for a in jax.tree_util.tree_leaves(x)):
            count["n"] += 1
        local.inside = True
        try:
            return get(x)
        finally:
            local.inside = False

    def read(self):
        if self._npy_value is None and not getattr(local, "inside", False):
            count["n"] += 1
        return value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(read))
    return count


@pytest.mark.parametrize("chunk", [1, 64])
def test_host_transfer_counter_counts_the_driver_fetches(fetches, chunk):
    mdp = generators.garnet(n=200, m=4, k=4, gamma=0.95, seed=1)
    opts = IPIOptions(method="ipi_gmres", atol=1e-6, dtype="float32")
    driver.solve(mdp, opts, chunk=chunk)              # compile outside
    before, fetched = trace.host_transfers(), fetches["n"]
    r = driver.solve(mdp, opts, chunk=chunk)
    delta = trace.host_transfers() - before
    assert delta == fetches["n"] - fetched
    # one control fetch before each run-chunk and one after the last,
    # then ten pieces of the result readback
    chunks = -(-r.outer_iterations // chunk)
    assert delta == chunks + 1 + 10
