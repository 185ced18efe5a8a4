"""Instance families: the solve driver builds the family its configuration
names.

GARNET's table is pinned to its bytes, on one device and under the ``1d``
layout on four virtual devices.  A banded family defined here, and
registered for the test alone, runs through a whole CPU run of the solve
driver: it reads ``correct`` true, keeps its band on every seed, gives the
solver the same work on every seed, and reads ``correct`` false under an
altered answer.
"""

import functools
import json
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness
from bench.drivers import solve_loop
from bench.tests import faults

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# sha256 of idx, val and cost of garnet_1m's instance at n = 4096, plain
# and renamed by the seed 3000001701 + 2**33
GARNET_4096 = {
    None: "1e720a3a67d4bf9810d1974ff9822430479a95f86e72235afeecebe326779730",
    11589936293:
        "f5f6fb2de3236e3ecf7a56fbf104682d6b01d0c6746b5dc7c7f1b0cdd56e8cf0",
}

_DIGESTS = r"""
import hashlib, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import numpy as np
from repro.api import Session
from bench.families.garnet import garnet
with Session({"-layout": sys.argv[2], "-verbose": False}) as s:
    mesh, _ = s.placement()
out = {}
for relabel in (None, 11589936293):
    t = garnet(3000000901, 4096, 16, 8, relabel=relabel, mesh=mesh)
    h = hashlib.sha256()
    for a in t:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    out[str(relabel)] = [h.hexdigest(), len(t[0].sharding.device_set)]
print(json.dumps(out))
"""


@pytest.mark.parametrize("layout,devices", [("single", 1), ("1d", 4)])
def test_garnet_table_is_pinned(layout, devices):
    """The same bytes for the same (instance_seed, seed, n, m, k) on one
    device and with its rows split over four (a child process: the device
    count is fixed before JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", _DIGESTS, ROOT, layout],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {str(k): [v, devices] for k, v in GARNET_4096.items()}


# ---------------------------------------------------------------- band walk

BAND = 1
P_FWD = 0.7


@functools.lru_cache(maxsize=None)
def _band_make(n: int):
    def make(words, seed_words):
        skey = gen.key(*seed_words)
        flip = jax.random.bernoulli(skey)
        swap = jnp.array([[1, 0], [0, 1]], jnp.int32)

        def one(r, s):
            left, right = jnp.clip(s - 1, 0, n - 1), jnp.clip(s + 1, 0, n - 1)
            ids = jnp.stack([jnp.stack([left, right]),
                             jnp.stack([right, left])])
            ids = jnp.where(flip, n - 1 - ids, ids)
            val = jnp.broadcast_to(jnp.array([P_FWD, 1 - P_FWD],
                                             jnp.float32), (2, 2))
            cost = jnp.where(s == 0, 0.0, 1.0) * jnp.ones(2, jnp.float32)
            slot = swap[jax.random.bernoulli(
                jax.random.fold_in(skey, r)).astype(jnp.int32)]
            return ids[:, slot], val[:, slot], cost

        def rows_of(rows):
            return jax.vmap(one)(rows, jnp.where(flip, n - 1 - rows, rows))
        return rows_of
    return make


def _band_table(cfg, seed, mesh):
    return gen.table(_band_make(cfg["n"]), cfg["n"],
                     (gen.seed_words(cfg["instance_seed"]),
                      gen.seed_words(seed, 1)), mesh)


def _band_family():
    """A chain walk, band 1: action 0 tries a step down, action 1 a step
    up, each taken with probability 0.7 (else the other way); each step
    costs 1 but at the target, state 0 (the program's ``chain_walk``, whose
    safeguarded iPI converges in 2 outer iterations at gamma 0.99; with the
    target inside the chain it stalls).  The run's seed mirrors the chain
    (s -> n-1-s) and swaps each row's successor slots: the same MDP under
    other names, whose band stays 1.  It keeps the order of the actions:
    where actions tie the first is greedy, and the first policy is all
    ties."""
    mod = types.ModuleType("bench.families.band_walk", _band_family.__doc__)
    mod.table = _band_table
    return mod


BAND_CONFIG = {"family": "band_walk", "n": 2048, "m": 2, "k": 2,
               "band": BAND, "options": {"-halo": BAND}}


@pytest.fixture
def band_walk(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.band_walk",
                        _band_family())


def _band_run(seed):
    return harness.run_cell("garnet_1m.solve", seed, 1.0, False,
                            t_start=time.monotonic(), require_chip=False,
                            persistent_cache=False,
                            config_override=BAND_CONFIG)


@pytest.mark.usefixtures("band_walk", "fresh_programs")
def test_band_family_is_correct():
    """A whole run: the driver builds the band family's table, the program
    checks its band (``-halo`` from the configuration's ``options``), and
    the reference passes every answer."""
    line = _band_run(3000001711 + (1 << 32))
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.usefixtures("band_walk", "fresh_programs")
def test_band_family_altered_answer_is_not_correct(monkeypatch):
    faults.answer_altered(monkeypatch)
    line = _band_run(3000001712)
    assert line["correct"] is False, line


_FOUR = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness
from bench.tests import test_bench_families as t
sys.modules["bench.families.band_walk"] = t._band_family()
line = harness.run_cell("garnet_1m.solve", 3000001715, 1.0, False,
                        t_start=time.monotonic(), require_chip=False,
                        persistent_cache=False,
                        config_override={**t.BAND_CONFIG, "layout": "1d"})
print(json.dumps(line))
"""


def test_band_family_on_four_devices_is_correct():
    """The band family's rows split over four virtual devices (``1d``),
    exchanged by the program's halo layout of width ``band``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR, ROOT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is True, line


@pytest.mark.parametrize("seed", [0, 1, 2, 3000001713, 3000001714 + (1 << 33),
                                  (1 << 64) - 1])
def test_band_family_keeps_its_band(seed):
    cfg = {"instance_seed": 3000000901, **BAND_CONFIG}
    idx, val, _ = (np.asarray(a) for a in _band_table(cfg, seed, None))
    rows = np.arange(cfg["n"]).reshape(-1, 1, 1)
    assert np.abs(idx - rows).max() <= BAND
    np.testing.assert_allclose(val.sum(-1), 1.0, atol=1e-6)


def test_band_family_gives_every_seed_the_same_work():
    from repro.api import MDP, Session

    cfg = {"instance_seed": 3000000901, "gamma": 0.99, **BAND_CONFIG}
    work = set()
    with Session({"-method": "ipi_gmres", "-dtype": "float32",
                  "-atol": 1e-3, "-layout": "single"}) as s:
        for seed in (3000001721, 3000001722, 3000001723 + (1 << 40)):
            idx, val, cost = _band_table(cfg, seed, None)
            r = s.solve(MDP.from_arrays(idx=idx, val=val, cost=cost,
                                        gamma=cfg["gamma"], validate=False))
            work.add((r.outer_iterations, r.inner_iterations))
    assert len(work) == 1, work


# ------------------------------------------------------------- the driver


def test_options_merge_over_the_configuration():
    with open(os.path.join(BENCH, "configs", "garnet_1m.json")) as f:
        cfg = json.load(f)
    base = {"-method": "ipi_gmres", "-dtype": "float32", "-atol": 0.001,
            "-layout": "single", "-verbose": False}
    assert solve_loop._options(cfg) == base
    more = {**cfg, "options": {"-halo": 3, "-layout": "1d"}}
    assert solve_loop._options(more) == {**base, "-halo": 3, "-layout": "1d"}


def test_a_configuration_without_a_family_is_an_error():
    ctx = types.SimpleNamespace(config={"name": "nameless", "n": 16})
    with pytest.raises(ValueError, match="names no family"):
        solve_loop.run(ctx)


@pytest.mark.usefixtures("fresh_programs")
def test_traced_run_times_the_family_kernels(monkeypatch):
    """A traced one-device run records the ELL kernels' facts through the
    garnet family's defaults, with the module names they are timed under."""
    from bench import counts

    real = counts.peaks
    monkeypatch.setattr(counts, "peaks", lambda kind: real("TPU v5 lite"))
    facts = {}
    run = solve_loop.run

    def spy(ctx):
        out = run(ctx)
        facts.update(out.facts)
        return out

    monkeypatch.setattr(solve_loop, "run", spy)
    line = harness.run_cell("garnet_1m.solve", 3000001716, 1.0, True,
                            t_start=time.monotonic(), require_chip=False,
                            persistent_cache=False,
                            config_override={"n": 2048})
    assert line["correct"] is True, line
    n, m, k = 2048, 16, 8
    assert facts["kernels"] == {
        "backup": {"flops": counts.backup_flops(n, m, k),
                   "bytes": counts.backup_bytes(n, m, k),
                   "module": "bench_backup", "calls": 3},
        "spmv": {"flops": counts.spmv_flops(n, k),
                 "bytes": counts.spmv_bytes(n, k),
                 "module": "bench_spmv", "calls": 3}}
