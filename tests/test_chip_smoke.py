"""``chip_smoke.py`` on the CPU: every phase at a tiny size, the refusal to
run without a TPU, and the refusal to run outside a checkout.

The script's phase functions take their sizes as arguments, so the same
code the chip runs is exercised here end to end (through ``Session`` and
``Server``) at shapes the CPU handles in seconds.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.CompileClock()


def test_main_solve_and_parity(smoke, clock, capsys):
    core, r = smoke.phase_main(
        clock, dict(n=4096, m=16, k=8, gamma=0.99, seed=0), 1e-3)
    smoke.phase_parity(core, r, 1000)
    out = capsys.readouterr().out
    assert "independent_residual=" in out and "agrees_bitwise=True" in out
    assert "backup_values_equal=True" in out and "spmv_equal=True" in out


def test_reference_against_exact_policy_iteration(smoke, capsys):
    smoke.phase_reference(dict(n=256, m=8, k=8, gamma=0.99, seed=1), 1e-4)
    assert "value_err=" in capsys.readouterr().out


def test_exact_policy_iteration_is_optimal(smoke):
    """The host reference itself: its values satisfy the Bellman optimality
    equation in float64."""
    import numpy as np

    from repro.core import generators

    core = generators.garnet(64, 4, 3, gamma=0.9, seed=3)
    idx = np.asarray(core.idx)
    val = np.asarray(core.val, np.float64)
    cost = np.asarray(core.cost, np.float64)
    v, pi, q = smoke.exact_policy_iteration(idx, val, cost, 0.9)
    tv = (cost + 0.9 * np.einsum("sak,sak->sa", val, v[idx])).min(axis=1)
    assert np.abs(tv - v).max() < 1e-10
    assert (q[np.arange(64), pi] <= q.min(axis=1) + 1e-10).all()


def test_adaptive_phase(smoke, capsys):
    smoke.phase_adaptive(dict(n=2048, gamma=0.999), 1e-2)
    assert "chose=" in capsys.readouterr().out


def test_server_phase_bitwise(smoke, capsys):
    smoke.phase_server((300, 420, 300, 420), dict(m=8, k=8, gamma=0.95),
                       1e-4)
    assert "bitwise_equal=4" in capsys.readouterr().out


def test_check_raises_smoke_failure(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


_FOUR = textwrap.dedent("""
    import importlib.util, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {src!r})
    spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    cs.leg_four_chips(cs.CompileClock(),
                      dict(n=4096, m=16, k=8, gamma=0.99, seed=0),
                      dict(n=1 << 14, gamma=0.99))
    print("FOUR_OK")
""")


def test_four_chip_leg_on_virtual_devices():
    src = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(src=src, script=SCRIPT)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout
    assert "table_bytes_per_device={0:" in out.stdout
    assert "frontier_reach=1" in out.stdout     # the chain's ring exchange
    # mpi is bit-equal across layouts, for both instances
    assert out.stdout.count("method=mpi bitwise_equal=True") == 2


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _run_script(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run_script(str(alone), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "checkout" in out.stderr
