"""``bench/run.py`` refuses to run without the accelerator, and outside a
checkout, before doing any work: non-zero exit, no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(cwd, workload="garnet_1m.solve"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj), line


@pytest.mark.parametrize("workload", ["garnet_1m.solve", "garnet_16m_x4.solve"])
def test_refuses_the_cpu(workload):
    proc = _run(ROOT, workload)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    _no_result(proc)
    assert "src/repro" in proc.stderr


def test_unknown_workload_fails():
    proc = _run(ROOT, "no_such.cell")
    _no_result(proc)
