"""A solve run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run of a cell (set-up, window, reference) on the
CPU at a small size, with the harness's look for a chip skipped, once for
each fault the solve cells can have: a step that returns its state
unchanged, an answer altered where it is produced, the lower-precision
control in the program's place, and (on four virtual devices) the value
exchange between chips left out.  A sound run of the same size reads
``correct`` true.
"""

import json
import os
import subprocess
import sys

import pytest

from bench.tests import faults

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
pytestmark = pytest.mark.usefixtures("fresh_programs")


def test_sound_run_is_correct():
    line = faults.run(faults.SOLVE)
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("fault", [faults.state_unchanged,
                                   faults.answer_altered, faults.control],
                         ids=["state_unchanged", "answer_altered",
                              "control"])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = faults.run(faults.SOLVE)
    assert line["correct"] is False, line


_EXCHANGE = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax, jax.numpy as jnp
if sys.argv[2] == "broken":
    from repro.core.comm import Axes
    Axes.allgather_state = lambda self, x, dtype=None: (
        x if self.state is None else jnp.concatenate([x] * self.state_size()))
from bench import harness
line = harness.run_cell("garnet_1m.solve", 3000000009, 1.0, False,
                        t_start=time.monotonic(), require_chip=False,
                        persistent_cache=False,
                        config_override={"n": 4096, "layout": "1d"})
print(json.dumps(line))
"""


@pytest.mark.parametrize("mode", ["sound", "broken"])
def test_exchange_left_out_is_not_correct(mode):
    """The solve cell's path under the ``1d`` layout on four virtual CPU
    devices (the device count is fixed before JAX starts, so in a child
    process): the rows of the table and of the solve are split over the
    devices, and every backup all-gathers the values."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _EXCHANGE, ROOT, mode],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is (mode == "sound"), line
