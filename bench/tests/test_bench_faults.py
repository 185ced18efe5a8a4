"""A solve run with the timed path broken underneath reads ``correct`` false.

Each test drives a whole run of a cell (set-up, window, reference) on the
CPU at a small size, with the harness's look for a chip skipped, once for
each fault the solve cells can have: a step that returns its state
unchanged, an answer altered where it is produced, the lower-precision
control in the program's place, and (on four virtual devices) the value
exchange between chips left out.  The four-chip cell's own configuration
(``1d``) meets each of them on four virtual devices.  A sound run of the
same size reads ``correct`` true.
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
import pytest
from bench import harness
from bench.tests import faults
if sys.argv[2] == "broken":
    faults.exchange_left_out(pytest.MonkeyPatch())
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


_FOUR_CHIP = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import pytest
from bench import counts, harness
from bench.drivers import solve_loop
from bench.tests import faults
fault = sys.argv[2]
if fault != "sound":
    getattr(faults, fault)(pytest.MonkeyPatch())
# the CPU is no device of the peak table: read the chip's entry instead
real = counts.peaks
counts.peaks = lambda kind: real("TPU v5 lite")
facts = {}
run = solve_loop.run
def spy(ctx):
    out = run(ctx)
    facts.update(out.facts)
    return out
solve_loop.run = spy
line = harness.run_cell("garnet_16m_x4.solve", 3000001597 + (1 << 32), 1.0,
                        sys.argv[3] == "1", t_start=time.monotonic(),
                        require_chip=False, persistent_cache=False,
                        config_override={"n": 4096})
print(json.dumps({"line": line, "kernels": facts.get("kernels"),
                  "exchange": facts.get("exchange")}))
"""


def _four_chip(fault, traced):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR_CHIP, ROOT, fault,
                           str(int(traced))],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["sound", "exchange_left_out",
                                   "state_unchanged", "answer_altered",
                                   "control"])
def test_four_chip_cell_faults(fault):
    """``garnet_16m_x4.solve`` at n = 4096 on four virtual CPU devices,
    its own ``1d`` configuration: sound, it reads ``correct`` true; with
    each fault planted under the timed path, false."""
    got = _four_chip(fault, traced=False)
    line = got["line"]
    assert line["device"]["count"] == 4
    assert line["correct"] is (fault == "sound"), line


def test_four_chip_traced_run_records_the_shard_counts():
    """A traced run on a mesh records what the in-solve rooflines and the
    exchange's roofline read: one chip's rows gathering from the whole
    vector, and the bytes one chip receives per all-gather."""
    from bench import counts

    got = _four_chip("sound", traced=True)
    assert got["line"]["correct"] is True
    n, m, k, rows = 4096, 16, 8, 1024
    assert got["kernels"] == {
        "backup": {"flops": counts.backup_flops(rows, m, k),
                   "bytes": counts.backup_bytes(rows, m, k, values=n)},
        "spmv": {"flops": counts.spmv_flops(rows, k),
                 "bytes": counts.spmv_bytes(rows, k, values=n)}}
    assert got["exchange"] == {"bytes": (n - rows) * 4}

