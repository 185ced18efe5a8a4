"""The reduction from a profiler trace to numbers: interval arithmetic on
hand-made traces, and the whole reduction on a small trace recorded on a
TPU v5e chip (``data/``).  Reads files only: no device."""

import glob
import os

import pytest

from bench import trace as tr
from bench.trace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, modules=None, spans=(), host=()):
    return Trace(ops={d: sorted((Event(*e) for e in evs),
                                key=lambda e: (e.start, -e.end))
                      for d, evs in ops.items()},
                 modules={d: [Event(*e) for e in evs]
                          for d, evs in (modules or {}).items()},
                 spans=[Event(*s) for s in spans],
                 host=[Event(*h) for h in host])


def test_busy_is_the_union_inside_the_window():
    t = _trace({0: [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (90, 120, "d")]})
    assert tr.busy_ns(t, 0, 100) == {0: 20 + 10 + 10}
    assert tr.busy_ns(t, 15, 35) == {0: 5 + 5}


def test_leaves_drop_containers():
    evs = [Event(0, 100, "while"), Event(10, 20, "fusion.1"),
           Event(30, 50, "call"), Event(35, 40, "fusion.2"),
           Event(120, 130, "copy")]
    assert [e.name for e in tr.leaves(evs)] == ["fusion.1", "fusion.2",
                                                 "copy"]


def test_exposed_collective_time():
    t = _trace({0: [(0, 10, "fusion.1"), (10, 30, "all-gather.1"),
                    (30, 35, "fusion.2"), (40, 50, "all-reduce.3")],
                1: [(0, 50, "while.1"), (0, 10, "fusion.9"),
                    (10, 20, "all-gather.2"), (20, 50, "fusion.3")]})
    # device 0: collectives [10, 30) and [40, 50), no compute beside them;
    # device 1: the loop op spans its body, so only its leaves count
    assert tr.exposed_collective_ns(t, 0, 100) == {0: 30, 1: 10}
    assert tr.exposed_collective_ns(t, 15, 45) == {0: 20, 1: 5}


def test_module_time_by_name():
    t = _trace({0: []}, modules={0: [(0, 5, "jit_bench_backup(1)"),
                                     (10, 13, "jit_solve_chunk(2)"),
                                     (20, 27, "jit_bench_backup(1)")]})
    assert tr.module_ns(t, "bench_backup") == {0: 12}
    assert tr.module_ns(t, "nothing") == {}


def test_breakdown_names_ops_and_gaps():
    t = _trace({0: [(0, 10, "fusion.1"), (20, 60, "fusion.2"),
                    (70, 75, "fusion.1")]},
               spans=[(0, 100, "bench.solve")],
               host=[(10, 20, "PjitFunction(solve_chunk)")])
    b = tr.breakdown(t, 0, 100)
    assert b["device_ops"] == [["fusion.2", 40e-9], ["fusion.1", 15e-9]]
    gaps = dict((round(s * 1e9), n) for n, s in b["idle_gaps"])
    assert gaps[25] == "bench.solve"                  # [75, 100)
    assert gaps[10] == "bench.solve/PjitFunction(solve_chunk)"   # [10, 20)
    assert len(b["idle_gaps"]) == 3


def test_span_lookup():
    t = _trace({0: []}, spans=[(5, 9, "bench.solve")])
    assert tr.span(t, "bench.solve").dur == 4
    with pytest.raises(KeyError):
        tr.span(t, "bench.serve")


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e chip: a short ``vi`` solve of a
    4096-state garnet under ``bench.solve``, then two backups under
    ``bench.kernels`` in a module named ``bench_backup``."""
    return tr.load(os.path.join(DATA, "small_trace.xplane.pb"))


def test_recorded_trace_has_one_device_and_the_spans(recorded):
    assert list(recorded.ops) == [0] and len(recorded.ops[0]) > 10
    assert {s.name for s in recorded.spans} >= {"bench.solve",
                                                 "bench.kernels"}
    # device operations are named by instruction, result type and opcode
    assert all(" = " not in e.name for e in recorded.ops[0])


def test_recorded_busy_lies_inside_the_window(recorded):
    s = tr.span(recorded, "bench.solve")
    busy = tr.busy_ns(recorded, s.start, s.end)[0]
    assert 0 < busy <= s.dur
    facts = {"trace": recorded, "window": "solve",
             "window_ns": (s.start, s.end)}
    from bench.metrics_common import idle_share

    share = idle_share(facts, "solve")
    assert 0 <= share < 100
    assert idle_share(facts, "serve") is None


def test_recorded_kernel_roofline(recorded):
    from bench import counts
    from bench.metrics_common import kernel_roofline

    k = tr.span(recorded, "bench.kernels")
    calls = [e for e in recorded.modules[0] if "bench_backup" in e.name]
    assert len(calls) == 2
    # the device clock is put on the host's to within about a millisecond
    skew = 2_000_000
    assert all(k.start - skew <= e.start and e.end <= k.end + skew
               for e in calls)
    facts = {"trace": recorded, "peak": counts.peaks("TPU v5 lite"),
             "kernels": {"backup": {"module": "bench_backup", "calls": 2,
                                    "flops": counts.backup_flops(4096, 16, 8),
                                    "bytes": counts.backup_bytes(4096, 16,
                                                                 8)}}}
    share = kernel_roofline(facts, "backup")
    assert 0 < share < 100
    assert kernel_roofline(facts, "spmv") is None


def test_recorded_breakdown_and_no_collectives(recorded):
    s = tr.span(recorded, "bench.solve")
    b = tr.breakdown(recorded, s.start, s.end)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(sec > 0 for _, sec in b["device_ops"])
    assert all(name.startswith("bench.solve") for name, _ in b["idle_gaps"])
    assert tr.exposed_collective_ns(recorded, s.start, s.end) == {0: 0}


def test_only_one_recorded_trace_is_kept():
    files = glob.glob(os.path.join(DATA, "*"))
    assert [os.path.basename(f) for f in files] == ["small_trace.xplane.pb"]
    assert os.path.getsize(files[0]) < 300_000
