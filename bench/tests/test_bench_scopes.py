"""The attribution of device time to the program's scopes (``bench/scopes``)
and the readers built on it: hand-made profiles, the recorded trace of a
program without scopes (every new reader reads nothing), a recorded trace of
a scoped ``ipi_gmres`` solve on a TPU v5e chip (``scoped_data/``), and the
host spans of a CPU solve."""

import importlib.util
import json
import os

import pytest

from bench import scopes
from bench import trace as tr
from bench.scopes import Op, Profile
from bench.trace import Event, Trace

TESTS = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(TESTS), "metrics")
NEW_READERS = ["solve.spmv_share", "solve.backup_share",
               "kernel.spmv_roofline.solve", "kernel.backup_roofline.solve",
               "solver.spmv_calls", "host.syncs_per_solve",
               "host.sync_idle_share"]
SCOPED = os.path.join(TESTS, "scoped_data", "scoped_solve.xplane.pb")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _profile(ops, program=(), window=(0, 100)):
    """One device; ``ops`` are ``(start, end, name, scope)``."""
    evs = sorted((Op(*o) for o in ops), key=lambda e: (e.start, -e.end))
    trace = Trace(ops={0: evs}, modules={0: []},
                  spans=[Event(*window, "bench.solve")], host=[])
    return Profile(trace, [(Event(a, b, n), st) for a, b, n, st in program])


def _facts(p, **more):
    return {"trace": p.trace, "window": "solve", "scoped_profile": p,
            **more}


# two SpMV calls inside a GMRES cycle.  The gather reads the table; the
# profile puts the compiler's relayout between it and the row sum under the
# enclosing cycle; the SpMV's last step fuses into the cycle's subtraction,
# so that fusion carries the cycle's scope (its root's); a zero-length
# custom call starts with the second gather
SYNTHETIC = [
    (0, 10, "fusion.1", "repro.backup"),
    (10, 12, "copy.1", None),
    (12, 28, "fusion.2", "repro.spmv"),          # the gather
    (28, 30, "broadcast.1", "repro.gmres.cycle"),
    (30, 32, "fusion.5", "repro.spmv"),          # the row sum
    (32, 34, "fusion.3", "repro.gmres.cycle"),   # x - gamma * y
    (34, 40, "fusion.4", "repro.gmres.cycle"),
    (40, 40, "custom-call.1", None),
    (40, 56, "fusion.2", "repro.spmv"),
    (56, 58, "fusion.5", "repro.spmv"),
    (58, 60, "fusion.3", "repro.gmres.cycle"),
    (0, 80, "while.1", None),                    # the loop spans its body
    (80, 90, "fusion.1", "repro.backup"),
]


def test_scope_of_takes_the_innermost_program_scope():
    assert scopes.scope_of("jit(solve_chunk)/while/body/repro.outer/"
                           "repro.gmres.cycle/jit(ell_matvec)/repro.spmv/"
                           "gather:Gather") == "repro.spmv"
    assert scopes.scope_of("jit(solve_chunk)/while/body/add:") is None
    assert scopes.scope_of("") is None


def test_scope_time_and_calls_on_a_hand_made_profile():
    p = _profile(SYNTHETIC)
    ns = scopes.scope_ns(p, 0, 100)[0]
    assert ns == {"repro.backup": 20, None: 2, "repro.spmv": 36,
                  "repro.gmres.cycle": 12}
    # the gathers count, the row sums (an eighth of a gather) do not
    assert scopes.calls(p, "repro.spmv", 0, 100) == {0: 2}
    assert scopes.calls(p, "repro.backup", 0, 100) == {0: 2}
    # a call is counted in the window it starts in
    assert scopes.calls(p, "repro.spmv", 35, 100) == {0: 1}
    assert scopes.scope_ns(p, 20, 45)[0] == {"repro.spmv": 15,
                                              "repro.gmres.cycle": 10}


def test_readers_on_a_hand_made_profile():
    program = [(0, 100, "repro.solve", {"host_transfers": 12}),
               (60, 100, "repro.driver.sync", {}),
               (95, 100, "repro.driver.readback", {})]
    p = _profile(SYNTHETIC, program, window=(0, 100))
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    kernels = {"spmv": {"flops": 0, "bytes": 3}, "backup": {"flops": 0,
                                                           "bytes": 4}}
    facts = _facts(p, peak=peak, kernels=kernels)
    assert _reader("solve.spmv_share")(facts) == pytest.approx(36.0)
    assert _reader("solve.backup_share")(facts) == pytest.approx(20.0)
    assert _reader("solver.spmv_calls")(facts) == 2
    # 2 calls of 3 bytes at 1 byte/ns take 6 ns of the 36 ns measured
    assert _reader("kernel.spmv_roofline.solve")(facts) == \
        pytest.approx(100 * 6 / 36)
    assert _reader("kernel.backup_roofline.solve")(facts) == \
        pytest.approx(100 * 8 / 20)
    assert _reader("host.syncs_per_solve")(facts) == 12
    # idle in [60, 80) is busy (the loop), [90, 100) idle: all of it is
    # inside the sync or the readback
    assert _reader("host.sync_idle_share")(facts) == pytest.approx(10.0)


def test_host_waits_outside_idle_time_read_zero():
    p = _profile([(0, 100, "fusion.1", "repro.backup")],
                 [(10, 20, "repro.driver.sync", {})])
    assert _reader("host.sync_idle_share")(_facts(p)) == 0.0


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_without_its_facts(name):
    read = _reader(name)
    assert read({}) is None
    assert read({"trace": None, "window": "solve"}) is None
    empty = _profile([(0, 10, "fusion.1", None)])
    assert read(_facts(empty)) is None


@pytest.fixture(scope="module")
def unscoped():
    """The trace in ``data/``, recorded from a program without scopes or
    spans of its own."""
    path = os.path.join(TESTS, "data", "small_trace.xplane.pb")
    return path, scopes.load(path), tr.load(path)


def test_load_reads_the_events_of_trace_load(unscoped):
    _, p, t = unscoped

    def rows(evs):
        return [(e.start, e.end, e.name) for e in evs]

    assert rows(p.trace.ops[0]) == rows(t.ops[0])
    assert rows(p.trace.modules[0]) == rows(t.modules[0])
    assert rows(p.trace.async_ops[0]) == rows(t.async_ops[0])
    assert rows(p.trace.spans) == rows(t.spans)
    assert sorted(rows(p.trace.host)) == sorted(rows(t.host))


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reads_nothing_in_an_unscoped_trace(unscoped, name):
    path, p, _ = unscoped
    from bench import counts

    s = tr.span(p.trace, "bench.solve")
    facts = {"trace": p.trace, "window": "solve", "xplane": path,
             "window_ns": (s.start, s.end),
             "peak": counts.peaks("TPU v5 lite"),
             "kernels": {k: {"flops": 1, "bytes": 1}
                         for k in ("spmv", "backup")}}
    assert all(e.scope is None for e in p.trace.ops[0])
    assert _reader(name)(facts) is None


def test_host_spans_of_a_cpu_solve(tmp_path):
    """A CPU profile has the host spans (and no device rows): the driver's
    spans lie inside ``repro.solve``, whose stat is the counter's delta."""
    import jax

    from repro.api import MDP, Session
    from repro.utils import trace as rt

    mdp = MDP.from_generator("garnet", n=200, m=4, k=4, gamma=0.95, seed=0)
    with Session({"-method": "ipi_gmres", "-atol": 1e-6,
                  "-verbose": False}) as sess:
        sess.solve(mdp)
        before = rt.host_transfers()
        with tr.capture(str(tmp_path)):
            with jax.profiler.TraceAnnotation("bench.solve"):
                sess.solve(mdp)
        delta = rt.host_transfers() - before
    p = scopes.load(tr.xplane_path(str(tmp_path)))
    s = tr.span(p.trace, "bench.solve")
    spans = scopes.program_spans(
        p, (rt.SOLVE, rt.DRIVER_INIT, rt.DRIVER_DISPATCH, rt.DRIVER_SYNC,
            rt.DRIVER_READBACK), s.start, s.end)
    names = [e.name for e, _ in spans]
    assert names[0] == rt.SOLVE and names[1] == rt.DRIVER_INIT
    assert names[-1] == rt.DRIVER_READBACK
    assert names.count(rt.DRIVER_SYNC) == names.count(rt.DRIVER_DISPATCH) + 1
    solve, stats = spans[0]
    assert all(solve.start <= e.start and e.end <= solve.end
               for e, _ in spans)
    assert stats == {rt.TRANSFERS_STAT: delta}
    facts = {"trace": p.trace, "window": "solve", "scoped_profile": p}
    assert _reader("host.syncs_per_solve")(facts) == delta == 12
    assert _reader("solver.spmv_calls")(facts) is None     # no device rows


def spmvs_from_iterations(trace_inner, restart):
    """SpMVs ``ipi_gmres`` executes, from its iteration counts: per outer
    iteration ``gmres``'s ``r0``, then whole cycles of ``restart + 1`` (the
    cycle's own residual and every Arnoldi step, masked or not)."""
    return sum(1 + -(-i // restart) * (restart + 1) for i in trace_inner)


@pytest.fixture(scope="module")
def recorded_scoped():
    """A scoped ``ipi_gmres`` solve of the cell's garnet at n = 4096 under
    ``bench.solve``, recorded on one TPU v5e chip by the benchmark's own
    capture, and what the solve returned."""
    from bench import counts

    with open(SCOPED.replace(".xplane.pb", ".json")) as f:
        meta = json.load(f)
    p = scopes.load(SCOPED)
    n, m, k = meta["n"], meta["m"], meta["k"]
    facts = {"trace": p.trace, "window": "solve", "xplane": SCOPED,
             "peak": counts.peaks("TPU v5 lite"),
             "kernels": {"spmv": {"flops": counts.spmv_flops(n, k),
                                  "bytes": counts.spmv_bytes(n, k)},
                         "backup": {"flops": counts.backup_flops(n, m, k),
                                    "bytes": counts.backup_bytes(n, m, k)}}}
    return meta, p, facts


def test_recorded_solve_counts_every_spmv_it_ran(recorded_scoped):
    meta, p, facts = recorded_scoped
    assert _reader("solver.spmv_calls")(facts) == spmvs_from_iterations(
        meta["trace_inner"], meta["restart"])
    assert sum(meta["trace_inner"]) == meta["inner"]
    # one backup starts the solve, one evaluates each outer iteration
    lo, hi = scopes.window(p, facts)
    assert scopes.calls(p, scopes.BACKUP, lo, hi) == {0: meta["outer"] + 1}


def test_recorded_solve_reads_every_new_metric(recorded_scoped):
    _, _, facts = recorded_scoped
    got = {name: _reader(name)(facts) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["solve.spmv_share"] + got["solve.backup_share"] < 100
    assert 0 < got["kernel.spmv_roofline.solve"] <= 100
    assert 0 < got["kernel.backup_roofline.solve"] <= 100
    assert got["host.syncs_per_solve"] == 12
    assert 0 <= got["host.sync_idle_share"] < 100


def test_only_the_scoped_recording_is_kept():
    files = sorted(os.listdir(os.path.dirname(SCOPED)))
    assert files == ["scoped_solve.json", "scoped_solve.xplane.pb"]
    assert os.path.getsize(SCOPED) < 1_000_000
