"""The exchange layer's readers (``exchange.share``,
``exchange.exposed_share``, ``kernel.exchange_roofline.solve``) and the
in-solve readers on a mesh: hand-made profiles, and the traced run of
``garnet_16m_x4.solve`` at n = 65536 recorded on four TPU v5e chips by the
harness's own capture (``exchange_data/``), with what each reader gives
on it written down beside it.  Reads files only: no device."""

import gzip
import importlib.util
import json
import os
import shutil

import pytest

from bench import counts, scopes
from bench import trace as tr
from bench.scopes import Op, Profile
from bench.trace import Event, Trace

TESTS = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(TESTS), "metrics")
DATA = os.path.join(TESTS, "exchange_data")
EXCHANGE_READERS = ["exchange.share", "exchange.exposed_share",
                    "kernel.exchange_roofline.solve"]
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9,
        "ici_bits_per_s": 8e9}          # 1 byte per ns over the links


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _facts(ops, async_ops=None, window=(0, 100), exchange=None):
    """A hand-made profile; ``ops`` and ``async_ops`` map a device to
    ``(start, end, name, scope)`` rows."""
    def line(rows):
        return sorted((Op(*r) for r in rows), key=lambda e: (e.start, -e.end))
    trace = Trace(ops={d: line(r) for d, r in ops.items()},
                  modules={d: [] for d in ops},
                  spans=[Event(*window, "bench.solve")], host=[],
                  async_ops={d: line(r) for d, r in (async_ops or {}).items()})
    facts = {"trace": trace, "window": "solve", "window_ns": window,
             "scoped_profile": Profile(trace, []), "peak": PEAK}
    if exchange is not None:
        facts["exchange"] = {"bytes": exchange}
    return facts


EX = "repro.exchange"
# two devices: device 0 gathers twice as operations, device 1 once as an
# async start/done pair with a backup running beside it
SYNC_AND_ASYNC = {
    0: [(0, 10, "all-gather.1 f32[64] all-gather", EX),
        (10, 40, "fusion.1 f32[8] fusion", "repro.backup"),
        (40, 50, "all-gather.1 f32[64] all-gather", EX),
        (50, 60, "psum.2 f32[] all-reduce", "repro.gmres.cycle")],
    1: [(0, 2, "all-gather-start.1 f32[64] all-gather-start", EX),
        (2, 30, "fusion.1 f32[8] fusion", "repro.backup"),
        (30, 32, "all-gather-done.1 f32[64] all-gather-done", EX),
        (40, 50, "all-gather-start.2 f32[64] all-gather-start", EX),
        (60, 62, "all-gather-done.2 f32[64] all-gather-done", EX)],
}
ASYNC = {1: [(0, 32, "all-gather-start.1 f32[64] all-gather-start", EX),
             (40, 62, "all-gather-start.2 f32[64] all-gather-start", EX)]}


def test_exchange_readers_on_a_hand_made_profile():
    facts = _facts(SYNC_AND_ASYNC, ASYNC, exchange=48)
    # self time under the scope: device 0 20 ns, device 1 2+2+10+2 = 16 ns
    assert _reader("exchange.share")(facts) == pytest.approx((20 + 16) / 2)
    # device 0: the gathers ran alone, 20 ns (the psum is the solvers'
    # dot, not the exchange); device 1: the async gathers [0, 32) and
    # [40, 62) less the backup's [2, 30), 26 ns
    assert _reader("exchange.exposed_share")(facts) == \
        pytest.approx((20 + 26) / 2)
    # two calls on each device; device 1 (the higher id on a tie) spent 54
    # ns from start to done: 2 x 48 bytes at 1 byte/ns over 54 ns
    assert _reader("kernel.exchange_roofline.solve")(facts) == \
        pytest.approx(100 * 96 / 54)


def test_a_device_with_more_gathers_is_read():
    ops = {0: SYNC_AND_ASYNC[0],
           1: [(0, 10, "all-gather.1 f32[64] all-gather", EX)]}
    assert _reader("kernel.exchange_roofline.solve")(
        _facts(ops, exchange=48)) == pytest.approx(100 * 96 / 20)


@pytest.mark.parametrize("name", EXCHANGE_READERS)
def test_exchange_readers_read_nothing_without_an_exchange(name):
    read = _reader(name)
    assert read({}) is None
    one_chip = _facts({0: [(0, 50, "fusion.1 f32[8] fusion",
                            "repro.backup")]}, exchange=48)
    assert read(one_chip) is None
    # a run without the exchange's facts reads no roofline
    if name == "kernel.exchange_roofline.solve":
        assert read(_facts(SYNC_AND_ASYNC, ASYNC)) is None


@pytest.fixture(scope="module")
def recorded_x4(tmp_path_factory):
    """The recorded four-chip trace, unpacked, its readers' facts, and
    what was recorded beside it."""
    with open(os.path.join(DATA, "x4_solve.json")) as f:
        meta = json.load(f)
    path = tmp_path_factory.mktemp("x4") / "x4_solve.xplane.pb"
    with gzip.open(os.path.join(DATA, "x4_solve.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    p = scopes.load(str(path))
    s = tr.span(p.trace, "bench.solve")
    facts = {"trace": p.trace, "window": "solve", "xplane": str(path),
             "window_ns": (s.start, s.end), "scoped_profile": p,
             "peak": counts.peaks("TPU v5 lite"), "kernels": meta["kernels"],
             "exchange": meta["exchange"],
             "solves": [[meta["outer"], meta["inner"]]]}
    return meta, p, facts


def test_recorded_facts_are_the_shard_counts(recorded_x4):
    meta, _, _ = recorded_x4
    n, m, k, chips = meta["n"], meta["m"], meta["k"], meta["chips"]
    rows = n // chips
    assert meta["kernels"]["backup"]["bytes"] == \
        counts.backup_bytes(rows, m, k, values=n)
    assert meta["kernels"]["spmv"]["bytes"] == \
        counts.spmv_bytes(rows, k, values=n)
    assert meta["exchange"]["bytes"] == counts.allgather_bytes(n, chips)


def test_recorded_run_gathers_once_per_spmv_and_evaluation(recorded_x4):
    """Every chip all-gathers the values for each SpMV (each ``r0`` and
    Arnoldi step) and for each outer iteration's evaluation backup; the
    solve's first backup reads the initial state's window."""
    meta, p, facts = recorded_x4
    lo, hi = facts["window_ns"]
    assert sorted(p.trace.ops) == list(range(meta["chips"]))
    spmvs = _reader("solver.spmv_calls")(facts)
    for d in p.trace.ops:
        calls, _ = scopes._allgathers(p, d, lo, hi)
        assert calls == meta["allgathers"] == spmvs + meta["outer"]


@pytest.mark.parametrize("name", ["device.idle_share.solve",
                                  "solve.spmv_share", "solve.backup_share",
                                  "kernel.spmv_roofline.solve",
                                  "kernel.backup_roofline.solve",
                                  "solver.spmv_calls",
                                  "host.syncs_per_solve",
                                  "host.sync_idle_share"]
                         + EXCHANGE_READERS)
def test_recorded_run_reads_what_was_written_down(recorded_x4, name):
    _, _, facts = recorded_x4
    meta = recorded_x4[0]
    got = _reader(name)(facts)
    assert got is not None
    assert got == pytest.approx(meta["expected"][name], rel=1e-12)
    if name.endswith("share") or "roofline" in name:
        assert 0 < got <= 100


def test_recorded_exchange_roofline_by_hand(recorded_x4):
    meta, p, facts = recorded_x4
    lo, hi = facts["window_ns"]
    d = max(p.trace.ops)            # every chip gathers alike: the last
    evs = [e for e in p.trace.ops[d] if e.scope == scopes.EXCHANGE
           and e.name.startswith("all-gather") and lo <= e.start < hi]
    assert len(evs) == meta["allgathers"] and not p.trace.async_ops.get(d)
    seconds = sum(e.dur for e in evs) / 1e9
    nbytes = meta["exchange"]["bytes"] * len(evs)
    assert _reader("kernel.exchange_roofline.solve")(facts) == \
        pytest.approx(100 * nbytes / (1600e9 / 8) / seconds)


def test_only_the_four_chip_recording_is_kept():
    assert sorted(os.listdir(DATA)) == ["x4_solve.json",
                                        "x4_solve.xplane.pb.gz"]
    assert os.path.getsize(os.path.join(DATA, "x4_solve.xplane.pb.gz")) \
        < 1_000_000
