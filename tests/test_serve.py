"""The serving subsystem: batched solve-as-a-service over ``Session``.

Contract (ISSUE 8): N concurrent clients submitting ragged-shape MDPs get
results **bitwise-equal** to direct ``Session.solve`` (vi/mpi are
elementwise — no cross-lane arithmetic — so batching lanes cannot perturb
them); compatible arrivals inside the batching window coalesce into fewer
compiled dispatches than requests; admission control rejects with
machine-readable reasons instead of queueing unboundedly; per-iteration
monitor records stream back tagged with the submitting request's id;
drain finishes in-flight work.  The fleet-sharded path (shape buckets
spread over the mesh's fleet axis) runs on 8 forced host devices in a
subprocess, like tests/test_fleet.py.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.api import MDP, Session
from repro.serve import AdmissionError, Server, slot_size
from repro.utils.lru import LRUCache

GAMMA = 0.9          # homogeneous: heterogeneous gammas take the traced-
                     # gamma path, which is not part of the bitwise contract
BASE = {"-method": "vi", "-atol": 1e-6, "-verbose": False}


def _garnet(n, seed):
    return MDP.from_generator("garnet", n=n, m=3, k=4, gamma=GAMMA,
                              seed=seed)


def _submit_all(server, mdps, **kw):
    """Submit from one thread per client, like real concurrent callers."""
    reqs = [None] * len(mdps)
    errs = [None] * len(mdps)

    def client(i):
        try:
            reqs[i] = server.submit(mdps[i], **kw)
        except Exception as e:  # noqa: BLE001
            errs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(mdps))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(e is None for e in errs), errs
    return reqs


# --------------------------------------------------------------------------- #
# bitwise parity + coalescing
# --------------------------------------------------------------------------- #

def test_concurrent_clients_bitwise_equal_and_coalesced():
    ns = [48, 64, 48, 64, 48, 48, 64, 48]
    mdps = [_garnet(n, seed=i) for i, n in enumerate(ns)]
    with Server({**BASE, "-serve_batch_window": 0.25}) as srv:
        reqs = _submit_all(srv, mdps)
        results = [r.result(timeout=600) for r in reqs]
        st = srv.stats()

    with Session(BASE) as sess:
        base = [sess.solve(m) for m in mdps]

    for i, (r, b) in enumerate(zip(results, base)):
        assert np.array_equal(np.asarray(r.v), np.asarray(b.v)), i
        assert np.array_equal(np.asarray(r.policy), np.asarray(b.policy)), i
        assert r.outer_iterations == b.outer_iterations, i
        assert np.array_equal(r.trace_residual, b.trace_residual,
                              equal_nan=True), i

    # batching coalesced: strictly fewer compiled dispatches than requests
    assert st["submitted"] == len(ns)
    assert st["completed"] == len(ns)
    assert st["dispatches"] < len(ns)
    assert st["dispatched_requests"] == len(ns)
    assert st["batch"]["max_size"] > 1
    # every dispatch is accounted against a program-cache slot
    pc = st["program_cache"]
    assert pc["hits"] + pc["misses"] == st["dispatches"]
    assert st["latency_s"]["p50"] > 0


def test_latency_splits_into_queue_wait_and_service():
    mdps = [_garnet(48, seed=40 + i) for i in range(4)]
    with Server({**BASE, "-serve_batch_window": 0.1}) as srv:
        reqs = _submit_all(srv, mdps)
        for r in reqs:
            r.result(timeout=600)
        lat = srv.stats()["latency_s"]
    for r in reqs:
        assert r.submitted <= r.dispatched <= r.completed
    assert lat["count"] == len(mdps)
    assert lat["queue"]["p50"] > 0 and lat["service"]["p50"] > 0
    assert lat["mean"] == pytest.approx(lat["queue"]["mean"]
                                        + lat["service"]["mean"])
    want = [r.latency for r in reqs]
    assert lat["mean"] == pytest.approx(sum(want) / len(want))


def test_two_shape_buckets_dispatch_separately():
    # 48 vs 96 states: pad waste past 25% -> bucket_indices splits, so one
    # coalesced group still dispatches as two compiled programs
    ns = [48, 96, 48, 96, 48, 96]
    mdps = [_garnet(n, seed=10 + i) for i, n in enumerate(ns)]
    with Server({**BASE, "-serve_batch_window": 0.25}) as srv:
        reqs = _submit_all(srv, mdps)
        results = [r.result(timeout=600) for r in reqs]
        st = srv.stats()

    with Session(BASE) as sess:
        for i, (m, r) in enumerate(zip(mdps, results)):
            b = sess.solve(m)
            assert np.array_equal(np.asarray(r.v), np.asarray(b.v)), i

    assert st["dispatches"] >= 2           # one per shape bucket
    assert st["dispatches"] < len(ns)      # but still coalesced
    pads = {s["n_pad"] for s in st["program_cache"]["slots"]}
    assert pads == {48, 96}


def test_program_cache_warm_hits_and_slot_padding():
    mdps1 = [_garnet(48, seed=20 + i) for i in range(5)]
    mdps2 = [_garnet(48, seed=30 + i) for i in range(5)]
    with Server({**BASE, "-serve_batch_window": 0.1}) as srv:
        for r in _submit_all(srv, mdps1):
            r.result(timeout=600)
        for r in _submit_all(srv, mdps2):
            r.result(timeout=600)
        st = srv.stats()
    # both waves are 5 requests padded to the same mid2 fleet slot (6), so
    # the second dispatch reuses the warm program slot
    assert st["program_cache"]["hits"] >= 1
    assert st["padded_lanes"] >= 2
    slots = st["program_cache"]["slots"]
    assert any(s["fleet_slot"] == 6 and s["dispatches"] >= 2 for s in slots)


def test_slot_size_grids():
    ns = (1, 2, 3, 4, 5, 6, 7, 12, 13, 24, 25)
    assert [slot_size(n, "mid2", 64) for n in ns] == \
        [1, 2, 3, 4, 6, 6, 8, 12, 16, 24, 32]
    assert [slot_size(n, "pow2", 64) for n in (1, 3, 5, 9)] == [1, 4, 8, 16]
    assert slot_size(24, "exact", 64) == 24


def test_incompatible_overrides_do_not_batch():
    mdps = [_garnet(48, seed=40 + i) for i in range(4)]
    with Server({**BASE, "-serve_batch_window": 0.2}) as srv:
        reqs = [srv.submit(mdps[0], atol=1e-6),
                srv.submit(mdps[1], atol=1e-6),
                srv.submit(mdps[2], atol=1e-8),
                srv.submit(mdps[3], atol=1e-8)]
        results = [r.result(timeout=600) for r in reqs]
        st = srv.stats()
    assert st["dispatches"] == 2           # one per override signature
    assert st["batch"]["max_size"] == 2
    assert results[2].residual <= 1e-8


# --------------------------------------------------------------------------- #
# admission control
# --------------------------------------------------------------------------- #

def test_admission_rejects_too_large():
    with Server({**BASE, "-serve_max_states": 50}) as srv:
        srv.submit(_garnet(48, seed=0)).result(timeout=600)
        with pytest.raises(AdmissionError) as exc:
            srv.submit(_garnet(64, seed=1))
        assert exc.value.reason == "too_large"
        assert "-serve_max_states" in str(exc.value)
        st = srv.stats()
    assert st["rejected"] == {"too_large": 1}
    assert st["completed"] == 1


def test_admission_rejects_queue_full():
    # a long window keeps the first submits queued while the third arrives
    with Server({**BASE, "-serve_max_queue": 2,
                 "-serve_batch_window": 5.0}) as srv:
        r1 = srv.submit(_garnet(48, seed=50))
        r2 = srv.submit(_garnet(48, seed=51))
        with pytest.raises(AdmissionError) as exc:
            srv.submit(_garnet(48, seed=52))
        assert exc.value.reason == "queue_full"
        assert "-serve_max_queue" in str(exc.value)
        assert srv.drain(timeout=600)      # cuts the window short
        assert r1.done and r2.done
        st = srv.stats()
    assert st["rejected"] == {"queue_full": 1}
    assert st["completed"] == 2


def test_draining_and_closed_reject_submits():
    srv = Server(BASE)
    try:
        req = srv.submit(_garnet(48, seed=60))
        assert srv.drain(timeout=600)
        with pytest.raises(AdmissionError) as exc:
            srv.submit(_garnet(48, seed=61))
        assert exc.value.reason == "draining"
        assert req.result(timeout=1) is not None   # drained work finished
    finally:
        srv.close()
    with pytest.raises(AdmissionError) as exc:
        srv.submit(_garnet(48, seed=62))
    assert exc.value.reason == "closed"


def test_submit_rejects_batched_container_and_junk():
    from repro.core import generators, stack_mdps
    stacked = stack_mdps([generators.garnet(n=32, m=3, k=4, seed=s)
                          for s in range(2)])
    with Server(BASE) as srv:
        with pytest.raises(ValueError, match="one MDP per request"):
            srv.submit(MDP(stacked))
        with pytest.raises(TypeError, match="repro.api.MDP"):
            srv.submit("not an mdp")


# --------------------------------------------------------------------------- #
# monitor streams, result lookup, drain
# --------------------------------------------------------------------------- #

def test_monitor_streams_attributed_per_request():
    mdps = [_garnet(48, seed=70 + i) for i in range(4)]
    with Server({**BASE, "-serve_batch_window": 0.25}) as srv:
        reqs = _submit_all(srv, mdps, monitor=True)
        streams = {r.id: list(srv.stream(r)) for r in reqs}
        results = {r.id: r.result(timeout=600) for r in reqs}
        st = srv.stats()

    assert st["dispatches"] == 1           # all four shared one program
    for rid, recs in streams.items():
        assert recs, rid
        # every record carries the submitting request's id and the fleet
        # lane's own residual trajectory, one record per outer iteration;
        # the stream spans the whole bucket's run, so a lane that converged
        # early plateaus at its final residual while bucket-mates finish
        assert all(rec["request"] == rid for rec in recs)
        assert [rec["k"] for rec in recs] == list(range(len(recs)))
        res = np.array([rec["res"] for rec in recs])
        trace = np.asarray(results[rid].trace_residual)
        k = min(len(res), len(trace))
        assert np.array_equal(res[:k], trace[:k]), rid
        assert len(res) >= len(trace) - 1, rid


def test_stream_requires_monitor_flag():
    with Server(BASE) as srv:
        req = srv.submit(_garnet(48, seed=80))
        with pytest.raises(ValueError, match="monitor=True"):
            next(iter(srv.stream(req)))
        req.result(timeout=600)


def test_result_by_id_and_unknown_id():
    with Server(BASE) as srv:
        req = srv.submit(_garnet(48, seed=81))
        res = srv.result(req.id, timeout=600)
        assert res.converged
        with pytest.raises(KeyError, match="unknown"):
            srv.result(10 ** 9)


def test_drain_completes_in_flight_work():
    mdps = [_garnet(48, seed=90 + i) for i in range(5)]
    with Server({**BASE, "-serve_batch_window": 2.0}) as srv:
        reqs = _submit_all(srv, mdps)
        assert srv.drain(timeout=600)      # dispatches without the window
        assert all(r.done for r in reqs)
        assert all(r.result(timeout=1).converged for r in reqs)
        st = srv.stats()
        assert st["queue_depth"] == 0
        assert st["in_flight"] == 0
        assert st["draining"]


def test_close_fails_undispatched_requests():
    srv = Server({**BASE, "-serve_batch_window": 30.0})
    reqs = _submit_all(srv, [_garnet(48, seed=100 + i) for i in range(3)])
    srv.close(timeout=0.05)                # drain times out -> abandon
    failed = 0
    for r in reqs:
        try:
            r.result(timeout=600)
        except AdmissionError as e:
            assert e.reason == "closed"
            failed += 1
    # the scheduler may have dispatched some before the cutoff; whatever
    # was still queued must fail loudly rather than hang
    assert failed + sum(r._error is None for r in reqs) == 3


# --------------------------------------------------------------------------- #
# session-layer satellites: fleet-cache LRU, concurrent jsonl stats
# --------------------------------------------------------------------------- #

def test_lru_cache_eviction_and_counters():
    lru = LRUCache(2)
    assert lru.get("a") is None
    lru.put("a", 1)
    lru.put("b", 2)
    assert lru.get("a") == 1               # refresh 'a'
    assert lru.put("c", 3) == ("b", 2)     # LRU 'b' evicted
    assert lru.get("b") is None
    st = lru.stats()
    assert st == {"size": 2, "capacity": 2, "hits": 1, "misses": 2,
                  "evictions": 1, "hit_rate": 1 / 3}


def test_session_cache_stats_surface():
    # counters live-count in the fleet-sharded path (subprocess test below);
    # here just the surface: the LRU stats dict and the per-entry embedding
    mdps = [_garnet(32, seed=110 + i) for i in range(3)]
    with Session(BASE) as sess:
        sess.solve_fleet(mdps)
        cs = sess.cache_stats
        assert set(cs) == {"fleet", "run_chunk_programs"}
        assert {"size", "capacity", "hits", "misses", "evictions",
                "hit_rate"} <= set(cs["fleet"])
        assert "cache" in sess.stats[-1]["fleet"]


def test_concurrent_jsonl_stats_stay_valid(tmp_path):
    path = tmp_path / "stats.jsonl"
    opts = {**BASE, "-file_stats": str(path),
            "-file_stats_format": "jsonl"}
    mdps = [_garnet(32, seed=120 + i) for i in range(6)]
    with Session(opts) as sess:
        threads = [threading.Thread(target=sess.solve, args=(m,))
                   for m in mdps]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    lines = path.read_text().strip().splitlines()
    assert len(lines) == len(mdps)         # one line per solve, none torn
    entries = [json.loads(ln) for ln in lines]
    assert all(e["solves"][0]["converged"] for e in entries)


# --------------------------------------------------------------------------- #
# fleet-sharded serving (8 forced host devices, subprocess)
# --------------------------------------------------------------------------- #

_FLEET_SCRIPT = r"""
import os, threading
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import json
import numpy as np
from repro.api import MDP, Session
from repro.serve import Server

ns = [120, 180, 120, 180, 120, 120, 180, 120, 180, 120]
mdps = [MDP.from_generator("garnet", n=n, m=4, k=4, gamma=0.95, seed=i)
        for i, n in enumerate(ns)]
base_opts = {"-method": "vi", "-atol": 1e-8, "-dtype": "float64",
             "-verbose": False}

with Server({**base_opts, "-serve_batch_window": 0.5}) as srv:
    mesh, layout = srv.session.placement(fleet_size=8)
    reqs = [None] * len(mdps)
    def client(i):
        reqs[i] = srv.submit(mdps[i])
    ts = [threading.Thread(target=client, args=(i,))
          for i in range(len(mdps))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    results = [r.result(timeout=600) for r in reqs]
    st = srv.stats()

# single-device replicated baseline: the fleet-sharded bitwise reference
# for the elementwise methods (tests/test_fleet.py contract)
with Session({**base_opts, "-layout": "single"}) as sess:
    base = [sess.solve(m) for m in mdps]

out = {
    "devices": jax.device_count(),
    "layout": layout,
    "dispatches": st["dispatches"],
    "completed": st["completed"],
    "bitwise_v": all(np.array_equal(np.asarray(a.v), np.asarray(b.v))
                     for a, b in zip(results, base)),
    "bitwise_pi": all(np.array_equal(np.asarray(a.policy),
                                     np.asarray(b.policy))
                      for a, b in zip(results, base)),
    "outer_eq": all(a.outer_iterations == b.outer_iterations
                    for a, b in zip(results, base)),
    "slots": st["program_cache"]["slots"],
}

# the session fleet-container LRU counts live on the deferred +
# fleet-sharded device-materialization path: same fleet twice -> warm hit
from repro.core.generators import garnet_functions
fmdps = [MDP.from_functions(**garnet_functions(n=160, m=4, k=4,
                                               gamma=0.95, seed=s))
         for s in range(4)]
with Session(base_opts) as s2:
    s2.solve_fleet(fmdps)
    c1 = dict(s2.cache_stats["fleet"])
    s2.solve_fleet(fmdps)
    c2 = dict(s2.cache_stats["fleet"])
    out["fleet_cache_first"] = c1
    out["fleet_cache_second"] = c2
    out["entry_has_cache"] = "cache" in s2.stats[-1]["fleet"]
print("RESULT " + json.dumps(out))
"""


def test_serve_fleet_sharded_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", _FLEET_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["devices"] == 8
    assert out["layout"] in ("fleet", "fleet2d")
    assert out["completed"] == 10
    assert out["dispatches"] < 10          # coalesced across clients
    assert out["bitwise_v"] and out["bitwise_pi"] and out["outer_eq"]
    assert out["fleet_cache_first"]["misses"] >= 1
    assert out["fleet_cache_first"]["hits"] == 0
    assert out["fleet_cache_second"]["hits"] >= 1
    assert out["entry_has_cache"]
