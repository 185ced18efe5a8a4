"""The reference, the control and the data generator, on the CPU at sizes
a test run holds."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _dense_check(idx, val, cost, gamma, v, pi):
    """Q by explicit loops in float64: the reference's reference."""
    n, m, k = idx.shape
    q = np.zeros((n, m))
    for s in range(n):
        for a in range(m):
            q[s, a] = float(cost[s, a]) + gamma * sum(
                float(val[s, a, j]) * float(v[idx[s, a, j]])
                for j in range(k))
    best = q.min(axis=1)
    return (np.abs(best - np.asarray(v, np.float64)).max(),
            (q[np.arange(n), pi] - best).max())


def test_evaluate_matches_loops():
    idx, val, cost = (np.asarray(a) for a in gen.garnet(5, 64, 4, 3))
    rng = np.random.default_rng(0)
    v = rng.random(64).astype(np.float32) * 10
    pi = rng.integers(0, 4, 64).astype(np.int32)
    got = reference.evaluate([(0, idx, val, cost)], 0.9, v, pi, 64)
    res, gap = _dense_check(idx, val, cost, 0.9, v, pi)
    assert got["residual"] == pytest.approx(res, rel=1e-12)
    assert got["greedy_gap"] == pytest.approx(gap, rel=1e-12)


def test_evaluate_in_row_blocks(monkeypatch):
    idx, val, cost = (np.asarray(a) for a in gen.garnet(6, 100, 3, 2))
    v = np.linspace(0, 5, 100).astype(np.float32)
    pi = np.zeros(100, np.int32)
    whole = reference.evaluate([(0, idx, val, cost)], 0.8, v, pi, 100)
    monkeypatch.setattr(reference, "BLOCK_ROWS", 7)
    parts = [(0, idx[:40], val[:40], cost[:40]),
             (40, idx[40:], val[40:], cost[40:])]
    assert reference.evaluate(parts, 0.8, v, pi, 100) == whole


def test_evaluate_rejects_missing_actions_and_nan():
    idx, val, cost = (np.asarray(a) for a in gen.garnet(7, 16, 2, 2))
    v = np.zeros(16, np.float32)
    pi = np.full(16, 2, np.int32)               # action 2 of 2 does not exist
    got = reference.evaluate([(0, idx, val, cost)], 0.9, v, pi, 16)
    assert got["greedy_gap"] == float("inf")
    v[3] = np.nan
    got = reference.evaluate([(0, idx, val, cost)], 0.9, v,
                             np.zeros(16, np.int32), 16)
    assert got["residual"] == float("inf")


def test_generator_is_a_function_of_the_seed(monkeypatch):
    a = [np.asarray(x) for x in gen.garnet(3000000001, 256, 4, 3)]
    b = [np.asarray(x) for x in gen.garnet(3000000001, 256, 4, 3)]
    c = [np.asarray(x) for x in gen.garnet(3000000001 + (1 << 32), 256, 4, 3)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    # rows do not depend on the chunking
    monkeypatch.setattr(gen, "CHUNK_ROWS", 64)
    gen._single.cache_clear()
    d = [np.asarray(x) for x in gen.garnet(3000000001, 256, 4, 3)]
    gen._single.cache_clear()
    for x, y in zip(a, d):
        np.testing.assert_array_equal(x, y)


def test_generator_table_is_a_garnet():
    idx, val, cost = (np.asarray(a) for a in gen.garnet(9, 512, 4, 8))
    assert idx.dtype == np.int32 and val.dtype == np.float32
    assert idx.min() >= 0 and idx.max() < 512 and len(np.unique(idx)) > 400
    np.testing.assert_allclose(val.sum(-1), 1.0, atol=1e-6)
    assert (val > 0).all() and (cost >= 0).all() and (cost < 1).all()


def _renaming(plain, relabeled):
    """The permutation that carries ``plain``'s states to ``relabeled``'s,
    found by each row's costs (distinct random floats)."""
    where = {row.tobytes(): s for s, row in enumerate(relabeled[2])}
    return np.array([where[row.tobytes()] for row in plain[2]])


def test_relabeling_renames_the_states(monkeypatch):
    plain = [np.asarray(x) for x in gen.garnet(11, 256, 4, 3)]
    one = [np.asarray(x) for x in gen.garnet(11, 256, 4, 3,
                                             relabel=3000000901 + (1 << 33))]
    perm = _renaming(plain, one)
    assert sorted(perm) == list(range(256))
    assert (perm != np.arange(256)).mean() > 0.9
    np.testing.assert_array_equal(one[0][perm], perm[plain[0]])
    np.testing.assert_array_equal(one[1][perm], plain[1])
    np.testing.assert_array_equal(one[2][perm], plain[2])
    # another seed renames otherwise; the same seed, in chunks, alike
    other = [np.asarray(x) for x in gen.garnet(11, 256, 4, 3, relabel=5)]
    assert not np.array_equal(_renaming(plain, other), perm)
    monkeypatch.setattr(gen, "CHUNK_ROWS", 64)
    gen._single.cache_clear()
    again = [np.asarray(x) for x in gen.garnet(
        11, 256, 4, 3, relabel=3000000901 + (1 << 33))]
    gen._single.cache_clear()
    for x, y in zip(one, again):
        np.testing.assert_array_equal(x, y)


def test_relabeled_instances_take_the_same_work():
    """Every seed solves the configuration's instance under other names:
    the same iterations, the same values under the renaming."""
    from repro.api import MDP, Session

    cfg = _config("garnet_1m")
    n = 2048
    tables = [gen.garnet(cfg["instance_seed"], n, cfg["m"], cfg["k"],
                         relabel=seed)
              for seed in (None, 3000000911, 3000000912 + (1 << 40))]
    results = []
    with Session({"-method": cfg["method"], "-dtype": cfg["dtype"],
                  "-atol": cfg["atol"], "-layout": "single"}) as s:
        for t in tables:
            results.append(s.solve(MDP.from_arrays(
                idx=t[0], val=t[1], cost=t[2], gamma=cfg["gamma"],
                validate=False)))
    plain = [np.asarray(x) for x in tables[0]]
    for t, r in zip(tables[1:], results[1:]):
        assert (r.outer_iterations, r.inner_iterations) == (
            results[0].outer_iterations, results[0].inner_iterations)
        perm = _renaming(plain, [np.asarray(x) for x in t])
        np.testing.assert_allclose(np.asarray(r.v)[perm], results[0].v,
                                   rtol=1e-5)


@pytest.mark.parametrize("config,n", [("garnet_1m", 4096)])
def test_control_fails_where_the_program_passes(config, n):
    """The program's answer at a small size reads within every limit; the
    control, one bfloat16 backup of the same values, reads beyond one."""
    from repro.api import MDP, Session

    cfg = _config(config)
    table = gen.garnet(11, n, cfg["m"], cfg["k"])
    mdp = MDP.from_arrays(idx=table[0], val=table[1], cost=table[2],
                          gamma=cfg["gamma"], validate=False)
    with Session({"-method": cfg["method"], "-dtype": "float32",
                  "-atol": cfg["atol"], "-layout": "single"}) as s:
        r = s.solve(mdp)
    blocks = list(reference.host_blocks(table))
    sound = reference.evaluate(blocks, cfg["gamma"], r.v, r.policy, n)
    assert all(sound[k] <= cfg["limits"][k] for k in sound), sound
    cv, cpi = reference.control_answer(table, cfg["gamma"], r.v)
    ctrl = reference.evaluate(blocks, cfg["gamma"], cv, cpi, n)
    assert any(ctrl[k] > 3 * cfg["limits"][k] for k in ctrl), ctrl
