"""Benchmark 3 — kernel layer: fused streaming Bellman backup vs the unfused
per-action baseline.

The fused row is the dispatch layer's default path (``-kernel_impl auto`` —
the cache-blocked XLA implementation on CPU, with the scan chunk chosen by
the tile autotuner).  The unfused baseline is the madupite "standard
kernels" composition: one policy-restricted SpMV per action, stacked into
the (n, m) Q-table, then min/argmin — what you write without a fused
backup primitive.  Both sides are jit'd callables with identical
``(idx, val, cost, gamma, v)`` signatures and identical outputs, so the
comparison is like-for-like.

Shapes:
  * n=1e6, m=4, K=4 2-D grid stencil (N/S/E/W neighbors) — the paper's
    maze/diffusion-style problem family; banded successor structure.
  * n=1e5, m=16, K=8 uniform-random successors — unstructured (garnet-like).

Extra rows: the blocked-impl tile sweep (recording the autotuned choice),
the policy SpMV, and — at full scale — an XLA-flag-bundle A/B comparison
run in fresh subprocesses (flags must precede backend init).

The Pallas path is validated bit-for-bit in interpret mode (see
tests/test_kernels_tiled.py) and targeted at TPU; CPU wall time here only
covers the XLA impls.  ``MADUPITE_BENCH_SCALE`` (CI: ~0.02) scales the
state counts.
"""

from __future__ import annotations

import functools
import os
import subprocess
import sys
import time

import numpy as np

SCALE = float(os.environ.get("MADUPITE_BENCH_SCALE", "1.0"))

_REPS = 5


def _time(fn, *args, reps: int = _REPS) -> float:
    """us per call: min over ``reps`` timed calls after one warmup call."""
    import jax

    out = fn(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e6


def _stencil_ell(side: int, m: int, k: int):
    """2-D grid with an N/S/E/W successor stencil shared across actions."""
    import jax.numpy as jnp

    n = side * side
    rng = np.random.default_rng(0)
    r = np.arange(n)
    x, y = r // side, r % side
    nb = np.stack([((x + 1) % side) * side + y, ((x - 1) % side) * side + y,
                   x * side + (y + 1) % side, x * side + (y - 1) % side], -1)
    nb = nb[:, :k] if k <= 4 else np.pad(nb, ((0, 0), (0, k - 4)), "edge")
    idx = np.broadcast_to(nb[:, None, :], (n, m, k)).astype(np.int32)
    val = rng.random((n, m, k), dtype=np.float32)
    val /= val.sum(-1, keepdims=True)
    cost = rng.random((n, m), dtype=np.float32)
    v = rng.random(n, dtype=np.float32)
    return (jnp.asarray(idx.copy()), jnp.asarray(val), jnp.asarray(cost),
            jnp.asarray(v))


def _random_ell(n: int, m: int, k: int):
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    idx = rng.integers(0, n, (n, m, k)).astype(np.int32)
    val = rng.random((n, m, k), dtype=np.float32)
    val /= val.sum(-1, keepdims=True)
    cost = rng.random((n, m), dtype=np.float32)
    v = rng.random(n, dtype=np.float32)
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(cost),
            jnp.asarray(v))


def _unfused(i, w, c, g, u):
    """Per-action SpMV composition (the standard-kernels baseline)."""
    import jax.numpy as jnp

    m = i.shape[1]
    cols = [jnp.sum(w[:, a, :] * jnp.take(u, i[:, a, :], axis=0), axis=-1)
            for a in range(m)]
    q = c + g * jnp.stack(cols, axis=1)
    return q.min(-1), q.argmin(-1).astype(jnp.int32)


def _bench_backup(rows, label, idx, val, cost, v):
    import jax

    from repro.kernels import ops

    n, m, k = idx.shape
    gamma = 0.99
    # tune eagerly first: the fused timing below traces ops.ell_backup
    # inside an outer jit, where the tuner can only consult its cache
    impl = ops.resolve_impl(None)
    bn = (ops.backup_block_rows(n, m, k, v.shape[0], val.dtype)
          if impl == "blocked" else None)
    fused = jax.jit(lambda i, w, c, g, u: ops.ell_backup(i, w, c, g, u))
    unfused = jax.jit(_unfused)
    t_un = _time(unfused, idx, val, cost, gamma, v)
    t_fu = _time(fused, idx, val, cost, gamma, v)
    ratio = t_un / t_fu if t_fu else float("nan")
    rows.append((f"kernels/backup_unfused/n={n}", t_un,
                 f"per-action SpMV + stack + min/argmin m={m} K={k}"))
    rows.append((f"kernels/backup_fused/n={n}", t_fu,
                 f"impl=auto->{impl} block_rows={bn} "
                 f"{ratio:.2f}x vs unfused"))
    print(f"  {label}: unfused {t_un / 1e3:.1f} ms, fused {t_fu / 1e3:.1f} ms"
          f" ({ratio:.2f}x, impl={impl}, block_rows={bn})", flush=True)


def _bench_tile_sweep(rows, idx, val, cost, v):
    import jax

    from repro.kernels import ops, ref

    n, m, k = idx.shape
    gamma = 0.99
    cands = [c for c in ops.BLOCK_ROWS_CANDIDATES if c <= n] or [n]
    sweep = {}
    for bn in cands:
        fn = jax.jit(functools.partial(ref.ell_backup_blocked, block_rows=bn))
        sweep[bn] = _time(fn, idx, val, cost, gamma, v, reps=3)
    chosen = ops.backup_block_rows(n, m, k, v.shape[0], val.dtype)
    best = min(sweep, key=sweep.get)
    detail = " ".join(f"bn={bn}:{int(us)}us" for bn, us in sweep.items())
    rows.append((f"kernels/backup_tile_sweep/n={n}", sweep[best],
                 f"{detail} autotuned={chosen}"))
    print(f"  tile sweep: {detail}; autotuned choice bn={chosen}", flush=True)


def _bench_spmv(rows, idx, val, v):
    import jax

    from repro.kernels import ops, ref

    n, _, k = idx.shape
    i1, w1 = idx[:, 0, :], val[:, 0, :]
    fused = jax.jit(lambda i, w, x: ops.ell_matvec(i, w, x))
    plain = jax.jit(ref.ell_matvec)
    t_fu = _time(fused, i1, w1, v)
    t_pl = _time(plain, i1, w1, v)
    rows.append((f"kernels/spmv_blocked/n={n}", t_fu,
                 f"{t_pl / t_fu:.2f}x vs one-shot chain K={k}"))
    print(f"  spmv: blocked {t_fu / 1e3:.2f} ms vs chain {t_pl / 1e3:.2f} ms",
          flush=True)


_CHILD = r"""
import sys, time
sys.path.insert(0, {src!r})
import numpy as np
import jax, jax.numpy as jnp
from repro.kernels import ops, tuning
tuning.configure(enabled=False)
side = {side}; m, k = 4, 4
n = side * side
rng = np.random.default_rng(0)
r = np.arange(n); x, y = r // side, r % side
nb = np.stack([((x+1)%side)*side+y, ((x-1)%side)*side+y,
               x*side+(y+1)%side, x*side+(y-1)%side], -1)
idx = jnp.asarray(
    np.broadcast_to(nb[:, None, :], (n, m, k)).astype(np.int32).copy())
val = jnp.asarray(rng.random((n, m, k), dtype=np.float32))
cost = jnp.asarray(rng.random((n, m), dtype=np.float32))
v = jnp.asarray(rng.random(n, dtype=np.float32))
fn = jax.jit(lambda i, w, c, g, u: ops.ell_backup(i, w, c, g, u))
out = fn(idx, val, cost, 0.99, v)
jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
ts = []
for _ in range(5):
    t0 = time.perf_counter()
    out = fn(idx, val, cost, 0.99, v)
    jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
    ts.append(time.perf_counter() - t0)
print("US=%.1f" % (min(ts) * 1e6))
"""


def _bench_flag_bundles(rows, side: int) -> None:
    """A/B the XLA flag bundles in fresh subprocesses (flags must be set
    before the backend initializes, so in-process timing can't see them)."""
    from repro.utils import xla_flags

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = _CHILD.format(src=os.path.join(root, "src"), side=side)
    base_us = None
    for bundle in (None, "cpu-single", "cpu-host"):
        # the bundles are CPU flags; a child must never take an accelerator
        # (and this parent has already imported jax)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        if bundle is not None:
            env["XLA_FLAGS"] = xla_flags.merged_flags(
                bundle, env.get("XLA_FLAGS", ""))
        try:
            out = subprocess.run(
                [sys.executable, "-c", child], env=env, timeout=600,
                capture_output=True, text=True, check=True).stdout
            us = float(next(l for l in out.splitlines()
                            if l.startswith("US=")).split("=")[1])
        except (subprocess.SubprocessError, StopIteration, ValueError) as e:
            print(f"  bundle {bundle}: failed ({e})", flush=True)
            continue
        name = bundle or "none"
        if base_us is None:
            base_us = us
        rows.append((f"kernels/backup_bundle_{name}", us,
                     f"XLA_FLAGS bundle {name} ({base_us / us:.2f}x vs none)"))
        print(f"  bundle {name}: {us / 1e3:.1f} ms", flush=True)


def run(rows) -> None:
    side = max(32, int(round(1000 * SCALE ** 0.5)))
    n_rand = max(1024, int(100_000 * SCALE))

    idx, val, cost, v = _stencil_ell(side, 4, 4)
    _bench_backup(rows, f"stencil n={side * side} m=4 K=4", idx, val, cost, v)
    _bench_tile_sweep(rows, idx, val, cost, v)
    _bench_spmv(rows, idx, val, v)

    ridx, rval, rcost, rv = _random_ell(n_rand, 16, 8)
    _bench_backup(rows, f"random n={n_rand} m=16 K=8", ridx, rval, rcost, rv)

    if SCALE >= 1.0:
        _bench_flag_bundles(rows, side)
