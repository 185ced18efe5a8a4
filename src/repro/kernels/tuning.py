"""Tile autotuner for the streaming kernels, with a persisted JSON cache.

The dispatch layer (:mod:`repro.kernels.ops`) resolves tile sizes at trace
time — tile sizes are Python ints baked into the jaxpr, so the lookup runs
as ordinary Python during tracing.  On a cache miss the tuner times each
candidate on synthetic data of the same shape/dtype (eager, outside the
trace being built) and persists the winner, keyed by

    (kernel, backend, n-bucket, m, K, dtype)

where the n-bucket is the next power of two — close shapes share an entry so
a solver sweeping problem sizes does not retune per size.  The JSON cache
lives at ``~/.cache/madupite/autotune.json`` by default; override with the
``-kernel_tune_cache`` option or :func:`configure`.  ``-kernel_tune off``
disables measurement (defaults are used and nothing is written).

A corrupt or unreadable cache file is treated as empty (warned once) and is
overwritten on the next successful tune.
"""

from __future__ import annotations

import json
import os
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

DEFAULT_CACHE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "madupite", "autotune.json")

_CACHE_VERSION = 1

# Below this element count (n * m * K) tuning costs more than it can ever
# save; callers get the default candidate and nothing is cached.
MIN_TUNE_ELEMS = 1 << 21

_TIMING_REPS = 3


@dataclass
class _State:
    enabled: bool = True
    cache_path: str = DEFAULT_CACHE_PATH
    entries: dict[str, dict[str, Any]] = field(default_factory=dict)
    loaded_from: str | None = None
    warned_corrupt: bool = False


_state = _State()


def configure(*, enabled: bool | None = None,
              cache_path: str | None = None) -> None:
    """Set tuner behaviour (called by Session from the options DB)."""
    if enabled is not None:
        _state.enabled = bool(enabled)
    if cache_path is not None and cache_path != _state.cache_path:
        _state.cache_path = cache_path
        _state.entries = {}
        _state.loaded_from = None
        _state.warned_corrupt = False


def reset(*, cache_path: str | None = None) -> None:
    """Forget all in-memory state (tests)."""
    global _state
    _state = _State()
    if cache_path is not None:
        _state.cache_path = cache_path


def enabled() -> bool:
    return _state.enabled


def cache_path() -> str:
    return _state.cache_path


def n_bucket(n: int) -> int:
    """Next power of two >= n: close sizes share a tuning entry."""
    return 1 << max(0, int(n - 1).bit_length())


def cache_key(kernel: str, backend: str, n: int, m: int, k: int,
              dtype: Any) -> str:
    return f"{kernel}|{backend}|n{n_bucket(n)}|m{m}|k{k}|{dtype}"


def _load() -> None:
    if _state.loaded_from == _state.cache_path:
        return
    _state.loaded_from = _state.cache_path
    path = _state.cache_path
    if not os.path.exists(path):
        return
    try:
        with open(path) as f:
            blob = json.load(f)
        entries = blob["entries"]
        if not isinstance(entries, dict):
            raise ValueError("entries is not a dict")
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        if not _state.warned_corrupt:
            warnings.warn(
                f"madupite autotune cache {path!r} is unreadable ({e}); "
                "starting from an empty cache", stacklevel=3)
            _state.warned_corrupt = True
        return
    # merge under whatever was recorded in-memory this process
    for key, entry in entries.items():
        _state.entries.setdefault(key, entry)


def _persist() -> None:
    path = _state.cache_path
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _CACHE_VERSION, "entries": _state.entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        warnings.warn(f"could not persist autotune cache to {path!r}: {e}",
                      stacklevel=3)


def lookup(key: str) -> dict[str, Any] | None:
    _load()
    return _state.entries.get(key)


def record(key: str, entry: dict[str, Any]) -> None:
    _load()
    _state.entries[key] = entry
    _persist()


def tune(kernel: str, backend: str, n: int, m: int, k: int, dtype: Any,
         candidates: Sequence[Any], default: Any,
         bench: Callable[[Any], float] | None,
         ) -> Any:
    """Resolve the tile choice for one kernel shape.

    Returns the cached winner if present; otherwise, when tuning is enabled,
    the shape is big enough and a ``bench`` callable is given, times each
    candidate (``bench(candidate) -> seconds``), records the winner and
    returns it.  In every other case returns ``default``.
    """
    key = cache_key(kernel, backend, n, m, k, dtype)
    hit = lookup(key)
    if hit is not None:
        return hit["choice"]
    if (not _state.enabled or bench is None
            or n * m * k < MIN_TUNE_ELEMS or len(candidates) <= 1):
        return default
    if _tracing():
        # The dispatch layer is being traced inside an enclosing jit:
        # running the candidates here would stage them into that trace
        # instead of timing them.  Fall back to the default and leave the
        # cache untouched, so a later eager call can still tune the shape.
        return default
    timings: dict[str, float] = {}
    best, best_t = default, float("inf")
    for cand in candidates:
        try:
            t = min(bench(cand) for _ in range(_TIMING_REPS))
        except Exception as e:  # noqa: BLE001 - a failing candidate is skipped
            warnings.warn(f"autotune candidate {cand!r} failed: {e}",
                          stacklevel=2)
            continue
        timings[str(cand)] = t
        if t < best_t:
            best, best_t = cand, t
    if timings:
        record(key, {"choice": best, "timings_s": timings})
    return best


def _tracing() -> bool:
    """True while a jax transformation is tracing the caller: any operation
    issued now is staged into that trace and comes back as a tracer."""
    import jax
    import jax.numpy as jnp

    return isinstance(jnp.zeros(()), jax.core.Tracer)


def measure(fn: Callable[[], Any]) -> float:
    """One timed run of ``fn`` (seconds), blocking on all outputs."""
    import jax

    out = fn()
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    out = fn()
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    return time.perf_counter() - t0
