"""Named XLA flag bundles, applied per topology at session start.

madupite ships PETSc options tables tuned per machine; the JAX analogue is
the ``XLA_FLAGS`` environment variable.  This module names a few vetted
per-topology combinations so a run can say ``-xla_flag_bundle cpu-single``
instead of exporting raw flags, and so A/B benchmarks
(``benchmarks/run.py --only kernels``) can sweep them reproducibly.

Flags must reach XLA before the backend initializes.  ``apply_bundle``
merges the bundle into the environment variable its compiler reads
(:func:`env_var`): ``XLA_FLAGS`` for the CPU bundles, ``LIBTPU_INIT_ARGS``
for the TPU ones, whose flags only libtpu knows (jaxlib aborts on them in
``XLA_FLAGS``).  Existing flags are kept; bundle flags are appended, and
last-one-wins parsing makes the bundle take precedence on conflicts.  If
the JAX backend is already up, the merge still happens — useful for
subprocess benchmarking — but a warning explains that the current process
will not see the change.
"""

from __future__ import annotations

import os
import warnings

# Each bundle: flag name -> value.  Rendered as --name=value.
BUNDLES: dict[str, dict[str, str]] = {
    # Single-core CPU solver runs (the common laptop / CI topology): stop
    # Eigen from spawning a thread pool that only adds scheduling noise at
    # nproc=1, and keep min/max IEEE-strict so argmin tie-breaks stay exact.
    "cpu-single": {
        "xla_cpu_multi_thread_eigen": "false",
        "xla_cpu_enable_fast_min_max": "false",
    },
    # Multi-core CPU hosts: default threading, strict min/max only.
    "cpu-host": {
        "xla_cpu_enable_fast_min_max": "false",
    },
    # Communication-overlapped backups on CPU (-comm_overlap): the
    # concurrency-optimized thunk scheduler lets XLA:CPU run the value-window
    # collective concurrently with the interior-row backup that does not
    # depend on it.  On TPU the same overlap needs the async-collective
    # family instead — use "tpu-collectives" there.
    "cpu-overlap": {
        "xla_cpu_enable_concurrency_optimized_scheduler": "true",
        "xla_cpu_enable_fast_min_max": "false",
    },
    # TPU pods: overlap collective latency with compute — matters for the
    # state-axis all-gather before every backup and psum_state reductions.
    "tpu-collectives": {
        "xla_tpu_enable_latency_hiding_scheduler": "true",
        "xla_enable_async_all_gather": "true",
        "xla_enable_async_collective_permute": "true",
    },
    # TPU single-host: latency hiding only.
    "tpu-host": {
        "xla_tpu_enable_latency_hiding_scheduler": "true",
    },
}


def bundle_names() -> tuple[str, ...]:
    return tuple(sorted(BUNDLES))


def bundle(name: str) -> dict[str, str]:
    try:
        return dict(BUNDLES[name])
    except KeyError:
        raise KeyError(
            f"unknown XLA flag bundle {name!r}; "
            f"available: {', '.join(bundle_names())}") from None


def env_var(name: str) -> str:
    """The environment variable the bundle's compiler reads its flags from."""
    bundle(name)
    return "LIBTPU_INIT_ARGS" if name.startswith("tpu-") else "XLA_FLAGS"


def render(name: str) -> str:
    """The bundle as an XLA_FLAGS fragment: ``--flag=value ...``."""
    return " ".join(f"--{k}={v}" for k, v in bundle(name).items())


def backend_initialized() -> bool:
    """True if a JAX backend already exists (flags no longer take effect)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:  # noqa: BLE001 - private API; absent means unknown
        return False


def merged_flags(name: str, existing: str | None = None) -> str:
    """The bundle's variable (:func:`env_var`) with the bundle appended
    (bundle wins conflicts)."""
    fragment = render(name)
    existing = (existing if existing is not None
                else os.environ.get(env_var(name), ""))
    keep = [tok for tok in existing.split() if tok]
    # drop stale settings of the same flags so repeated applies stay idempotent
    names = {f"--{k}=" for k in bundle(name)}
    keep = [tok for tok in keep
            if not any(tok.startswith(p) for p in names)]
    return " ".join(keep + fragment.split())


def apply_bundle(name: str, *, env: dict | None = None) -> str:
    """Merge the bundle into ``env[env_var(name)]`` and return the new
    value."""
    env = os.environ if env is None else env
    var = env_var(name)
    merged = merged_flags(name, env.get(var))
    if env is os.environ and backend_initialized():
        warnings.warn(
            f"XLA flag bundle {name!r} applied after the JAX backend "
            "initialized; the current process keeps its old flags "
            "(subprocesses inherit the new ones)", stacklevel=2)
    env[var] = merged
    return merged
