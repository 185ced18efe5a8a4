"""The session layer: mesh/layout placement, solve dispatch, run outputs.

madupite hides PETSc's communicator setup behind ``madupite.initialize()``;
this module is the analogue for the JAX mesh machinery.  A
:class:`Session` owns

* **placement** — it builds the device mesh from the visible devices and
  picks the layout (``1d``/``2d``/``fleet``/``fleet2d``) from the problem
  shape and fleet size, overridable via ``-layout`` / ``-fleet``;
* **dispatch** — :meth:`Session.solve` / :meth:`Session.solve_fleet` run
  the core engines (:mod:`repro.core.driver`) with one consistent options
  view, materializing function-backed MDPs shard-locally on the session's
  mesh;
* **bucketing** — ragged fleets are grouped by state count into
  pad-efficient buckets (``-fleet_bucketing auto``), one compiled program
  per bucket;
* **outputs** — JSON run statistics (``-file_stats``), the optimal policy
  (``-file_policy``) and value vector (``-file_cost``);
* the **run-chunk cache lifecycle** — closing the session releases the
  compiled ``run_chunk`` programs (:func:`repro.core.driver.clear_run_cache`).

    from repro.api import MDP, Options, madupite_session

    with madupite_session({"-method": "ipi_gmres", "-atol": 1e-8}) as s:
        result = s.solve(MDP.from_generator("garnet", n=10_000, m=16, k=8))
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import weakref
from typing import Any, Mapping, Sequence

import numpy as np

from repro.api.fleet import bucket_indices
from repro.api.mdp import MDP, place_function_fleet
from repro.api.options import Options
from repro.core import methods as _methods
from repro.core import partition
from repro.core import driver
from repro.core.driver import SolveResult
from repro.core.mdp import DenseMDP, EllMDP, MatrixFreeMDP
from repro.core.mdp import MDP as CoreMDP
from repro.utils import trace
from repro.utils.lru import LRUCache

__all__ = ["Session", "madupite_session"]

# capacity of the per-session device-fleet container cache: entries hold
# whole fleets of device shards, so the bound stays small
_FLEET_CACHE_CAPACITY = 8


class Session:
    """A solve context: options database + device placement + outputs.

    ``options`` may be an :class:`Options` database, a plain mapping of
    option keys, or ``None`` (registry defaults + ``MADUPITE_OPTIONS``
    from the environment).  ``mesh`` optionally pins an explicit
    ``jax.sharding.Mesh`` instead of the auto-built one.
    """

    def __init__(self, options: Options | Mapping[str, Any] | None = None,
                 *, mesh=None, clear_cache_on_close: bool = True):
        if isinstance(options, Options):
            self.options = options
        else:
            self.options = Options.from_sources(options)
        self._mesh_override = mesh
        self._mesh_cache: dict = {}
        self._stats: list[dict] = []
        # per -file_stats path: (format, entries already on disk) — the
        # jsonl format streams O(1) appends instead of re-serializing the
        # whole accumulated list on every solve
        self._stats_written: dict[str, tuple[str, int]] = {}
        self._closed = False
        self._clear_cache = clear_cache_on_close
        # function-backed builders this session placed on a mesh: their
        # mesh-keyed device shards are evicted on close (the builders may
        # outlive the session, but the meshes should not pin device memory)
        self._placed_mdps: weakref.WeakSet = weakref.WeakSet()
        # builders this session solved matrix-free: their O(n) operator
        # containers (and the compiled solve programs whose closures pin
        # the row constructors) are released on close — MDP.evict's
        # mesh-keyed cache only tracks materialized shards
        self._mf_mdps: weakref.WeakSet = weakref.WeakSet()
        # device-materialized fleet containers, keyed by (mesh, layout,
        # mode, pad_fleet, instance identities): warm repeated solve_fleet
        # calls skip re-construction, mirroring MDP.place's per-MDP cache.
        # A proper LRU — hit/miss/eviction counters land in the run stats
        # (and the serving program cache builds on the same mechanism).
        self._fleet_cache = LRUCache(_FLEET_CACHE_CAPACITY)
        # serializes stats recording + output-file writes: solves may run
        # concurrently from scheduler/client threads (repro.serve), and
        # interleaved -file_stats jsonl appends must stay line-atomic
        self._io_lock = threading.RLock()
        # -method auto probe results, keyed by the problem family
        # (n, m, gamma, mode): repeat solves of the same family skip the
        # probe phase and reuse the rule-table choice
        self._auto_cache: dict = {}
        _sync_x64(self.options)
        self._apply_kernel_options()

    def _apply_kernel_options(self) -> None:
        """Push kernel-facing options into their process-wide services:
        the XLA flag bundle (must precede backend init to take effect in
        this process) and the tile-autotuner configuration."""
        from repro.kernels import tuning as _tuning
        from repro.utils import xla_flags as _xla_flags

        bundle = self.options.get("-xla_flag_bundle")
        if bundle:
            _xla_flags.apply_bundle(bundle)
        _tuning.configure(
            enabled=self.options.get("-kernel_tune") != "off",
            cache_path=self.options.get("-kernel_tune_cache"))

    # ---- lifecycle ---------------------------------------------------------
    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Release the compiled run-chunk programs, cached meshes and the
        device MDP shards this session placed.

        ``clear_cache_on_close=False`` (the one-shot convenience wrappers)
        leaves the process-wide run-chunk cache alone so other live
        sessions keep their warm programs; the cache itself is bounded
        (:data:`repro.core.driver._RUN_CHUNK_CACHE` evicts past 64).
        Function-backed builders cache their materialized shards keyed by
        mesh (:attr:`repro.api.MDP._device_cache`); evicting the entries
        for this session's meshes stops reused builders from pinning
        device memory for meshes that no longer solve anything."""
        if not self._closed:
            mf = list(self._mf_mdps)
            if self._clear_cache:
                driver.clear_run_cache()
                if mf:
                    # matrix-free solves also compile through the
                    # module-level single-device jit caches, whose closures
                    # pin the RowSpec constructors (and whatever they close
                    # over) — clear_run_cache alone leaves them resident
                    driver._clear_compiled()
            meshes = set(self._mesh_cache.values())
            if self._mesh_override is not None:
                meshes.add(self._mesh_override)
            for mdp in list(self._placed_mdps):
                for mesh in meshes:
                    mdp.evict(mesh)
            for mdp in mf:
                # the O(n) operator container (placement tag + RowSpec);
                # cheap to rebuild, wrong to keep pinned past the session
                mdp._device_cache.pop(("built", "matrix_free"), None)
            self._mf_mdps = weakref.WeakSet()
            self._fleet_cache.clear()
            self._mesh_cache.clear()
            self._closed = True

    @property
    def stats(self) -> list[dict]:
        """Accumulated per-solve statistics (what ``-file_stats`` holds)."""
        with self._io_lock:
            return list(self._stats)

    @property
    def cache_stats(self) -> dict:
        """Counters of the session-owned caches: the device-fleet container
        LRU (hits/misses/evictions) and the current compiled run-chunk
        cache population."""
        return {
            "fleet": self._fleet_cache.stats(),
            "run_chunk_programs": len(driver._RUN_CHUNK_CACHE),
        }

    # ---- placement ---------------------------------------------------------
    def placement(self, opts: Options | None = None, *,
                  fleet_size: int | None = None):
        """``(mesh, layout)`` for a solve: auto-built unless overridden.

        Auto policy: one device -> single-device (no mesh); a single solve
        -> the paper-faithful ``1d`` layout over all devices; a fleet of
        B > 1 -> ``fleet`` layout, instance dim over a leading fleet axis
        whose size is the largest device-count divisor <= B.  ``-layout``
        forces a specific layout ('single' forces no mesh) and ``-fleet``
        the fleet-axis size.
        """
        import jax
        opts = opts or self.options
        layout = opts.get("-layout")
        if layout == "single":
            return None, "1d"
        if self._mesh_override is not None:
            mesh = self._mesh_override
            if layout == "auto":
                has_fleet = "fleet" in mesh.axis_names
                if has_fleet:
                    layout = "fleet2d" if len(mesh.axis_names) > 2 \
                        else "fleet"
                else:
                    layout = "1d"
            return mesh, layout
        n_dev = len(jax.devices())
        if n_dev == 1:
            if layout in ("fleet", "fleet2d"):
                raise ValueError(
                    f"-layout {layout} shards over a multi-device mesh but "
                    f"only one device is visible (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N to fake a "
                    f"mesh on CPU)")
            return None, "1d"
        if layout == "auto":
            layout = "fleet" if (fleet_size or 0) > 1 else "1d"
        if layout in ("fleet", "fleet2d"):
            f = opts.get("-fleet")
            if f is None:
                f = _largest_divisor(n_dev, at_most=max(fleet_size or 1, 1))
            key = (layout, f)
            if key not in self._mesh_cache:
                from repro.launch.mesh import make_fleet_mesh
                self._mesh_cache[key] = make_fleet_mesh(f, layout=layout)
            return self._mesh_cache[key], layout
        shape = (n_dev // 2, 2) if layout == "2d" and n_dev >= 2 \
            else (n_dev, 1)
        key = (layout, shape)
        if key not in self._mesh_cache:
            from repro.launch.mesh import make_host_mesh
            self._mesh_cache[key] = make_host_mesh(shape)
        return self._mesh_cache[key], layout

    # ---- solving -----------------------------------------------------------
    def solve(self, mdp: MDP | CoreMDP, *, monitor=None, stop_criterion=None,
              **overrides) -> SolveResult:
        """Solve one MDP through the session's placement and options.

        ``overrides`` are per-call option overrides (keys with or without
        the leading dash): ``s.solve(mdp, method="vi", atol=1e-6)``.

        ``monitor`` streams one record per outer iteration out of the
        compiled loop — a callable receiving ``{"k", "res", "inner",
        "elapsed"}`` dicts (or pass ``-monitor`` / ``monitor=True`` for
        PETSc-style printed lines).  While monitoring is on, the records
        and the dense convergence-history arrays also land in
        :attr:`stats` / ``-file_stats``.

        ``stop_criterion`` overrides ``-stop_criterion``: a registered
        name (``"atol"`` / ``"rtol"`` / ``"span"`` / user-registered) or a
        traced predicate ``fn(m: repro.api.StopMetrics) -> bool`` compiled
        straight into the loop.
        """
        with trace.counted_span(trace.SOLVE):
            return self._solve(mdp, monitor, stop_criterion, overrides)

    def _solve(self, mdp, monitor, stop_criterion, overrides) -> SolveResult:
        opts, mon_cb, mon_records = self._observe(overrides, monitor,
                                                  stop_criterion)
        mdp = self._wrap(mdp, opts)
        ipi = self._ipi(opts, mdp.mode)
        spec = _methods.get_method(ipi.method)
        adaptive_on = spec.virtual or bool(opts.get("-adapt_on_stagnation"))
        mesh, layout = self.placement(opts)
        core = mdp.place(mesh, layout, mode=ipi.mode,
                         materialize=opts.get("-mdp_materialize"))
        if mdp.deferred and mesh is not None:
            self._placed_mdps.add(mdp)
        if mdp.deferred and isinstance(core, MatrixFreeMDP):
            self._mf_mdps.add(mdp)
        t0 = time.time()
        report = None
        if adaptive_on:
            # virtual methods (-method auto) probe + select, then run
            # supervised; concrete methods under -adapt_on_stagnation skip
            # the probe but get the same stagnation hot-swap safety net
            from repro.adaptive import solve_adaptive
            key = None
            choice = None
            if spec.virtual:
                key = (int(mdp.n), int(mdp.m), float(mdp.gamma), ipi.mode)
                choice = self._auto_cache.get(key)
            r, report = solve_adaptive(
                core, ipi, mesh=mesh, layout=layout,
                probe_iters=opts.get("-probe_iters"), choice=choice,
                checkpoint_dir=opts.get("-checkpoint_dir"),
                chunk=opts.get("-chunk"), verbose=opts.get("-verbose"),
                monitor=mon_cb)
            if key is not None and report.choice is not None:
                self._auto_cache[key] = report.choice
        else:
            r = driver.solve(core, ipi, mesh=mesh, layout=layout,
                             checkpoint_dir=opts.get("-checkpoint_dir"),
                             chunk=opts.get("-chunk"),
                             verbose=opts.get("-verbose"), monitor=mon_cb)
        wall = time.time() - t0
        r = _trim(r, mdp.n)
        self._record([r], [mdp], ipi, opts, mesh, layout, wall, fleet=None,
                     monitor=mon_records, adaptive=report)
        self._write_outputs([r], opts)
        return r

    def solve_fleet(self, mdps: Sequence[MDP | CoreMDP], *, monitor=None,
                    stop_criterion=None, **overrides) -> list[SolveResult]:
        """Solve a fleet of MDPs in batched compiled programs.

        Ragged fleets (instances with very different state counts) are
        grouped into pad-efficient buckets (``-fleet_bucketing auto``) and
        each bucket runs one :func:`repro.core.driver.solve_many` program;
        results come back in input order.  All instances must share one
        ``mode``.

        A bucket of *function-backed* MDPs placed under a fleet-sharded
        layout skips host materialization entirely: each device
        materializes only the ``(B_local, n_local)`` block of the
        instances it owns from the jit'd constructors
        (:func:`repro.api.mdp.place_function_fleet`), so both the fleet
        and state dims of construction scale with the mesh.
        """
        if not mdps:
            return []
        opts, mon_cb, mon_records = self._observe(overrides, monitor,
                                                  stop_criterion)
        wrapped = [self._wrap(m, opts) for m in mdps]
        modes = {m.mode for m in wrapped}
        if len(modes) > 1:
            raise ValueError(f"solve_fleet needs one shared mode, got "
                             f"{sorted(modes)}; solve mixed-mode instances "
                             f"separately")
        ipi = self._ipi(opts, modes.pop())
        spec = _methods.get_method(ipi.method)
        buckets = bucket_indices([m.n for m in wrapped],
                                 policy=opts.get("-fleet_bucketing"))
        ckpt = opts.get("-checkpoint_dir")
        results: list[SolveResult | None] = [None] * len(wrapped)
        auto_choices: list[dict] | None = [] if spec.virtual else None
        t0 = time.time()
        for j, bucket in enumerate(buckets):
            mesh, layout = self.placement(opts, fleet_size=len(bucket))
            bucket_ckpt = ckpt if ckpt is None or len(buckets) == 1 \
                else os.path.join(ckpt, f"bucket{j}")
            bmdps = [wrapped[i] for i in bucket]
            bucket_ipi = ipi
            if spec.virtual:
                # fleets resolve the virtual method ONCE per bucket: probe
                # the bucket's largest instance on a single device and fix
                # the rule-table choice for the whole batched program (no
                # mid-solve supervision — a hot-swap would split the batch)
                bucket_ipi, choice = self._resolve_auto(bmdps, ipi, opts)
                auto_choices.append(dict(
                    bucket=j, method=choice.method, pc_type=choice.pc_type,
                    stop_criterion=choice.stop_criterion,
                    reason=choice.reason))
            payload = self._fleet_cores(bmdps, mesh, layout, ipi.mode, opts)
            origin = None if isinstance(payload, list) else \
                (len(bmdps), max(m.n for m in bmdps))
            # tag records by bucket so interleaved per-bucket streams stay
            # attributable in stats (each bucket restarts k at 0)
            bucket_cb = mon_cb if mon_cb is None or len(buckets) == 1 \
                else (lambda rec, _j=j: mon_cb({**rec, "bucket": _j}))
            rs = driver.solve_many(
                payload, bucket_ipi, mesh=mesh, layout=layout,
                pad_fleet=opts.get("-pad_fleet"), origin=origin,
                checkpoint_dir=bucket_ckpt, chunk=opts.get("-chunk"),
                verbose=opts.get("-verbose"), monitor=bucket_cb)
            for i, r in zip(bucket, rs):
                results[i] = _trim(r, wrapped[i].n)
        wall = time.time() - t0
        mesh, layout = self.placement(opts, fleet_size=len(wrapped))
        fleet_info = dict(size=len(wrapped),
                          buckets=[sorted(b) for b in buckets])
        if auto_choices is not None:
            fleet_info["auto"] = auto_choices
        self._record(results, wrapped, ipi, opts, mesh, layout, wall,
                     fleet=fleet_info, monitor=mon_records)
        self._write_outputs(results, opts)
        return results  # type: ignore[return-value]

    # ---- internals ---------------------------------------------------------
    def _observe(self, overrides, monitor, stop_criterion):
        """Resolve the per-call observability kwargs into the merged
        per-call options plus the monitor callback chain.

        Returns ``(opts, monitor_cb, records)`` — ``records`` is the list
        the callback appends every streamed record to (for :attr:`stats` /
        ``-file_stats``), or ``None`` when monitoring is off.  A callable
        ``stop_criterion`` is registered ad hoc (with span metrics
        enabled); ``monitor=False`` force-disables a session-level
        ``-monitor`` for this call."""
        overrides = dict(overrides)
        if stop_criterion is not None:
            if callable(stop_criterion):
                stop_criterion = _methods.adhoc_stop_criterion(stop_criterion)
            overrides.setdefault("-stop_criterion", stop_criterion)
        if monitor is False:
            overrides.setdefault("-monitor", False)
        elif monitor is not None:
            overrides.setdefault("-monitor", True)
        opts = self._opts(overrides)
        if not opts.get("-monitor"):
            return opts, None, None
        records: list[dict] = []
        sink = monitor if callable(monitor) else _methods.print_monitor

        def mon_cb(rec):
            records.append(rec)
            sink(rec)

        return opts, mon_cb, records

    def _opts(self, overrides: Mapping[str, Any]) -> Options:
        if self._closed:
            raise RuntimeError("this Session is closed; create a new one")
        if not overrides:
            return self.options
        opts = self.options.with_overrides(overrides)
        _sync_x64(opts)        # a per-call dtype override must flip x64 too
        return opts

    def _wrap(self, mdp: MDP | CoreMDP, opts: Options) -> MDP:
        if isinstance(mdp, MDP):
            return mdp
        if isinstance(mdp, (EllMDP, DenseMDP, MatrixFreeMDP)):
            return MDP(mdp, mode=opts.get("-mode"))
        raise TypeError(f"solve wants a repro.api.MDP (or a core "
                        f"EllMDP/DenseMDP/MatrixFreeMDP), got "
                        f"{type(mdp).__name__}")

    def _fleet_cores(self, bmdps: list[MDP], mesh, layout: str, mode: str,
                     opts: Options):
        """What one bucket hands :func:`repro.core.driver.solve_many`:
        the device-materialized batched container for an all-deferred
        bucket under a fleet-sharded layout, else per-instance builds —
        which under ``-mdp_materialize matrix_free`` are O(n) operator
        containers the driver stacks and places itself (no fleet-cache
        entry to manage: there are no device tables to pin)."""
        mat = opts.get("-mdp_materialize")
        if (mesh is not None and layout in partition.FLEET_LAYOUTS
                and mat != "host"
                and all(m.deferred for m in bmdps)
                and len({(m._spec.m, m._spec.nnz) for m in bmdps}) == 1
                and all(m.materialization(mat) == "device" for m in bmdps)):
            pad = opts.get("-pad_fleet")
            # weakly keyed on the builder identities: an entry whose fleet
            # the caller dropped can never be requested again, so purge it
            # (its device container would otherwise stay pinned till close)
            for k in self._fleet_cache.keys():
                if not all(r() is not None for r in k[4]):
                    self._fleet_cache.pop(k)
            key = (mesh, layout, mode, pad,
                   tuple(weakref.ref(m) for m in bmdps))
            batched = self._fleet_cache.get(key)
            if batched is None:
                batched = place_function_fleet(bmdps, mesh, layout, mode,
                                               pad_fleet=pad)
                self._fleet_cache.put(key, batched)
            return batched
        cores = [m.build(mat) for m in bmdps]
        for m, c in zip(bmdps, cores):
            if m.deferred and isinstance(c, MatrixFreeMDP):
                self._mf_mdps.add(m)
        return cores

    def _ipi(self, opts: Options, mdp_mode: str):
        """IPIOptions from the database; the MDP's mode wins unless the
        user explicitly set ``-mode``."""
        ipi = opts.to_ipi()
        if not opts.is_set("-mode") and ipi.mode != mdp_mode:
            ipi = dataclasses.replace(ipi, mode=mdp_mode)
        return ipi

    def _resolve_auto(self, bmdps: list[MDP], ipi, opts: Options):
        """Resolve a virtual method for one fleet bucket: probe the
        bucket's largest instance single-device, run the rule table, and
        return ``(concrete IPIOptions, MethodChoice)``.  Choices are cached
        per problem family (n, m, gamma, mode) so homogeneous fleets probe
        exactly once."""
        from repro.adaptive import probe, select_method
        rep = max(bmdps, key=lambda m: m.n)
        key = (int(rep.n), int(rep.m), float(rep.gamma), ipi.mode)
        choice = self._auto_cache.get(key)
        if choice is None:
            core = rep.place(None, "1d", mode=ipi.mode,
                             materialize=opts.get("-mdp_materialize"))
            profile, _ = probe(core, ipi,
                               probe_iters=opts.get("-probe_iters"))
            choice = select_method(
                profile, deterministic_dots=ipi.deterministic_dots)
            self._auto_cache[key] = choice
        resolved = dataclasses.replace(
            ipi, method=choice.method,
            stop_criterion=choice.stop_criterion,
            pc_type=choice.pc_type if ipi.pc_type == "none"
            else ipi.pc_type)
        return resolved, choice

    def _record(self, results, mdps, ipi, opts: Options, mesh, layout: str,
                wall: float, *, fleet, monitor=None, adaptive=None) -> None:
        entry = {
            "method": ipi.method,
            "mode": ipi.mode,
            "stop_criterion": ipi.stop_criterion,
            "layout": layout if mesh is not None else "single",
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "options": _jsonable(opts.as_dict(explicit_only=True)),
            "wall_s": round(wall, 6),
            "fleet": fleet,
            "solves": [
                {
                    "n": int(m.n), "m": int(m.m),
                    "gamma": float(m.gamma),
                    "converged": bool(r.converged),
                    "diverged": bool(getattr(r, "diverged", False)),
                    "outer_iterations": int(r.outer_iterations),
                    "inner_iterations": int(r.inner_iterations),
                    "residual": float(r.residual),
                    "gap_bound": float(r.gap_bound),
                }
                for m, r in zip(mdps, results)
            ],
        }
        if adaptive is not None:
            entry["adaptive"] = adaptive.as_dict()
        if fleet is not None:
            fleet = dict(fleet, cache=self._fleet_cache.stats())
            entry["fleet"] = fleet
        if monitor is not None:
            # monitoring on: the streamed records plus the dense
            # convergence-history arrays land in the run stats
            entry["monitor"] = sorted(
                monitor, key=lambda r: (r.get("bucket", 0), r["k"]))
            for s, r in zip(entry["solves"], results):
                s["trace_residual"] = [float(x) for x in r.trace_residual]
                s["trace_inner"] = [int(x) for x in r.trace_inner]
        with self._io_lock:
            self._stats.append(entry)

    def _write_outputs(self, results, opts: Options) -> None:
        with self._io_lock:
            self._write_stats(opts)
            for key, field in (("-file_policy", "policy"),
                               ("-file_cost", "v")):
                path = opts.get(key)
                if not path:
                    continue
                _ensure_dir(path)
                arrays = [np.asarray(getattr(r, field)) for r in results]
                if len(arrays) == 1:
                    np.save(path, arrays[0])
                else:
                    np.savez(path, **{f"instance_{i}": a
                                      for i, a in enumerate(arrays)})

    def _write_stats(self, opts: Options) -> None:
        """Persist run statistics.  The default ``jsonl`` format appends
        only the entries written since the last solve — O(1) per solve
        instead of re-serializing the whole accumulated list (which made a
        long-lived serving session O(solves^2) in stats I/O).  ``json``
        keeps the original single-array format (rewritten per solve).
        Toggling the format on one path mid-session forces a full rewrite
        (appending JSONL lines after a JSON array would corrupt both).

        Callers hold ``self._io_lock`` (via :meth:`_write_outputs`):
        concurrent solves from scheduler/client threads append entries and
        advance the per-path ``(format, written)`` cursor under one lock,
        so each entry lands in the file exactly once and every jsonl line
        stays whole."""
        path = opts.get("-file_stats")
        if not path:
            return
        _ensure_dir(path)
        fmt = opts.get("-file_stats_format")
        if fmt == "json":
            with open(path, "w") as f:
                json.dump(self._stats, f, indent=1)
            self._stats_written[path] = ("json", len(self._stats))
            return
        prev_fmt, start = self._stats_written.get(path, ("jsonl", 0))
        if prev_fmt != "jsonl":
            start = 0
        with open(path, "a" if start else "w") as f:
            for entry in self._stats[start:]:
                f.write(json.dumps(entry) + "\n")
        self._stats_written[path] = ("jsonl", len(self._stats))


def madupite_session(options: Options | Mapping[str, Any] | None = None, *,
                     mesh=None) -> Session:
    """Open a solve session (the ``madupite.initialize()`` analogue)::

        with madupite_session({"-method": "vi"}) as s:
            r = s.solve(mdp)
    """
    return Session(options, mesh=mesh)


def _sync_x64(opts: Options) -> None:
    """``-dtype float64`` requires jax_enable_x64, or every array silently
    truncates to f32 while the result claims f64."""
    if opts.get("-dtype") == "float64":
        import jax
        jax.config.update("jax_enable_x64", True)


def _largest_divisor(n: int, *, at_most: int) -> int:
    for d in range(min(n, at_most), 0, -1):
        if n % d == 0:
            return d
    return 1


def _trim(r: SolveResult, n: int) -> SolveResult:
    """Trim a result solved on a padded (device-materialized) MDP back to
    the true state count."""
    if len(r.v) <= n:
        return r
    return dataclasses.replace(r, v=r.v[:n], policy=r.policy[:n])


def _jsonable(d: dict) -> dict:
    return {k: (v if isinstance(v, (int, float, str, bool, type(None)))
                else repr(v)) for k, v in d.items()}


def _ensure_dir(path: str) -> None:
    parent = os.path.dirname(os.path.abspath(path))
    if parent:
        os.makedirs(parent, exist_ok=True)
