"""One benchmark run: one cell, one seed, one window.

``BENCHMARK.json`` names the cells; a cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The mix's ``kind`` names the driver,
``bench/drivers/<kind>.py``, which builds the cell's data from the seed,
warms up, measures, and checks its answers against the reference.  The
solve driver builds the instance family that the configuration's
``family`` names, ``bench/families/<family>.py``.  Each per-layer metric
is read by ``bench/metrics/<metric>.py`` from the facts the driver
gathered.  Nothing here lists a cell, a mix, a family or a metric: adding
one is adding files and entries.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


class NoChip(RuntimeError):
    """The accelerator the cell needs is not there."""


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> tuple:
    """``(cell, config, traffic)`` for ``workload``, read from their files."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_for(spec: dict, cell: str, traced: bool) -> list:
    """The metric entries a run of ``cell`` prints: its end-to-end metrics,
    or, traced, the per-layer metrics that read something in it."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}

    def reads_here(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in names

    return [m for m in spec["per_layer"] if reads_here(m)]


def reader(name: str):
    """``read(facts) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files, the run's arguments, and the
    process-wide services (compile clock, devices)."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float            # time.monotonic() at process start
    clock: object
    devices: list

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span in the profiler's trace (``bench.<name>``)."""
        import jax

        with jax.profiler.TraceAnnotation("bench." + name):
            yield

    def log(self, tag: str, **kv) -> None:
        print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
              flush=True)


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""

    e2e: dict                 # end-to-end metric name -> value
    facts: dict               # what the per-layer readers read
    checks: dict              # compared number -> (value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    busy_s: float | None = None
    window_s: float | None = None
    breakdown: dict | None = None


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def result_line(spec: dict, cell: dict, traced: bool, out: Outcome,
                devices) -> dict:
    metrics = {}
    for m in metrics_for(spec, cell["name"], traced):
        if traced:
            value = reader(m["name"])(out.facts)
        else:
            value = out.e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = out.failed == 0 and out.attempted > 0 and all(
        v <= lim for v, lim in out.checks.values())
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = out.busy_s
        device["window_s"] = out.window_s
        if out.breakdown is not None:
            line["breakdown"] = out.breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def _finite(x):
    """JSON has no infinity: a number that is not finite prints as null."""
    return x if isinstance(x, (int, str, bool)) or x is None \
        or math.isfinite(x) else None


def _clean(obj):
    if isinstance(obj, dict):
        return {k: _clean(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_clean(v) for v in obj]
    if isinstance(obj, float):
        return _finite(obj)
    return obj


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             persistent_cache: bool = True,
             config_override: dict | None = None,
             traffic_override: dict | None = None) -> dict:
    """Run one cell and return its result line (a dict).

    ``require_chip=False`` and the overrides exist for the benchmark's own
    tests, which drive a run on the CPU at small sizes."""
    spec = load_spec()
    cell, config, traffic = resolve(spec, workload)
    config = {**config, **(config_override or {})}
    traffic = {**traffic, **(traffic_override or {})}
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise NoChip(f"no src/repro in {ROOT}: run from a madupite checkout")
    if persistent_cache:
        # inside the checkout, at a fixed path: the path is part of the
        # cache key, and the two sides of a comparison share nothing
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
        if len(devices) != cell["chips"]:
            raise NoChip(f"{workload} needs {cell['chips']} chip(s); JAX "
                         f"found {len(devices)}")
    if persistent_cache:
        from repro.utils import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.clock import CompileClock

    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, t_start=t_start,
              clock=CompileClock(), devices=devices)
    ctx.log("env", workload=workload, seed=seed, seconds=seconds,
            trace=int(trace), device=devices[0].device_kind,
            count=len(devices), cache=os.environ.get(
                "JAX_COMPILATION_CACHE_DIR"))
    out = driver(traffic["kind"]).run(ctx)
    return _clean(result_line(spec, cell, trace, out, devices))


def emit(line: dict) -> None:
    """The checks as the last lines on stderr, the result as the last line
    on stdout."""
    for k, c in line["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    emit(line)
    return 0
