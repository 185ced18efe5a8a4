"""MDP serving CLI — drive a :class:`repro.serve.Server` with a workload.

Stands up the in-process batched solve server and replays a request
stream into it from concurrent client threads, with Poisson arrivals:

    # generator-driven: 32 garnet requests, ragged state counts, ~50 req/s
    PYTHONPATH=src python -m repro.launch.serve --requests 32 \
        --instance garnet --n-choices 256,384 --m 8 --rate 50

    # file-driven: one JSON object per line
    PYTHONPATH=src python -m repro.launch.serve --workload reqs.jsonl

    # fleet-sharded buckets over 8 fake devices
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --requests 16 --rate 100

A workload-file line is ``{"instance": "garnet", "n": 256, "m": 8,
"seed": 3, "gamma": 0.95, "overrides": {"-atol": 1e-6},
"monitor": false}`` — generator kwargs at the top level, per-request
solver-option overrides under ``"overrides"``.

Server knobs are options-database keys (``-serve_batch_window``,
``-serve_max_queue``, ``-serve_max_states``, ``-serve_max_batch``,
``-serve_program_cache``, ``-serve_slot_policy``) reachable through
``--option key=value`` or ``MADUPITE_OPTIONS``; ``--window`` is sugar for
the batching window.  Exits non-zero when any request fails or is
rejected.
"""

from __future__ import annotations

import argparse
import json
import random
import threading
import time

from repro.api import MDP, Options
from repro.serve import AdmissionError, Server
from repro.serve.stats import percentile


def _parse_workload_file(path: str) -> list[dict]:
    specs = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                spec = json.loads(line)
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{lineno}: bad JSON: {e}")
            if "instance" not in spec:
                raise SystemExit(f"{path}:{lineno}: missing 'instance'")
            specs.append(spec)
    return specs


def _generate_workload(args) -> list[dict]:
    """Ragged synthetic workload: state counts drawn from --n-choices."""
    rng = random.Random(args.seed)
    choices = [int(x) for x in args.n_choices.split(",")]
    specs = []
    for i in range(args.requests):
        n = rng.choice(choices)
        spec = {"instance": args.instance, "gamma": args.gamma}
        if args.instance == "garnet":
            spec.update(n=n, m=args.m, k=args.k, seed=args.seed + i)
        elif args.instance == "maze2d":
            spec.update(size=max(2, round(n ** 0.5)), seed=args.seed + i)
        elif args.instance == "sis":
            spec.update(pop=n, n_actions=args.m, seed=args.seed + i)
        else:  # chain_walk
            spec.update(n=n)
        specs.append(spec)
    return specs


def _build_mdp(spec: dict) -> MDP:
    kw = {k: v for k, v in spec.items()
          if k not in ("instance", "overrides", "monitor")}
    return MDP.from_generator(spec["instance"], **kw)


def build_options(args) -> Options:
    opts = Options.from_sources()                    # env ingested here
    if args.window is not None:
        opts.set("-serve_batch_window", args.window, source="cli")
    if args.monitor:
        opts.set("-monitor", True, source="cli")
    opts.ingest_cli(args.option)
    if not opts.is_set("-dtype"):
        opts.set("-dtype", "float64", source="default")
    if not opts.is_set("-max_outer"):
        opts.set("-max_outer", 2000, source="default")
    return opts


def _submit_clients(server: Server, specs: list[dict], rate: float,
                    seed: int, monitor: bool):
    """One client thread per request, started on a Poisson arrival clock
    (exponential inter-arrival gaps at ``rate`` req/s)."""
    rng = random.Random(seed)
    outcomes: list[dict | None] = [None] * len(specs)

    def client(i: int, spec: dict) -> None:
        mon = bool(spec.get("monitor", monitor))
        overrides = spec.get("overrides", {})
        t0 = time.monotonic()
        try:
            req = server.submit(_build_mdp(spec), monitor=mon, **overrides)
            n_records = 0
            if mon:
                for _ in server.stream(req):
                    n_records += 1
            res = req.result()
            outcomes[i] = {"ok": True, "converged": bool(res.converged),
                           "outer": int(res.outer_iterations),
                           "latency": time.monotonic() - t0,
                           "records": n_records}
        except AdmissionError as e:
            outcomes[i] = {"ok": False, "rejected": e.reason,
                           "error": str(e)}
        except Exception as e:  # noqa: BLE001 — report, don't hang the run
            outcomes[i] = {"ok": False, "error": f"{type(e).__name__}: {e}"}

    threads = []
    for i, spec in enumerate(specs):
        t = threading.Thread(target=client, args=(i, spec), daemon=True)
        threads.append(t)
        t.start()
        if rate > 0 and i + 1 < len(specs):
            time.sleep(rng.expovariate(rate))
    for t in threads:
        t.join()
    return outcomes


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", default=None,
                    help="JSONL request file (one spec per line); "
                         "otherwise a synthetic workload is generated")
    ap.add_argument("--requests", type=int, default=16,
                    help="generated workload size")
    ap.add_argument("--instance", default="garnet",
                    choices=["garnet", "maze2d", "sis", "chain_walk"])
    ap.add_argument("--n-choices", default="256,384",
                    help="comma-separated state counts the generated "
                         "workload samples from (ragged shape buckets)")
    ap.add_argument("--m", type=int, default=8)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--gamma", type=float, default=0.95)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate in requests/second "
                         "(0 = submit all at once)")
    ap.add_argument("--window", type=float, default=None,
                    help="option -serve_batch_window (batching linger, s)")
    ap.add_argument("--monitor", action="store_true",
                    help="stream per-iteration records for every request")
    ap.add_argument("--option", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="set any options-database key (repeatable; the "
                         "leading dash is optional), e.g. "
                         "--option serve_max_batch=16")
    args = ap.parse_args(argv)

    specs = (_parse_workload_file(args.workload) if args.workload
             else _generate_workload(args))
    if not specs:
        raise SystemExit("empty workload")
    opts = build_options(args)

    with Server(opts) as server:
        mesh, layout = server.session.placement()
        if mesh is not None:
            print(f"[serve] mesh {dict(mesh.shape)} layout={layout}")
        print(f"[serve] {len(specs)} requests, Poisson rate="
              f"{args.rate}/s, window="
              f"{opts.get('-serve_batch_window')}s")
        t0 = time.monotonic()
        outcomes = _submit_clients(server, specs, args.rate, args.seed,
                                   args.monitor)
        wall = time.monotonic() - t0
        server.drain()
        st = server.stats()

    ok = [o for o in outcomes if o and o.get("ok")]
    bad = [o for o in outcomes if not (o and o.get("ok"))]
    lats = sorted(o["latency"] for o in ok)
    print(f"[serve] completed={len(ok)}/{len(specs)} wall={wall:.2f}s "
          f"throughput={len(ok) / wall:.1f} req/s")
    if lats:
        print(f"[serve] latency p50={percentile(lats, 50) * 1e3:.1f}ms "
              f"p95={percentile(lats, 95) * 1e3:.1f}ms")
    pc = st["program_cache"]
    print(f"[serve] dispatches={st['dispatches']} "
          f"mean_batch={st['batch']['mean_size']:.1f} "
          f"padded_lanes={st['padded_lanes']}")
    print(f"[serve] program_cache hit_rate={pc['hit_rate']:.2f} "
          f"(hits={pc['hits']} misses={pc['misses']} "
          f"evictions={pc['evictions']})")
    for o in bad:
        print(f"[serve] FAILED: {o}")
    return 0 if not bad else 1


if __name__ == "__main__":
    from repro.utils import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
