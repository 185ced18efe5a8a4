"""MDP solve CLI — a thin shell over the options database + session layer.

Every solver/placement/output setting is an options-database key
(:mod:`repro.api.options`); the named flags below are convenience aliases
for the common ones, and ``--option key=value`` (repeatable) reaches the
full registry.  ``MADUPITE_OPTIONS`` in the environment is ingested first
(precedence: explicit flag / ``--option`` > environment > defaults):

    PYTHONPATH=src python -m repro.launch.solve --instance maze2d --size 64 \
        --method ipi_gmres --atol 1e-8 --ckpt-dir /tmp/mdp_run

    MADUPITE_OPTIONS="-method vi -atol 1e-6" \
    PYTHONPATH=src python -m repro.launch.solve --instance garnet --n 5000

    PYTHONPATH=src python -m repro.launch.solve --instance sis --n 2000 \
        --option mode=maxreward --option file_stats=run.json

Fleet mode: ``--batch N`` solves N instances in batched compiled programs
(``Session.solve_fleet``; a seed ensemble, or a gamma-conditioning sweep
with ``--sweep-gamma LO HI``).  The session auto-picks the mesh layout —
``fleet``-sharded over >1 device — overridable with ``--option layout=...``
/ ``--option fleet=F``:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.solve --instance garnet \
        --n 2000 --batch 16 --option layout=fleet --option fleet=8
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import MDP, Options, Session
from repro.core import generators


def _gen_kwargs(args) -> dict:
    if args.instance == "garnet":
        return dict(n=args.n, m=args.m, k=args.k, gamma=args.gamma,
                    seed=args.seed)
    if args.instance == "maze2d":
        return dict(size=args.size, gamma=args.gamma, seed=args.seed)
    if args.instance == "sis":
        return dict(pop=args.n, n_actions=args.m, gamma=args.gamma,
                    seed=args.seed)
    if args.instance == "chain_walk":
        return dict(n=args.n, gamma=args.gamma)
    raise ValueError(args.instance)


def build_instance(args) -> MDP:
    if args.load:
        return MDP.from_file(args.load)
    return MDP.from_generator(args.instance, **_gen_kwargs(args))


def build_fleet(args) -> list:
    """``--batch N`` fleet: seed ensemble, or a gamma sweep with
    ``--sweep-gamma``."""
    kw = _gen_kwargs(args)
    sweep = None
    if args.sweep_gamma is not None:
        lo, hi = args.sweep_gamma
        # log-spaced in (1 - gamma): resolves the conditioning ~ 1/(1-gamma)
        sweep = {"gamma": list(1.0 - np.geomspace(1 - lo, 1 - hi,
                                                  args.batch))}
    return generators.generate_many(args.instance, args.batch, sweep=sweep,
                                    **kw)


def build_options(args) -> Options:
    """Flags -> options database (env < flags/--option; flags the user did
    not pass fall back to CLI-flavored soft defaults, which still lose to
    ``MADUPITE_OPTIONS``)."""
    opts = Options.from_sources()                    # env ingested here
    flag_map = {"method": "-method", "ksp_type": "-ksp_type",
                "atol": "-atol", "stop_criterion": "-stop_criterion",
                "max_outer": "-max_outer", "dtype": "-dtype",
                "layout": "-layout", "fleet": "-fleet",
                "ckpt_dir": "-checkpoint_dir", "mode": "-mode"}
    for flag, key in flag_map.items():
        val = getattr(args, flag)
        if val is not None:
            opts.set(key, val, source="cli")
    if args.single_device:
        opts.set("-layout", "single", source="cli")
    if args.monitor:
        opts.set("-monitor", True, source="cli")
    opts.ingest_cli(args.option)
    # the CLI has always defaulted to PETSc-style f64 and a deep outer cap;
    # keep that, but let the environment override
    if not opts.is_set("-dtype"):
        opts.set("-dtype", "float64", source="default")
    if not opts.is_set("-max_outer"):
        opts.set("-max_outer", 2000, source="default")
    if not opts.is_set("-verbose"):
        opts.set("-verbose", True, source="default")
    return opts


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--instance", default="garnet",
                    choices=["garnet", "maze2d", "sis", "chain_walk"])
    ap.add_argument("--load", default=None, help="load an MDP saved by io.py")
    ap.add_argument("--n", type=int, default=10000)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--gamma", type=float, default=0.99)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--method", default=None,
                    help="option -method (any live-registry name)")
    ap.add_argument("--ksp-type", default=None,
                    help="option -ksp_type (inner solver sugar; any "
                         "live-registry name incl. user-registered)")
    ap.add_argument("--mode", default=None,
                    choices=["mincost", "maxreward"], help="option -mode")
    ap.add_argument("--atol", type=float, default=None, help="option -atol")
    ap.add_argument("--stop-criterion", default=None,
                    help="option -stop_criterion (atol|rtol|span|registered)")
    ap.add_argument("--monitor", action="store_true",
                    help="option -monitor (per-outer-iteration records)")
    ap.add_argument("--max-outer", type=int, default=None,
                    help="option -max_outer")
    ap.add_argument("--layout", default=None,
                    choices=["auto", "single", "1d", "2d", "fleet",
                             "fleet2d"], help="option -layout")
    ap.add_argument("--fleet", type=int, default=None,
                    help="option -fleet (fleet-axis size)")
    ap.add_argument("--dtype", default=None, help="option -dtype")
    ap.add_argument("--ckpt-dir", default=None,
                    help="option -checkpoint_dir")
    ap.add_argument("--single-device", action="store_true",
                    help="option -layout=single")
    ap.add_argument("--option", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="set any options-database key (repeatable; the "
                         "leading dash is optional), e.g. "
                         "--option mode=maxreward")
    ap.add_argument("--batch", type=int, default=1,
                    help="solve a fleet of N instances in batched "
                         "programs (seed ensemble unless --sweep-gamma)")
    ap.add_argument("--sweep-gamma", type=float, nargs=2, default=None,
                    metavar=("LO", "HI"),
                    help="with --batch: gamma sweep over [LO, HI] instead "
                         "of a seed ensemble")
    args = ap.parse_args(argv)

    if args.sweep_gamma is not None and args.batch <= 1:
        raise SystemExit("--sweep-gamma needs --batch N (the sweep IS the "
                         "fleet); e.g. --batch 8 --sweep-gamma 0.9 0.9999")
    opts = build_options(args)
    if opts.get("-layout") in ("fleet", "fleet2d") and args.batch <= 1:
        raise SystemExit(f"-layout {opts.get('-layout')} shards the fleet "
                         "dim; it needs a fleet (--batch N)")

    with Session(opts) as session:
        mesh, layout = session.placement(
            fleet_size=args.batch if args.batch > 1 else None)
        if mesh is not None:
            print(f"[solve] distributed over mesh {dict(mesh.shape)} "
                  f"layout={layout}")

        if args.batch > 1:
            if args.load:
                raise SystemExit("--batch does not combine with --load")
            fleet = build_fleet(args)
            print(f"[solve] fleet B={args.batch} instance={args.instance} "
                  f"n={fleet[0].n_global} m={fleet[0].m_global} "
                  f"gammas={[round(float(m.gamma), 6) for m in fleet]}")
            t0 = time.time()
            results = session.solve_fleet(fleet)
            wall = time.time() - t0
            for b, r in enumerate(results):
                print(f"[solve] [{b}] {r.summary()}")
            print(f"[solve] fleet wall={wall:.2f}s "
                  f"({wall / args.batch:.2f}s/instance amortized)")
            return 0 if all(r.converged for r in results) else 1

        mdp = build_instance(args)
        print(f"[solve] instance={args.instance} n={mdp.n} m={mdp.m} "
              f"gamma={mdp.gamma} mode={mdp.mode}")
        t0 = time.time()
        r = session.solve(mdp)
        print(f"[solve] {r.summary()}  wall={time.time()-t0:.2f}s")
        adaptive = session.stats[-1].get("adaptive")
        if adaptive is not None:
            # what -method auto / -adapt_on_stagnation actually ran
            choice = adaptive.get("choice")
            if choice is not None:
                print(f"[solve] auto-selected {choice['method']} "
                      f"(stop={choice['stop_criterion']} "
                      f"pc={choice['pc_type']}): {choice['reason']}")
            for s in adaptive["swaps"]:
                print(f"[solve] hot-swap at k={s['k']}: "
                      f"{s['from_method']} -> {s['to_method']} "
                      f"(pc={s['pc_type']}) — {s['reason']}")
            if adaptive["methods"]:
                print(f"[solve] methods run: "
                      f"{' -> '.join(adaptive['methods'])}")
        print(f"[solve] ||v - v*||_inf <= {r.gap_bound:.3e} (certificate)")
        return 0 if r.converged else 1


if __name__ == "__main__":
    from repro.utils import compile_cache
    compile_cache.enable()
    raise SystemExit(main())
