import os
# fake CPU devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the step function is lowered with sharding-annotated ShapeDtypeStructs (no
allocation), compiled for the 256-chip single-pod mesh and the 512-chip
2-pod mesh, and the compiled artifact's memory_analysis / cost_analysis /
collective schedule are recorded for EXPERIMENTS.md (§Dry-run, §Roofline).

Usage:
  python -m repro.launch.dryrun --suite lm --mesh pod --out results.json
  python -m repro.launch.dryrun --arch granite-34b --shape train_4k --mesh multipod
  python -m repro.launch.dryrun --suite mdp
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\][^ ]*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        total += numel * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, keyed by op kind (result-shape
    bytes of each collective op in the partitioned module)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_txt, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_txt)
        counts[kind] += 1
    out["counts"] = counts
    return out


def analyze(compiled, lower_s, compile_s) -> dict:
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    rec = dict(
        flops=float(cost.get("flops", -1)),
        bytes_accessed=float(cost.get("bytes accessed", -1)),
        collectives={k: v for k, v in coll.items() if k != "counts"},
        collective_counts=coll["counts"],
        lower_s=round(lower_s, 2), compile_s=round(compile_s, 2),
    )
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        rec[attr] = getattr(mem, attr, None)
    return rec


# ------------------------------------------------------------------------- #
# LM cells                                                                   #
# ------------------------------------------------------------------------- #

def run_lm_cell(arch: str, shape_name: str, mesh) -> dict:
    from repro.configs import get_train_config
    from repro.launch import specs as S
    from repro.train.steps import (make_decode_step, make_prefill_step,
                                   make_train_step)

    si = S.input_specs(arch, shape_name, mesh)
    model, shape = si["model"], si["shape"]
    tcfg = get_train_config(arch)
    t0 = time.time()
    if shape.kind == "train":
        fn = make_train_step(model, tcfg, n_microbatches=si["n_micro"])
        out_shardings = (jax.tree.map(lambda s: s.sharding, si["params"]),
                         jax.tree.map(lambda s: s.sharding, si["opt"]),
                         None)
        lowered = jax.jit(fn, out_shardings=out_shardings).lower(
            si["params"], si["opt"], si["step"], si["batch"])
    elif shape.kind == "prefill":
        fn = make_prefill_step(model)
        lowered = jax.jit(fn).lower(si["params"], si["batch"]["tokens"],
                                    si["batch"].get("patches"))
    else:
        fn = make_decode_step(model)
        cache_sh = jax.tree.map(lambda s: s.sharding, si["cache"])
        lowered = jax.jit(fn, out_shardings=(None, None, cache_sh)).lower(
            si["params"], si["token"], si["cache"])
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return analyze(compiled, t1 - t0, t2 - t1)


# ------------------------------------------------------------------------- #
# MDP (paper) cells                                                          #
# ------------------------------------------------------------------------- #

MDP_CELLS = {
    # name: (n, m, K, layout, method, halo).  A "+pc" suffix on the method
    # ("ipi_gmres+jacobi") compiles the same program with that preconditioner
    # enabled, so the setup + apply FLOPs are charged by cost_analysis; the
    # "auto" method compiles the probe program (a short VI burst) AND the
    # main solve and reports their summed cost — what an adaptive solve pays.
    "mdp_vi_16m": (1 << 24, 16, 16, "1d", "vi", 0),
    "mdp_gmres_16m": (1 << 24, 16, 16, "1d", "ipi_gmres", 0),
    "mdp_gmres_16m_jacobi": (1 << 24, 16, 16, "1d", "ipi_gmres+jacobi", 0),
    "mdp_gmres_16m_bjacobi": (1 << 24, 16, 16, "1d", "ipi_gmres+bjacobi", 0),
    "mdp_auto_16m": (1 << 24, 16, 16, "1d", "auto", 0),
    "mdp_gmres_2d_1m_256a": (1 << 20, 256, 16, "2d", "ipi_gmres", 0),
    "mdp_bicgstab_64m": (1 << 26, 8, 8, "1d", "ipi_bicgstab", 0),
    # beyond-paper layouts (§Perf): banded halo exchange replaces the
    # all-gather of v (maze2d-structured instance, bandwidth = 4096)
    "mdp_vi_16m_halo": (1 << 24, 16, 16, "1d", "vi", 4096),
    "mdp_gmres_16m_halo": (1 << 24, 16, 16, "1d", "ipi_gmres", 4096),
    # dense transition tensor (K=0 marker): backups become MXU matmuls —
    # the compute-bound corner of the solver (small-n, action-rich MDPs)
    "mdp_dense_32k": (1 << 15, 64, 0, "1d", "vi", 0),
}

# Matrix-free cells: the abstract container is an O(n) placement tag plus a
# REAL FN_REGISTRY row spec, so lowering re-traces the constructors inside
# every backup — the compiled cost_analysis therefore charges the per-sweep
# recompute FLOPs automatically, and memory_analysis shows the O(n)
# argument footprint (no table anywhere).
MDP_MF_CELLS = {
    # name: (fn-registry family, family kwargs, layout, method, halo)
    "mdp_mf_vi_64m": ("garnet", dict(n=1 << 26, m=8, k=8), "1d", "vi", 0),
    "mdp_mf_gmres_64m": ("garnet", dict(n=1 << 26, m=8, k=8), "1d",
                         "ipi_gmres", 0),
    # the state-ceiling cell: 2^30 states would need a 100+ GB/device ELL
    # table; the operator solves it in ~GBs of value vectors per device
    "mdp_mf_vi_1g": ("garnet", dict(n=1 << 30, m=8, k=8), "1d", "vi", 0),
    # banded family (sis: band=1) under the halo ring exchange
    "mdp_mf_vi_16m_halo": ("sis", dict(pop=(1 << 24) - 1, n_actions=4),
                           "1d", "vi", 1),
}


def run_mdp_cell(name: str, mesh) -> dict:
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ipi, partition
    from repro.core.mdp import DenseMDP, EllMDP, MatrixFreeMDP

    spec = None
    if name in MDP_MF_CELLS:
        fam, fam_kw, layout, method, halo = MDP_MF_CELLS[name]
        from repro.api.mdp import MDP as _ApiMDP
        spec = _ApiMDP.from_generator(fam, deferred=True,
                                      **fam_kw)._row_spec()
        n, m, k = spec.n, spec.m, spec.nnz
    else:
        n, m, k, layout, method, halo = MDP_CELLS[name]
    axes = partition.mesh_axes(mesh, layout)
    import math
    n_shards = math.prod(mesh.shape[a] for a in (
        axes.state if isinstance(axes.state, tuple) else (axes.state,)))
    m_shards = 1 if axes.action is None else mesh.shape[axes.action]
    if spec is not None:  # matrix-free operator: O(n) tag, no table
        mdp_abs = MatrixFreeMDP(
            tag=jax.ShapeDtypeStruct((n,), jnp.int8),
            gamma=0.9999, n_global=n, m_global=m, spec=spec)
    elif k == 0:  # dense transition tensor
        mdp_abs = DenseMDP(
            p=jax.ShapeDtypeStruct((n, m, n), jnp.float32),
            cost=jax.ShapeDtypeStruct((n, m), jnp.float32),
            gamma=0.9999, n_global=n, m_global=m)
    else:
        mdp_abs = EllMDP(
            idx=jax.ShapeDtypeStruct((n, m, k), jnp.int32),
            val=jax.ShapeDtypeStruct((n, m, k), jnp.float32),
            cost=jax.ShapeDtypeStruct((n, m), jnp.float32),
            gamma=0.9999, n_global=n, m_global=m)
    specs = partition.mdp_pspecs(mdp_abs, axes)
    mdp_sds = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        mdp_abs, specs)
    nl = n // n_shards

    def compile_program(opts):
        state_specs = ipi.SolveState(
            v=P(axes.state), tv=P(axes.state), pi=P(axes.state),
            res=P(), k=P(), inner_total=P(), trace_res=P(), trace_inner=P(),
            res0=P(), span=P(), done=P(), diverged=P(), n_true=P(),
            win=P(axes.state) if halo else P())
        sspec_tree = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  state_specs)
        state_sds = ipi.SolveState(
            v=jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sspec_tree.v),
            tv=jax.ShapeDtypeStruct((n,), jnp.float32,
                                    sharding=sspec_tree.tv),
            pi=jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sspec_tree.pi),
            res=jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=sspec_tree.res),
            k=jax.ShapeDtypeStruct((), jnp.int32, sharding=sspec_tree.k),
            inner_total=jax.ShapeDtypeStruct((), jnp.int32,
                                             sharding=sspec_tree.inner_total),
            trace_res=jax.ShapeDtypeStruct((opts.max_outer + 1,), jnp.float32,
                                           sharding=sspec_tree.trace_res),
            trace_inner=jax.ShapeDtypeStruct((opts.max_outer,), jnp.int32,
                                             sharding=sspec_tree.trace_inner),
            res0=jax.ShapeDtypeStruct((), jnp.float32,
                                      sharding=sspec_tree.res0),
            span=jax.ShapeDtypeStruct((), jnp.float32,
                                      sharding=sspec_tree.span),
            done=jax.ShapeDtypeStruct((), jnp.bool_,
                                      sharding=sspec_tree.done),
            diverged=jax.ShapeDtypeStruct((), jnp.bool_,
                                          sharding=sspec_tree.diverged),
            n_true=jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=sspec_tree.n_true),
            # sync methods carry an empty stale window (async_vi state only)
            win=jax.ShapeDtypeStruct((0,), jnp.float32,
                                     sharding=sspec_tree.win))
        fn = jax.jit(
            jax.shard_map(
                partial(ipi.solve_chunk, opts=opts, axes=axes),
                mesh=mesh,
                in_specs=(partition.mdp_pspecs(mdp_abs, axes),
                          state_specs, P(), P()),
                out_specs=state_specs, check_vma=False))
        t0 = time.time()
        lowered = fn.lower(mdp_sds, state_sds,
                           jax.ShapeDtypeStruct((), jnp.int32),
                           jax.ShapeDtypeStruct((), jnp.int32))
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        return analyze(compiled, t1 - t0, t2 - t1)

    method_full = method
    method, _, pc_type = method.partition("+")
    if method == "auto":
        # an adaptive solve lowers (and pays for) TWO programs: the probe —
        # a short fixed-length VI burst under the never-stop "probe"
        # criterion — and the main solve the policy engine picks; charge
        # both so EXPERIMENTS.md reflects the true compile + step cost
        probe = compile_program(ipi.IPIOptions(
            method="vi", stop_criterion="probe", max_outer=8,
            halo=halo))
        rec = compile_program(ipi.IPIOptions(
            method="ipi_gmres", max_outer=100, max_inner=32,
            restart=16, halo=halo))
        for k_ in ("flops", "bytes_accessed", "lower_s", "compile_s"):
            rec[k_] = round(rec[k_] + probe[k_], 2)
        rec["collectives"] = {k_: v + probe["collectives"].get(k_, 0)
                              for k_, v in rec["collectives"].items()}
        rec["probe_flops"] = probe["flops"]
    else:
        rec = compile_program(ipi.IPIOptions(
            method=method, max_outer=100, max_inner=32, restart=16,
            halo=halo, pc_type=pc_type or "none"))
    rec["layout"] = layout
    rec["method"] = method_full
    rec["nmk"] = (n, m, k)
    # per-device value-window bytes received per backup: the banded layout
    # moves only the +-halo boundary entries, not the full vector — report
    # the actual window so EXPERIMENTS.md rooflines do not charge halo cells
    # for an all-gather they never issue
    itemsize = jnp.dtype(jnp.float32).itemsize
    rec["window_bytes"] = (2 * halo * itemsize if halo
                           else (n - nl) * itemsize)
    if spec is not None:
        # memory crossover: both footprints are linear in n, so the trade
        # is a constant ratio — report it plus the per-host state ceilings
        # each way (the recompute FLOPs the operator pays per sweep are
        # already in rec["flops"]: lowering traced the constructors)
        from repro.kernels import matrix_free as _mf
        krylov = method not in ("vi", "async_vi")
        tb = _mf.table_bytes(n, m, k)
        ob = _mf.operator_bytes(n, k, krylov=krylov)
        host = 16 << 30   # a 16 GB device/host as the reference budget
        rec["table_bytes"] = tb
        rec["operator_bytes"] = ob
        rec["memory_ratio"] = round(tb / ob, 2)
        rec["states_per_16g_materialized"] = host // (tb // n)
        rec["states_per_16g_matrix_free"] = host // (ob // n)
        print(f"[mf] {name}: table {tb / 1e9:.2f} GB vs operator "
              f"{ob / 1e9:.3f} GB ({tb / ob:.0f}x); a 16 GB device holds "
              f"{host // (tb // n):,} materialized vs "
              f"{host // (ob // n):,} matrix-free states", flush=True)
    return rec


# ------------------------------------------------------------------------- #
# CLI                                                                        #
# ------------------------------------------------------------------------- #

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=("lm", "mdp", "all"), default=None)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="both")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import ARCHS, cells
    from repro.launch.mesh import make_production_mesh

    meshes = {"pod": False, "multipod": True}
    mesh_names = [args.mesh] if args.mesh != "both" else ["pod", "multipod"]

    jobs = []
    if args.arch:
        shapes = [args.shape] if args.shape else \
            [s.name for s in cells(args.arch)]
        jobs += [("lm", args.arch, s) for s in shapes]
    if args.suite in ("lm", "all"):
        jobs += [("lm", a, s.name) for a in ARCHS for s in cells(a)]
    if args.suite in ("mdp", "all"):
        jobs += [("mdp", name, "")
                 for name in list(MDP_CELLS) + list(MDP_MF_CELLS)]

    results = {}
    for mesh_name in mesh_names:
        mesh = make_production_mesh(multi_pod=meshes[mesh_name])
        for kind, a, s in jobs:
            key = f"{a}/{s}/{mesh_name}" if s else f"{a}/{mesh_name}"
            t0 = time.time()
            try:
                rec = run_lm_cell(a, s, mesh) if kind == "lm" \
                    else run_mdp_cell(a, mesh)
                rec["status"] = "ok"
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            rec["wall_s"] = round(time.time() - t0, 2)
            results[key] = rec
            flops = rec.get("flops", 0)
            print(f"[{rec['status']}] {key}  wall={rec['wall_s']}s "
                  f"flops={flops:.3e} "
                  f"coll={sum(rec.get('collectives', {}).values()):.3e}B",
                  flush=True)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_fail = sum(1 for r in results.values() if r["status"] != "ok")
    print(f"done: {len(results) - n_fail}/{len(results)} ok", flush=True)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
