import os
# fake CPU devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline-term extraction (per arch x shape cell, single-pod mesh).

Methodology (see EXPERIMENTS.md §Roofline): XLA's cost analysis counts
while-loop bodies ONCE, so the full-depth scan-over-layers lowering
undercounts flops/bytes/collectives.  We therefore lower *reduced-depth,
unrolled* variants at full width (loop-free HLO -> exact counts), fit the
per-layer cost linearly in depth, and evaluate at the real depth:

    dense/moe/ssm/vlm : f(L) = c + a.L          (two lowers, L=1,2)
    hybrid (zamba2)   : f = c + a.L_mamba + s.N_shared   (three lowers)
    encdec (whisper)  : f = c + e.L_enc + d.L_dec        (three lowers)

Train cells are lowered with n_microbatches=1 and scaled by the real
microbatch count (grad accumulation repeats the identical body; the
optimizer-update overcount is <0.1% and noted).  MDP solver terms are lowered
loop-free directly (one Bellman backup / one policy matvec per record).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (collective bytes are per-device, so the term uses one link's bandwidth).
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _counts(lowered):
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    from repro.launch.dryrun import collective_bytes
    coll = collective_bytes(compiled.as_text())
    return dict(flops=float(cost.get("flops", 0)),
                bytes=float(cost.get("bytes accessed", 0)),
                coll=float(sum(v for k, v in coll.items()
                               if k != "counts")),
                coll_by_kind={k: v for k, v in coll.items()
                              if k != "counts"})


def _lower_cell(arch, shape_name, mesh, cfg_override):
    """Lower one (possibly reduced-depth) unrolled cell; return counts."""
    import repro.launch.specs as S
    from repro.configs import get_train_config
    from repro.models import build_model
    from repro.train.steps import (make_decode_step, make_prefill_step,
                                   make_train_step)

    # Patch the registry config via monkey-patched get_config path:
    # easier: rebuild specs manually with the override config.
    from repro.configs.base import SHAPES
    import repro.configs as C
    import repro.train.sharding as shd
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = cfg_override
    shape = SHAPES[shape_name]
    tcfg = get_train_config(arch)
    model = build_model(cfg)
    pshapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    if tcfg.replicate_params:
        pspecs = jax.tree.map(lambda s: P(*([None] * len(s.shape))), pshapes)
    else:
        pspecs = shd.infer_param_specs(pshapes, mesh)
    sds = lambda s, sp: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, sp))
    psds = jax.tree.map(sds, pshapes, pspecs)

    # borrow the shape-dependent builders by faking the registry entry
    orig_get = C.get_config
    C.get_config = lambda a: cfg if a == arch else orig_get(a)
    S_get = S.get_config
    S.get_config = C.get_config
    try:
        if shape.kind == "train":
            from repro.train.optimizer import init_opt_state
            oshapes = jax.eval_shape(lambda p: init_opt_state(p, tcfg),
                                     pshapes)
            if tcfg.replicate_params:
                ospecs = jax.tree.map(
                    lambda s: P(*([None] * len(s.shape))), oshapes)
            else:
                ospecs = shd.infer_param_specs(oshapes, mesh)
            osds = jax.tree.map(sds, oshapes, ospecs)
            batch = S.batch_specs(arch, shape, mesh)
            fn = make_train_step(model, tcfg, n_microbatches=1, unroll=True)
            out_sh = (jax.tree.map(lambda s: s.sharding, psds),
                      jax.tree.map(lambda s: s.sharding, osds), None)
            lowered = jax.jit(fn, out_shardings=out_sh).lower(
                psds, osds, jax.ShapeDtypeStruct((), jnp.int32), batch)
            scale = S.n_microbatches(arch, shape, mesh)
        elif shape.kind == "prefill":
            batch = S.batch_specs(arch, shape, mesh)
            fn = make_prefill_step(model, unroll=True)
            lowered = jax.jit(fn).lower(psds, batch["tokens"],
                                        batch.get("patches"))
            scale = 1
        else:
            cache = S.cache_specs(arch, shape, mesh)
            token = S.decode_token_specs(arch, shape, mesh)
            fn = make_decode_step(model, unroll=True)
            cache_sh = jax.tree.map(lambda s: s.sharding, cache)
            lowered = jax.jit(fn, out_shardings=(None, None, cache_sh)).lower(
                psds, token, cache)
            scale = 1
    finally:
        C.get_config = orig_get
        S.get_config = orig_get
    c = _counts(lowered)
    return {k: (v * scale if k != "coll_by_kind" else
                {kk: vv * scale for kk, vv in v.items()})
            for k, v in c.items()}


def lm_cell_terms(arch: str, shape_name: str, mesh) -> dict:
    """Fit reduced-depth counts to the full config; return roofline terms."""
    from repro.configs import get_config
    cfg = get_config(arch)
    rep = dataclasses.replace

    if cfg.family == "hybrid":
        f1 = _lower_cell(arch, shape_name, mesh,
                         rep(cfg, n_layers=1, shared_attn_every=0))
        f2 = _lower_cell(arch, shape_name, mesh,
                         rep(cfg, n_layers=2, shared_attn_every=0))
        f2s = _lower_cell(arch, shape_name, mesh,
                          rep(cfg, n_layers=2, shared_attn_every=2))
        n_sites = cfg.n_layers // cfg.shared_attn_every
        fit = lambda k: (f1[k] + (cfg.n_layers - 1) * (f2[k] - f1[k])
                         + n_sites * (f2s[k] - f2[k]))
    elif cfg.family == "encdec":
        f11 = _lower_cell(arch, shape_name, mesh,
                          rep(cfg, n_layers=1, encoder_layers=1))
        f21 = _lower_cell(arch, shape_name, mesh,
                          rep(cfg, n_layers=2, encoder_layers=1))
        f12 = _lower_cell(arch, shape_name, mesh,
                          rep(cfg, n_layers=1, encoder_layers=2))
        fit = lambda k: (f11[k]
                         + (cfg.n_layers - 1) * (f21[k] - f11[k])
                         + (cfg.encoder_layers - 1) * (f12[k] - f11[k]))
    else:
        f1 = _lower_cell(arch, shape_name, mesh, rep(cfg, n_layers=1))
        f2 = _lower_cell(arch, shape_name, mesh, rep(cfg, n_layers=2))
        fit = lambda k: f1[k] + (cfg.n_layers - 1) * (f2[k] - f1[k])

    flops, bts, coll = fit("flops"), fit("bytes"), fit("coll")
    return finish_terms(arch, shape_name, mesh, flops, bts, coll)


def finish_terms(arch, shape_name, mesh, flops, bts, coll) -> dict:
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    import math
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = math.prod(mesh.shape.values())
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        model_flops = 6 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2 * n_active * shape.global_batch  # one token
    t_comp = flops / PEAK_FLOPS
    t_mem = bts / HBM_BW
    t_coll = coll / ICI_BW
    dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
    return dict(
        arch=arch, shape=shape_name,
        flops_per_device=flops, bytes_per_device=bts,
        collective_bytes_per_device=coll,
        compute_s=t_comp, memory_s=t_mem, collective_s=t_coll,
        dominant=dom[1],
        model_flops_global=model_flops,
        model_flops_per_device=model_flops / chips,
        useful_flops_ratio=(model_flops / chips) / max(flops, 1),
        roofline_fraction=max(
            min((model_flops / chips) / PEAK_FLOPS, t_comp)
            / max(t_comp, t_mem, t_coll, 1e-30), 0.0),
    )


# --------------------------------------------------------------------------- #
# MDP solver roofline (loop-free lowers of the per-iteration bodies)          #
# --------------------------------------------------------------------------- #

def mdp_terms(name: str, mesh) -> dict:
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import bellman, partition
    from repro.core.mdp import EllMDP
    from repro.launch.dryrun import MDP_CELLS

    import math
    from repro.core.mdp import DenseMDP
    n, m, k, layout, method, halo = MDP_CELLS[name]
    axes = partition.mesh_axes(mesh, layout)
    if k == 0:  # dense (MXU) representation
        mdp_abs = DenseMDP(p=jax.ShapeDtypeStruct((n, m, n), jnp.float32),
                           cost=jax.ShapeDtypeStruct((n, m), jnp.float32),
                           gamma=0.9999, n_global=n, m_global=m)
    else:
        mdp_abs = EllMDP(idx=jax.ShapeDtypeStruct((n, m, k), jnp.int32),
                         val=jax.ShapeDtypeStruct((n, m, k), jnp.float32),
                         cost=jax.ShapeDtypeStruct((n, m), jnp.float32),
                         gamma=0.9999, n_global=n, m_global=m)
    specs = partition.mdp_pspecs(mdp_abs, axes)
    ns = lambda sp: NamedSharding(mesh, sp)
    mdp_sds = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns(sp)),
        mdp_abs, specs)
    v_sds = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=ns(P(axes.state)))

    def one_vi_iteration(mdp, v):
        v_g = bellman.gather_v(v, axes, halo=halo)
        tv, pi = bellman.backup(mdp, v_g, axes, halo=halo)
        res = axes.pmax_state(jnp.max(jnp.abs(tv - v)))
        return tv, pi, res

    fn = jax.jit(jax.shard_map(
        one_vi_iteration, mesh=mesh, in_specs=(specs, P(axes.state)),
        out_specs=(P(axes.state), P(axes.state), P()), check_vma=False))
    c = _counts(fn.lower(mdp_sds, v_sds))

    chips = math.prod(mesh.shape.values())
    # useful backup flops: 2nmK sparse, 2*n^2*m dense
    model_flops = 2.0 * n * m * (k if k else n)
    t_comp = c["flops"] / PEAK_FLOPS
    t_mem = c["bytes"] / HBM_BW
    t_coll = c["coll"] / ICI_BW
    dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
    return dict(arch=name, shape=f"backup[{layout}]",
                flops_per_device=c["flops"], bytes_per_device=c["bytes"],
                collective_bytes_per_device=c["coll"],
                compute_s=t_comp, memory_s=t_mem, collective_s=t_coll,
                dominant=dom[1], model_flops_global=model_flops,
                model_flops_per_device=model_flops / chips,
                useful_flops_ratio=(model_flops / chips) / max(c["flops"], 1),
                roofline_fraction=(model_flops / chips / PEAK_FLOPS)
                / max(t_comp, t_mem, t_coll, 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=("lm", "mdp", "all"), default="all")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="results/roofline.json")
    args = ap.parse_args()

    from repro.configs import ARCHS, cells
    from repro.launch.dryrun import MDP_CELLS
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    jobs = []
    if args.arch:
        shapes = [args.shape] if args.shape else \
            [s.name for s in cells(args.arch)]
        jobs += [("lm", args.arch, s) for s in shapes]
    if args.suite in ("lm", "all") and not args.arch:
        jobs += [("lm", a, s.name) for a in ARCHS for s in cells(a)]
    if args.suite in ("mdp", "all") and not args.arch:
        jobs += [("mdp", c, "") for c in MDP_CELLS]

    results = {}
    for kind, a, s in jobs:
        key = f"{a}/{s}" if s else a
        t0 = time.time()
        try:
            rec = lm_cell_terms(a, s, mesh) if kind == "lm" \
                else mdp_terms(a, mesh)
            rec["status"] = "ok"
        except Exception as e:  # noqa: BLE001
            import traceback
            rec = {"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-1500:]}
        rec["wall_s"] = round(time.time() - t0, 1)
        results[key] = rec
        if rec["status"] == "ok":
            print(f"[ok] {key:36s} dom={rec['dominant']:10s} "
                  f"comp={rec['compute_s']:.2e}s mem={rec['memory_s']:.2e}s "
                  f"coll={rec['collective_s']:.2e}s "
                  f"useful={rec['useful_flops_ratio']:.3f}", flush=True)
        else:
            print(f"[FAIL] {key}: {rec['error']}", flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
