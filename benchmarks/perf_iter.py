import os
# fake CPU devices only: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf-iteration driver: hypothesis -> change -> re-lower -> re-analyse.

Each --variant toggles one optimization lever; the tool lowers the cell with
the lever applied and reports the three roofline terms so before/after pairs
land in EXPERIMENTS.md §Perf.

Levers (comma-separated in --variant):
  embed_novocabfsdp   embed table: TP on vocab only (kills the gather
                      involuntary-remat replication)
  replicate_small     no TP/FSDP for models < 1B params (pure DP; tiny archs
                      are over-distributed at TP=16)
  remat_dots          save dot outputs instead of full remat
  micro<N>            per-device microbatch size N (e.g. micro4)
  ssmchunk<N>         mamba2 SSD chunk length
  moegroup<N>         MoE dispatch group size
  attnchunk<N>        flash-scan KV chunk
  seqshard_attn       shard the attention *sequence* dim over data for
                      prefill (context parallelism)

Usage:
  PYTHONPATH=src:. python benchmarks/perf_iter.py \
      --arch mamba2-130m --shape train_4k --variant replicate_small
"""

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp


def apply_variants(arch, variants):
    """Returns (cfg_override, tcfg_override, sharding_kwargs)."""
    from repro.configs import get_config, get_train_config
    cfg = get_config(arch)
    tcfg = get_train_config(arch)
    shard_kw = {}
    for v in variants:
        if v == "embed_novocabfsdp":
            shard_kw["embed_tp_only"] = True
        elif v == "replicate_small":
            shard_kw["replicate_below"] = 1_000_000_000
        elif v == "remat_dots":
            tcfg = dataclasses.replace(tcfg, remat="dots")
        elif v.startswith("micro"):
            tcfg = dataclasses.replace(tcfg, microbatch=int(v[5:]))
        elif v.startswith("ssmchunk"):
            cfg = dataclasses.replace(cfg, ssm_chunk=int(v[8:]))
        elif v.startswith("moegroup"):
            cfg = dataclasses.replace(cfg, moe_group_size=int(v[8:]))
        elif v.startswith("attnchunk"):
            cfg = dataclasses.replace(cfg, attn_chunk=int(v[9:]))
        elif v == "baseline" or not v:
            pass
        else:
            raise ValueError(f"unknown variant {v!r}")
    return cfg, tcfg, shard_kw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import repro.configs as C
    import repro.train.sharding as shd
    from benchmarks import roofline as R
    from repro.launch.mesh import make_production_mesh

    variants = [v.strip() for v in args.variant.split(",")]
    cfg_o, tcfg_o, shard_kw = apply_variants(args.arch, variants)

    # patch the config registry + trainer config + sharding rules
    orig_cfg, orig_t = C.get_config, C.get_train_config
    C.get_config = lambda a: cfg_o if a == args.arch else orig_cfg(a)
    C.get_train_config = lambda a: tcfg_o if a == args.arch else orig_t(a)
    import repro.launch.specs as S
    S.get_config, S.get_train_config = C.get_config, C.get_train_config

    if shard_kw:
        orig_spec = shd.param_spec

        def patched(path, shape, **kw):
            import numpy as np
            size = int(np.prod(shape))
            if shard_kw.get("replicate_below", 0) and \
                    _model_small(cfg_o, shard_kw["replicate_below"]):
                from jax.sharding import PartitionSpec as P
                return P(*([None] * len(shape)))
            names = shd._path_names(path)
            if shard_kw.get("embed_tp_only") and names[-1] == "embed":
                kw = dict(kw, fsdp=False)
            return orig_spec(path, shape, **kw)
        shd.param_spec = patched

    def _model_small(cfg, thresh):
        return cfg.param_count() < thresh

    mesh = make_production_mesh(multi_pod=False)
    rec = R.lm_cell_terms(args.arch, args.shape, mesh)
    rec["variant"] = args.variant
    print(json.dumps(rec, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        mode = "a" if os.path.exists(args.out) else "w"
        with open(args.out, mode) as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
