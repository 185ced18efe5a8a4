"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One model class, four block kinds:

  * ``attn``  — pre-norm GQA attention + dense MLP (stablelm, minitron,
                granite, nemotron, llava backbone)
  * ``moe``   — GQA attention + MoE FFN (+ parallel dense residual, arctic)
  * ``mamba`` — Mamba2 SSD block (mamba2-130m; zamba2 backbone)
  * hybrid    — mamba stack with a single *shared* attention+MLP block
                applied every ``shared_attn_every`` layers (zamba2)

Layer stacks are scan-stacked (leading L axis) so the lowered HLO is O(1) in
depth; per-layer remat (``jax.checkpoint``) bounds activation memory to one
layer plus the carried residual stream.

Caches (decode):  attn -> (k, v) rings (B, S, KV, hd) + scalar length;
mamba -> (conv window, SSD state).  All cache tensors carry a leading L axis
and are scanned alongside the stacked params.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.layers import _init, apply_mlp, init_mlp, rms_norm


def zero_aux():
    return {"load_balance_loss": jnp.float32(0.0),
            "router_z_loss": jnp.float32(0.0)}


def _init_attn_block(key, cfg, dtype, *, moe: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype),
         "ln2": jnp.ones((cfg.d_model,), dtype),
         "attn": attn_lib.init_attention(k1, cfg, dtype)}
    if moe:
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
        if cfg.dense_residual:
            p["mlp"] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _init_mamba_block(key, cfg, dtype):
    return {"ln1": jnp.ones((cfg.d_model,), dtype),
            "mamba": mamba_lib.init_mamba2(key, cfg, dtype)}


def _attn_block(p, x, cache, *, cfg, positions, moe: bool):
    h, cache_out = attn_lib.apply_attention(
        p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg,
        positions=positions, cache=cache)
    x = x + h
    y = rms_norm(p["ln2"], x, cfg.norm_eps)
    aux = zero_aux()
    if moe:
        ym, aux = moe_lib.apply_moe(p["moe"], y, cfg)
        if cfg.dense_residual:
            ym = ym + apply_mlp(p["mlp"], y, cfg.mlp_type)
    else:
        ym = apply_mlp(p["mlp"], y, cfg.mlp_type)
    return x + ym, cache_out, aux


def _mamba_block(p, x, cache, *, cfg, positions):
    del positions
    h, cache_out = mamba_lib.apply_mamba2(
        p["mamba"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg, cache=cache)
    return x + h, cache_out, zero_aux()


class DecoderLM:
    """init/apply wrapper around the block stacks."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.kind = {"dense": "attn", "vlm": "attn", "moe": "moe",
                     "ssm": "mamba", "hybrid": "mamba"}[cfg.family]

    # ------------------------------------------------------------------ init
    def init(self, key):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 8)
        params = {
            "embed": _init(keys[0], (cfg.vocab_size, cfg.d_model),
                           scale=1.0, dtype=dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = _init(
                keys[1], (cfg.d_model, cfg.vocab_size), dtype=dtype)
        if cfg.family == "vlm":
            params["patch_proj"] = _init(
                keys[2], (cfg.d_model, cfg.d_model), dtype=dtype)
        lkeys = jax.random.split(keys[3], cfg.n_layers)
        if self.kind in ("attn", "moe"):
            init_l = functools.partial(_init_attn_block, cfg=cfg, dtype=dtype,
                                       moe=(self.kind == "moe"))
        else:
            init_l = functools.partial(_init_mamba_block, cfg=cfg, dtype=dtype)
        params["blocks"] = jax.vmap(init_l)(lkeys)
        if cfg.family == "hybrid":
            # zamba2: ONE shared attention+MLP block reused at every call site
            params["shared"] = _init_attn_block(keys[4], cfg, dtype, moe=False)
        return params

    # -------------------------------------------------------------- caches
    def n_shared_sites(self) -> int:
        cfg = self.cfg
        if cfg.family != "hybrid" or not cfg.shared_attn_every:
            return 0
        return cfg.n_layers // cfg.shared_attn_every

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Empty decode caches (filled by prefill or supplied by the bench)."""
        cfg = self.cfg
        l = cfg.n_layers
        if self.kind in ("attn", "moe"):
            kv = dict(
                k=jnp.zeros((l, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                            dtype),
                v=jnp.zeros((l, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                            dtype))
            return {"blocks": kv, "len": jnp.int32(0)}
        cache = {"blocks": dict(
            conv=jnp.zeros((l, batch, cfg.d_conv - 1,
                            cfg.d_inner + 2 * cfg.ssm_state), dtype),
            ssm=jnp.zeros((l, batch, cfg.ssm_heads, cfg.head_p,
                           cfg.ssm_state), jnp.float32)),
            "len": jnp.int32(0)}
        ns = self.n_shared_sites()
        if ns:
            cache["shared"] = dict(
                k=jnp.zeros((ns, batch, max_len, cfg.n_kv_heads,
                             cfg.head_dim), dtype),
                v=jnp.zeros((ns, batch, max_len, cfg.n_kv_heads,
                             cfg.head_dim), dtype))
        return cache

    # -------------------------------------------------------------- forward
    def _embed(self, params, tokens, patches):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.family == "vlm" and patches is not None:
            pe = patches.astype(x.dtype) @ params["patch_proj"]
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def _block_fn(self, mode: str):
        cfg = self.cfg
        moe = self.kind == "moe"
        if self.kind in ("attn", "moe"):
            base = functools.partial(_attn_block, cfg=cfg, moe=moe)
        else:
            base = functools.partial(_mamba_block, cfg=cfg)
        return base

    def _scan_stack(self, params_stack, x, *, positions, mode, cache,
                    remat: str = "full", unroll: bool = False):
        """Run the scan-stacked block params over x. Returns (x, cache, aux)."""
        fn = self._block_fn(mode)
        if remat != "none" and mode == "train":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else None)
            fn = jax.checkpoint(fn, policy=policy, static_argnums=())

        if unroll:
            # Python-loop execution: loop-free HLO (XLA cost_analysis counts
            # while-loop bodies once)
            l = jax.tree.leaves(params_stack)[0].shape[0]
            aux = zero_aux()
            caches = []
            length = None if cache is None else cache["len"]
            for i in range(l):
                p_l = jax.tree.map(lambda a: a[i], params_stack)
                if mode == "decode":
                    c_l = jax.tree.map(lambda a: a[i], cache["blocks"])
                    if self.kind in ("attn", "moe"):
                        x, c, a = fn(p_l, x, (c_l["k"], c_l["v"], length),
                                     positions=positions)
                        caches.append(dict(k=c[0], v=c[1]))
                    else:
                        x, c, a = fn(p_l, x, (c_l["conv"], c_l["ssm"]),
                                     positions=positions)
                        caches.append(dict(conv=c[0], ssm=c[1]))
                else:
                    x, c, a = fn(p_l, x, None, positions=positions)
                    if mode == "prefill":
                        caches.append(dict(k=c[0], v=c[1])
                                      if self.kind in ("attn", "moe")
                                      else dict(conv=c[0], ssm=c[1]))
                aux = jax.tree.map(jnp.add, aux, a)
            cache_out = None
            if caches:
                cache_out = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
            return x, cache_out, aux

        if mode == "train":
            def body(carry, p_l):
                h, aux = carry
                h, _, a = fn(p_l, h, None, positions=positions)
                return (h, jax.tree.map(jnp.add, aux, a)), None
            (x, aux), _ = jax.lax.scan(body, (x, zero_aux()), params_stack)
            return x, None, aux

        if mode == "prefill":
            attn_like = self.kind in ("attn", "moe")

            def body(carry, p_l):
                h, aux = carry
                h, c, a = fn(p_l, h, None, positions=positions)
                c = dict(k=c[0], v=c[1]) if attn_like else \
                    dict(conv=c[0], ssm=c[1])
                return (h, jax.tree.map(jnp.add, aux, a)), c
            (x, aux), cache_out = jax.lax.scan(
                body, (x, zero_aux()), params_stack)
            return x, cache_out, aux

        # decode: thread per-layer cache slices through the scan
        length = cache["len"]

        def body(carry, xs):
            h, aux = carry
            p_l, c_l = xs
            if self.kind in ("attn", "moe"):
                c_in = (c_l["k"], c_l["v"], length)
                h, (k, v, _), a = fn(p_l, h, c_in, positions=positions)
                c_out = dict(k=k, v=v)
            else:
                h, c_out_t, a = fn(p_l, h, (c_l["conv"], c_l["ssm"]),
                                   positions=positions)
                c_out = dict(conv=c_out_t[0], ssm=c_out_t[1])
            return (h, jax.tree.map(jnp.add, aux, a)), c_out

        (x, aux), blocks_out = jax.lax.scan(
            body, (x, zero_aux()), (params_stack, cache["blocks"]))
        return x, blocks_out, aux

    def forward(self, params, tokens, *, patches=None, mode: str = "train",
                cache=None, remat: str = "full", unroll: bool = False):
        """Returns ``(hidden, cache_out, aux)``.

        train/prefill: ``tokens (B, T)``; decode: ``tokens (B, 1)`` + cache.
        """
        cfg = self.cfg
        x = self._embed(params, tokens, patches)
        b, t, _ = x.shape
        if mode == "decode":
            positions = jnp.full((b, 1), cache["len"], jnp.int32)
        else:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))

        if cfg.family != "hybrid" or not cfg.shared_attn_every:
            x, blocks_cache, aux = self._scan_stack(
                params["blocks"], x, positions=positions, mode=mode,
                cache=cache, remat=remat, unroll=unroll)
            cache_out = self._pack_cache(blocks_cache, None, cache, t, mode)
            return rms_norm(params["final_norm"], x, cfg.norm_eps), \
                cache_out, aux

        # ---- zamba2 hybrid: segments of mamba blocks + shared attn block --- #
        every, l = cfg.shared_attn_every, cfg.n_layers
        sites = self.n_shared_sites()
        aux = zero_aux()
        shared_fn = functools.partial(_attn_block, cfg=cfg, moe=False)
        if mode == "train" and remat != "none":
            shared_fn = jax.checkpoint(shared_fn)
        seg_bounds = [(i * every, min((i + 1) * every, l)) for i in
                      range((l + every - 1) // every)]
        blocks_caches, shared_caches = [], []
        for si, (lo, hi) in enumerate(seg_bounds):
            seg_params = jax.tree.map(lambda a: a[lo:hi], params["blocks"])
            seg_cache = None
            if mode == "decode":
                seg_cache = {"blocks": jax.tree.map(
                    lambda a: a[lo:hi], cache["blocks"]),
                    "len": cache["len"]}
            x, bc, a = self._scan_stack(seg_params, x, positions=positions,
                                        mode=mode, cache=seg_cache,
                                        remat=remat, unroll=unroll)
            aux = jax.tree.map(jnp.add, aux, a)
            if bc is not None:
                blocks_caches.append(bc)
            if si < sites:  # shared block after each full segment
                if mode == "decode":
                    sc = (cache["shared"]["k"][si], cache["shared"]["v"][si],
                          cache["len"])
                    x, (k, v, _), a2 = shared_fn(params["shared"], x, sc,
                                                 positions=positions)
                    shared_caches.append(dict(k=k, v=v))
                else:
                    x, sc_out, a2 = shared_fn(params["shared"], x, None,
                                              positions=positions)
                    if mode == "prefill":
                        shared_caches.append(dict(k=sc_out[0], v=sc_out[1]))
                aux = jax.tree.map(jnp.add, aux, a2)
        blocks_cache = None
        if blocks_caches:
            blocks_cache = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *blocks_caches)
        shared_cache = None
        if shared_caches:
            shared_cache = jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=0), *shared_caches)
        cache_out = self._pack_cache(blocks_cache, shared_cache, cache, t,
                                     mode)
        return rms_norm(params["final_norm"], x, cfg.norm_eps), cache_out, aux

    def _pack_cache(self, blocks_cache, shared_cache, cache_in, t, mode):
        if mode == "train" or blocks_cache is None:
            return None
        if mode == "prefill":
            out = {"blocks": blocks_cache, "len": jnp.int32(t)}
        else:
            out = {"blocks": blocks_cache, "len": cache_in["len"] + 1}
        if shared_cache is not None:
            out["shared"] = shared_cache
        return out

    def logits(self, params, hidden):
        w = params["embed"].T if self.cfg.tie_embeddings else params["unembed"]
        return hidden @ w
