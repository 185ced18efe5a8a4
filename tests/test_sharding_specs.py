"""Sharding-rule inference: every full-config param must get a legal spec on
the production meshes (divisibility), and TP/EP/FSDP rules must fire."""

import math

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models import build_model
from repro.train import sharding as shd


def _abstract_mesh(shape, names):
    return jax.sharding.AbstractMesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


MESHES = [((16, 16), ("data", "model")),
          ((2, 16, 16), ("pod", "data", "model"))]


@pytest.mark.parametrize("arch", list(ARCHS))
@pytest.mark.parametrize("mesh_shape,mesh_names", MESHES)
def test_specs_divide(arch, mesh_shape, mesh_names):
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    mesh = _abstract_mesh(mesh_shape, mesh_names)
    specs = shd.infer_param_specs(shapes, mesh)

    def check(path, s, spec):
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = math.prod(dict(zip(mesh_names, mesh_shape))[a]
                             for a in axes)
            assert s.shape[d] % size == 0, (path, s.shape, spec)
    jax.tree_util.tree_map_with_path(check, shapes, specs)


def test_tp_rules_fire():
    """Attention/MLP/vocab shards over 'model'; experts over 'model' (EP)."""
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    cfg = get_config("arctic-480b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    specs = shd.infer_param_specs(shapes, mesh)
    assert "model" in jax.tree_util.tree_flatten(specs["embed"])[0] or \
        "model" in tuple(specs["embed"])
    moe_spec = specs["blocks"]["moe"]["w_up"]       # (L, E, d, f)
    assert moe_spec[1] == "model", moe_spec          # EP on the expert axis
    attn_spec = specs["blocks"]["attn"]["wq"]        # (L, d, h*hd)
    assert attn_spec[2] == "model", attn_spec


def test_fsdp_shards_large_params_only():
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    cfg = get_config("granite-34b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    specs = shd.infer_param_specs(shapes, mesh)
    # norms replicated; big matrices carry 'data' somewhere
    norm_spec = specs["blocks"]["ln1"]
    assert all(a is None for a in norm_spec)
    wq = specs["blocks"]["attn"]["wq"]
    assert "data" in tuple(wq), wq


def test_batch_and_cache_specs():
    mesh = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert shd.batch_axes(mesh) == ("pod", "data")
    assert shd.data_spec(mesh, 2) == P(("pod", "data"), None)
    cfg = get_config("zamba2-1.2b")
    cs = shd.cache_spec(cfg, mesh, batch=1)
    # B=1: sequence-parallel decode — seq dim sharded instead of batch
    assert not cs["batch_sharded"]
    assert cs["attn"][2] == ("pod", "data")
