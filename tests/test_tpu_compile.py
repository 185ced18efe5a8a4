"""Compiles of the TPU kernel path for a described (not attached) v5e chip.

The TPU compiler is installed with jax, so these compile the programs the
chip runs at real widths — garnet n = 2^20, m = 16, K = 8 and the K = 4
stencil — and check what only the chip's compiler can refuse: layouts,
fast-memory limits and HBM capacity.  Nothing runs, so they say nothing
about results or times.

``jax.default_backend()`` is the CPU here, so every compile names its
implementation explicitly: what ``-kernel_impl auto`` resolves to on TPU
(:data:`repro.kernels.ops.AUTO_IMPL`).  The topology is described inside a
module fixture only (one process at a time may load libtpu), and the
persistent compilation cache is off around the compiles: a topology
compile written to it could not be read back without a chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ipi
from repro.core.comm import Axes
from repro.core.mdp import EllMDP
from repro.kernels import matrix_free, ops

GARNET = (1 << 20, 16, 8)
STENCIL = (1 << 20, 4, 4)
TPU_IMPL = ops.AUTO_IMPL["tpu"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table(sharding, n, m, k):
    return (_sds(sharding, (n, m, k), jnp.int32),
            _sds(sharding, (n, m, k), jnp.float32),
            _sds(sharding, (n, m), jnp.float32))


def _temp(compiled) -> int:
    return compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("shape", [GARNET, STENCIL],
                         ids=["garnet_m16_k8", "stencil_m4_k4"])
def test_auto_backup_fits_v5e(one_chip, no_persistent_cache, shape):
    n, m, k = shape
    idx, val, cost = _table(one_chip, n, m, k)
    c = ops.ell_backup.lower(idx, val, cost, _sds(one_chip, (), jnp.float32),
                             _sds(one_chip, (n,), jnp.float32),
                             impl=TPU_IMPL).compile()
    assert _temp(c) <= 2 * matrix_free.table_bytes(n, m, k)


@pytest.mark.parametrize("shape", [GARNET, STENCIL],
                         ids=["garnet_k8", "stencil_k4"])
def test_auto_spmv_fits_v5e(one_chip, no_persistent_cache, shape):
    n, _, k = shape
    c = ops.ell_matvec.lower(_sds(one_chip, (n, k), jnp.int32),
                             _sds(one_chip, (n, k), jnp.float32),
                             _sds(one_chip, (n,), jnp.float32),
                             impl=TPU_IMPL).compile()
    assert _temp(c) <= 2 * n * k * 8


def test_default_solve_chunk_fits_v5e(one_chip, no_persistent_cache):
    """The whole compiled outer loop of the default method (ipi_gmres,
    float32) on the garnet table: the program phase (a) of chip_smoke.py
    runs."""
    n, m, k = GARNET
    idx, val, cost = _table(one_chip, n, m, k)
    mdp = EllMDP(idx=idx, val=val, cost=cost, gamma=0.99, n_global=n,
                 m_global=m)
    opts = ipi.IPIOptions(method="ipi_gmres", impl=TPU_IMPL, atol=1e-3)
    axes = Axes()
    state = jax.eval_shape(lambda md: ipi.init_state(md, axes, opts), mdp)
    state = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), state)
    c = ipi.solve_chunk.lower(mdp, state, _sds(one_chip, (), jnp.int32),
                              _sds(one_chip, (), jnp.int32), opts=opts,
                              axes=axes).compile()
    assert _temp(c) <= 2 * matrix_free.table_bytes(n, m, k)


def test_pallas_on_tpu_raises_the_compilers_error(one_chip,
                                                  no_persistent_cache):
    """``-kernel_impl pallas`` is never quietly swapped for another
    implementation: Mosaic's refusal of the in-kernel 1-D gather is what
    the caller sees."""
    n, m, k = 1 << 12, 16, 8
    idx, val, cost = _table(one_chip, n, m, k)
    # 32-bit, as the chip runs it (conftest turns x64 on for the f64 tests;
    # Mosaic has no 64-bit index arithmetic)
    with jax.enable_x64(False):
        with pytest.raises(NotImplementedError, match="2D gather"):
            ops.ell_backup.lower(idx, val, cost,
                                 _sds(one_chip, (), jnp.float32),
                                 _sds(one_chip, (n,), jnp.float32),
                                 impl="pallas").compile()
        with pytest.raises(NotImplementedError, match="2D gather"):
            ops.ell_matvec.lower(_sds(one_chip, (n, k), jnp.int32),
                                 _sds(one_chip, (n, k), jnp.float32),
                                 _sds(one_chip, (n,), jnp.float32),
                                 impl="pallas").compile()
