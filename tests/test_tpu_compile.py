"""The rk_stage kernels compile for a TPU v5e — without a TPU.

Interpret mode runs every other kernel test on the CPU and cannot see
what the TPU compiler refuses (misaligned blocks, rank-1 outputs that are
not lane multiples).  Here each kernel is lowered and compiled by the
installed TPU compiler for a described, not attached, v5e chip at the
widths the solver runs: the flat NODE-block state N = 8·128·768 in f32
and bf16, and batched (B, N) states of (64, 256) and (8, 4096).

The topology is described inside a module fixture — never at import —
so only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tableaus import DOPRI5, HEUN_EULER
from repro.kernels import rk_stage

FLAT_N = 8 * 128 * 768          # (batch, seq, d_model) of node18_cifar
BATCHED = [(64, 256), (8, 4096)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip can be written to the persistent
    # cache but never read back here — keep it off for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flat(sharding, dtype, stages):
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=sharding)
    return s((FLAT_N,)), s((stages, FLAT_N)), s((), jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine_compiles(one_chip, dtype):
    tab = DOPRI5
    _compile(lambda z, k, h: rk_stage.rk_stage_combine_pallas(
        z, k, h, tab.b, tab.b_err), *_flat(one_chip, dtype, tab.stages))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_increment_compiles(one_chip, dtype):
    tab = DOPRI5
    _compile(lambda z, k, h: rk_stage.rk_stage_increment_pallas(
        z, k, h, tab.a[3]), *_flat(one_chip, dtype, 3))


@pytest.mark.parametrize("with_err", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_combine_err_compiles(one_chip, dtype, with_err):
    tab = HEUN_EULER            # the NODE_TRAIN pair
    _compile(lambda z, k, h: rk_stage.rk_stage_combine_err_pallas(
        z, k, h, tab.b, tab.b_err, 1e-2, 1e-2, with_err=with_err),
        *_flat(one_chip, dtype, tab.stages))


def _batched(sharding, bsz, n, stages):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=sharding)
    return s((bsz, n)), s((stages, bsz, n)), s((bsz,))


@pytest.mark.parametrize("bsz,n", BATCHED)
def test_increment_batched_compiles(one_chip, bsz, n):
    tab = DOPRI5
    _compile(lambda z, k, h: rk_stage.rk_stage_increment_batched_pallas(
        z, k, h, tab.a[3]), *_batched(one_chip, bsz, n, 3))


@pytest.mark.parametrize("bsz,n", BATCHED)
def test_combine_err_batched_compiles(one_chip, bsz, n):
    tab = DOPRI5
    _compile(lambda z, k, h: rk_stage.rk_stage_combine_err_batched_pallas(
        z, k, h, tab.b, tab.b_err, 1e-6, 1e-6),
        *_batched(one_chip, bsz, n, tab.stages))


@pytest.mark.parametrize("bsz,n", BATCHED)
def test_combine_err_batched_rowtol_compiles(one_chip, bsz, n):
    tab = DOPRI5
    z, k, h = _batched(one_chip, bsz, n, tab.stages)
    _compile(lambda z, k, h, rt, at:
             rk_stage.rk_stage_combine_err_batched_rowtol_pallas(
                 z, k, h, tab.b, tab.b_err, rt, at), z, k, h, h, h)
