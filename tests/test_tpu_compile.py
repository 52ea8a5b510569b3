"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed alongside jax, lowers
and compiles each kernel for a chip that is described, not attached.  That
is what catches a block shape or a primitive Mosaic refuses, or more VMEM
than the chip has — none of which the interpret-mode parity tests can see.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around these compiles
(a cache entry compiled for a described chip cannot be read back without
one).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_tasks import SHAKESPEARE
from repro.kernels import fed_aggregate as fa
from repro.kernels import fed_select as fs
from repro.models import rnn


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [100, 16_384, fs.MAX_KERNEL_N])
def test_fed_select_compiles_for_v5e(one_chip, n):
    vec = _spec((n,), jnp.float32, one_chip)
    compiled = fs._select_pallas.lower(
        vec, _spec((n,), jnp.bool_, one_chip), _spec((), jnp.int32, one_chip),
        vec, vec, vec, beta=1e-3, weight_mode="unbiased",
        interpret=False).compile()
    _assert_kernel(compiled)


def test_fed_select_mask_compiles_for_v5e(one_chip):
    n = fs.MAX_KERNEL_N
    compiled = fs._mask_pallas.lower(
        _spec((n,), jnp.float32, one_chip), _spec((n,), jnp.bool_, one_chip),
        _spec((), jnp.int32, one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_fed_aggregate_compiles_for_v5e_at_shakespeare_width(one_chip):
    shapes = jax.eval_shape(
        functools.partial(rnn.init_params, SHAKESPEARE.model_cfg),
        jax.random.PRNGKey(0))
    d = sum(x.size for x in jax.tree.leaves(shapes))
    k = SHAKESPEARE.clients_per_round
    compiled = fa._fed_aggregate.lower(
        _spec((k, d), jnp.float32, one_chip),
        _spec((k,), jnp.float32, one_chip),
        tile=fa.DEFAULT_TILE, interpret=False).compile()
    _assert_kernel(compiled)
