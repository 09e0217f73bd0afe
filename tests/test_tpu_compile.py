"""The main path's Pallas kernels compile for a TPU v5e chip at real widths.

No chip is needed: the TPU compiler compiles for a *described* v5e chip
(``jax.experimental.topologies``), with ``interpret=False``, from shapes
only. That refuses what the Pallas interpreter accepts — block shapes off
the chip's (8, 128) tiling, unsupported ops in a kernel body — so a kernel
that stops lowering for the chip fails here and not on the chip.

Widths are the paper's: N = 1024 clients for the solver kernels, the
1.6M-parameter CNN (``configs.fmnist_cnn``) at N = 50 clients for top-k
and the score norm. The topology is described inside module fixtures —
never at import — and each test skips only when it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FairEnergyConfig
from repro.configs.fmnist_cnn import CONFIG as CNN
from repro.kernels.dual_solve.kernel import (N_SCALARS, dual_solve_pallas,
                                             dual_solve_pallas_joint)
from repro.kernels.dual_solve.ref import joint_levels
from repro.kernels.score_norm.kernel import sq_sum_partials
from repro.kernels.topk_sparsify.kernel import (topk_sparsify_pallas,
                                                topk_sparsify_rows_pallas)
from repro.models import cnn

N_SOLVER = 1024
N_CLIENTS = 50
BLOCK = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one; keep the cache out of them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def cnn_width():
    """Flat parameter count D of the paper's CNN, from shapes only."""
    shapes = jax.eval_shape(lambda: cnn.init_cnn(jax.random.PRNGKey(0), CNN))
    return int(sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes)))


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("joint", [False, True], ids=["gamma", "joint"])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
def test_dual_solve_compiles_for_v5e(one_chip, joint, scaled):
    vec = jax.ShapeDtypeStruct((N_SOLVER,), jnp.float32, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((N_SCALARS,), jnp.float32, sharding=one_chip)
    grid = FairEnergyConfig().gamma_grid
    if joint:
        kw = dict(levels=joint_levels(grid, (8.0, 16.0, 32.0)))
        fn = dual_solve_pallas_joint
    else:
        kw = dict(gamma_grid=tuple(grid))
        fn = dual_solve_pallas
    args = [vec] * 4 + [sc] + ([vec] if scaled else [])
    _compile(lambda *a: fn(*a, interpret=False, **kw), *args)


def test_topk_rows_compiles_for_v5e(one_chip, cnn_width):
    n_rows = N_CLIENTS * -(-cnn_width // BLOCK)
    rows = jax.ShapeDtypeStruct((n_rows, BLOCK), jnp.float32,
                                sharding=one_chip)
    ks = jax.ShapeDtypeStruct((n_rows,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda r, k: topk_sparsify_rows_pallas(r, k, interpret=False),
        rows, ks)
    # the [R, 4096] buffer, its output and the kernel's temporaries fit
    # in the 16 GB of one v5e chip
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert n_rows * BLOCK * 4 * 2 <= used < 16e9


def test_topk_static_compiles_for_v5e(one_chip, cnn_width):
    """One client's flat update at the static k of gamma = 0.1; its block
    count is not a multiple of 8, so the kernel's zero-row pad is in."""
    n = -(-cnn_width // BLOCK) * BLOCK
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    _compile(lambda v: topk_sparsify_pallas(v, k=410, block=BLOCK,
                                            interpret=False), vec)


def test_sq_sum_partials_compiles_for_v5e(one_chip, cnn_width):
    block = 65536
    n = -(-cnn_width // block) * block
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    _compile(lambda v: sq_sum_partials(v, block=block, interpret=False), vec)
