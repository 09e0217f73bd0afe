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
from repro.kernels.topk_sparsify.kernel import topk_sparsify_matrix_pallas
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


@pytest.mark.parametrize("n", [N_CLIENTS, 200])
def test_topk_matrix_compiles_for_v5e(one_chip, cnn_width, n):
    """The [N, D] top-k kernel over the CNN's flat updates, within the
    default VMEM: whole-client tiles of two blocks at N = 50, 112-row
    tiles of one block at N = 200."""
    mat = jax.ShapeDtypeStruct((n, cnn_width), jnp.float32, sharding=one_chip)
    ks = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    _compile(lambda m, k: topk_sparsify_matrix_pallas(m, k, block=BLOCK,
                                                      interpret=False),
             mat, ks)


def test_batch_block_topk_tpu_path_has_no_block_view(one_chip, cnn_width,
                                                      monkeypatch):
    """``batch_block_topk``'s kernel path at N = 50 compiles to the kernel
    over [N, D] itself: no [N * nb, 4096] view or other [N, D]-sized
    buffer among its temporaries."""
    from repro.fl.compression import batch_block_topk
    from repro.kernels.topk_sparsify import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mat = jax.ShapeDtypeStruct((N_CLIENTS, cnn_width), jnp.float32,
                               sharding=one_chip)
    gamma = jax.ShapeDtypeStruct((N_CLIENTS,), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda m, g: batch_block_topk(m, g, block=BLOCK, use_pallas=True),
        mat, gamma)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        N_CLIENTS * cnn_width * 4)


def test_topk_static_compiles_for_v5e(one_chip, cnn_width):
    """One client's flat update at the static k of gamma = 0.1, as
    ``ops.block_topk_sparsify`` runs it: the kernel over one row, whose
    block count is not a whole number of tiles."""
    vec = jax.ShapeDtypeStruct((1, cnn_width), jnp.float32, sharding=one_chip)
    _compile(lambda v: topk_sparsify_matrix_pallas(
        v, jnp.full((1,), 410, jnp.int32), block=BLOCK, interpret=False), vec)


def test_sq_sum_partials_compiles_for_v5e(one_chip, cnn_width):
    block = 65536
    n = -(-cnn_width // block) * block
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    _compile(lambda v: sq_sum_partials(v, block=block, interpret=False), vec)
