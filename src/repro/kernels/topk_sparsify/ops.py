"""jit'd public wrappers around the topk_sparsify Pallas kernel."""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import topk_sparsify_matrix_pallas


def block_topk_sparsify(vec: jnp.ndarray, gamma: float, *, block: int = 4096
                        ) -> tuple[jnp.ndarray, int]:
    """Same contract as kernels.topk_sparsify.ref.block_topk_ref: the
    matrix kernel over the vector as one row."""
    k = max(1, min(block, math.ceil(float(gamma) * block)))
    out = block_topk_sparsify_matrix(vec[None, :], jnp.full((1,), k, jnp.int32),
                                     block=block)
    return out[0], k


def block_topk_sparsify_matrix(mat: jnp.ndarray, ks: jnp.ndarray,
                               skip=False, *, block: int = 4096
                               ) -> jnp.ndarray:
    """mat: [N, D]; ks: [N] traced int32 in [1, block] — per-row dynamic k.
    Same keep rule as ``block_topk_sparsify`` in every ``block``-wide
    column block of each row, jittable with heterogeneous compression
    ratios (one row per client in the round engine). ``skip`` (traced
    bool) returns ``mat`` untouched."""
    return topk_sparsify_matrix_pallas(mat, ks, skip, block=block,
                                       interpret=interpret_mode())
