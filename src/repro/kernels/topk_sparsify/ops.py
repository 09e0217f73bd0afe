"""jit'd public wrapper around the topk_sparsify Pallas kernel."""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import topk_sparsify_pallas, topk_sparsify_rows_pallas


def block_topk_sparsify(vec: jnp.ndarray, gamma: float, *, block: int = 4096
                        ) -> tuple[jnp.ndarray, int]:
    """Same contract as kernels.topk_sparsify.ref.block_topk_ref."""
    n = vec.shape[0]
    k = max(1, min(block, math.ceil(float(gamma) * block)))
    nb = -(-n // block)
    pad = nb * block - n
    v = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)]) if pad else vec
    out = topk_sparsify_pallas(v, k=k, block=block, interpret=interpret_mode())
    return out[:n], k


def block_topk_sparsify_rows(rows: jnp.ndarray, ks: jnp.ndarray) -> jnp.ndarray:
    """rows: [R, block]; ks: [R] traced int32 — per-row dynamic k. Same
    keep rule as ``block_topk_sparsify`` but jittable with heterogeneous
    compression ratios (one row per client-block in the round engine)."""
    return topk_sparsify_rows_pallas(rows, ks, interpret=interpret_mode())
