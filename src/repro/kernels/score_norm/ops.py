"""jit'd wrapper: padded L2 norm via the Pallas partial-reduction kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import sq_sum_partials


def l2_norm(vec: jnp.ndarray, *, block: int = 65536) -> jnp.ndarray:
    n = vec.shape[0]
    block = min(block, max(128, 1 << (n - 1).bit_length()))
    nb = -(-n // block)
    pad = nb * block - n
    v = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)]) if pad else vec
    partials = sq_sum_partials(v, block=block, interpret=interpret_mode())
    return jnp.sqrt(jnp.sum(partials))
