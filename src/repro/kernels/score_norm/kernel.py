"""Pallas TPU kernel: fused blockwise sum-of-squares reduction.

One grid step per VMEM block; each step accumulates sum(x^2) for its block
into a [nb]-shaped partials output (fp32). The final sqrt(sum(partials))
happens in the jit'd wrapper (and, when the update is sharded, after a
scalar psum across shards — see fl/collectives). Avoids materializing x^2
in HBM: the square+reduce runs in VREGs on the VMEM-resident block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sq_sum_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.full(out_ref.shape, jnp.sum(x * x), jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sq_sum_partials(vec: jnp.ndarray, *, block: int = 65536,
                    interpret: bool = False) -> jnp.ndarray:
    """[nb] per-block sums of squares of ``vec`` ([nb * block]). Each block
    is laid out as an (8, block/8) VMEM tile and each partial lands in its
    own (1, 1) output block, so both obey the chip's (8, 128) tiling rule
    (block/8 a multiple of 128, or the whole last dimension)."""
    assert vec.ndim == 1 and vec.shape[0] % block == 0 and block % 8 == 0
    nb = vec.shape[0] // block
    tiles = vec.reshape(nb, 8, block // 8)
    out = pl.pallas_call(
        _sq_sum_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 8, block // 8), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32),
        interpret=interpret,
    )(tiles)
    return out.reshape(nb)
