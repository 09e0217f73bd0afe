"""Pallas TPU kernel: block-local magnitude top-k sparsification.

TPU adaptation of gradient top-k (DESIGN.md §4.1): no sort. Each grid step
owns one lane-aligned block resident in VMEM and finds the k-th largest
magnitude by **bisection on the fp32 bit pattern** (31 integer halvings —
exact for any dynamic range; see ``ref.topk_threshold_mask``, shared with
the pure-jnp fast path), then resolves ties by index order with a second
bisection on the cut index (the chip's Pallas lowering has no cumsum).
Everything is vector ops in VREGs; the MXU is not needed.

Grid: one program per tile of rows — 8 rows of one block each at f32
(16 at bf16: ``kernels.sublanes``), so the block obeys the chip's
(8, 128) tiling at any row count (the wrappers pad with zero rows). Block
size must be a multiple of 128 lanes (default 4096).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import sublanes
from .ref import topk_threshold_mask


def _topk_block_kernel(x_ref, out_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)
    mask = topk_threshold_mask(x, k, prefix_sum=False)
    out_ref[...] = (x * mask.astype(jnp.float32)).astype(out_ref.dtype)


def _topk_rows_kernel(k_ref, x_ref, out_ref):
    # k_ref is the (rows, 1) column of per-row k for this tile: every row
    # of the tile keeps its own count, so one launch handles
    # heterogeneous compression ratios.
    x = x_ref[...].astype(jnp.float32)
    mask = topk_threshold_mask(x, k_ref[...], prefix_sum=False)
    out_ref[...] = (x * mask.astype(jnp.float32)).astype(out_ref.dtype)


def _row_tiles(rows: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """Pad [R, block] with zero rows to whole native tiles of the dtype
    (``kernels.sublanes``). Rows are independent, so the pad rows change
    no real row; callers slice them off."""
    tile = sublanes(rows.dtype)
    pad = (-rows.shape[0]) % tile
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    return rows, tile


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_sparsify_pallas(vec: jnp.ndarray, *, k: int, block: int = 4096,
                         interpret: bool = False) -> jnp.ndarray:
    """vec: [n] (n % block == 0). Keeps top-k magnitudes per block."""
    assert vec.ndim == 1 and vec.shape[0] % block == 0, vec.shape
    nb = vec.shape[0] // block
    rows, tile = _row_tiles(vec.reshape(nb, block))
    spec = pl.BlockSpec((tile, block), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_topk_block_kernel, k=k),
        grid=(rows.shape[0] // tile,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, vec.dtype),
        interpret=interpret,
    )(rows)
    return out[:nb].reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_sparsify_rows_pallas(rows: jnp.ndarray, ks: jnp.ndarray, *,
                              interpret: bool = False) -> jnp.ndarray:
    """rows: [R, block]; ks: [R] int32 (traced). Keeps top-ks[r] magnitudes
    in row r — the dynamic-k companion to ``topk_sparsify_pallas``."""
    assert rows.ndim == 2 and ks.shape == (rows.shape[0],), (rows.shape, ks.shape)
    n_rows, block = rows.shape
    padded, tile = _row_tiles(rows)
    kcol = jnp.pad(ks.astype(jnp.int32), (0, padded.shape[0] - n_rows),
                   constant_values=1)[:, None]
    spec = pl.BlockSpec((tile, block), lambda i: (i, 0))
    out = pl.pallas_call(
        _topk_rows_kernel,
        grid=(padded.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, 1), lambda i: (i, 0)), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(padded.shape, rows.dtype),
        interpret=interpret,
    )(kcol, padded)
    return out[:n_rows]
