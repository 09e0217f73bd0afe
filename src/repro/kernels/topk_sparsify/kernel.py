"""Pallas TPU kernel: block-local magnitude top-k sparsification.

TPU adaptation of gradient top-k (DESIGN.md §4.1): no sort. Each grid step
owns a tile of rows by whole lane-aligned blocks resident in VMEM and
finds each block's k-th largest magnitude per row by **bisection on the
fp32 bit pattern** (31 integer halvings — exact for any dynamic range;
see ``ref.topk_keep``, which shares the bisection with the pure-jnp fast
path), then resolves ties by index order with a second bisection on the
cut index (the chip's Pallas lowering has no cumsum), only in a tile
where a tie can change the result. Everything is vector ops in VREGs;
the MXU is not needed.

One kernel, ``topk_sparsify_matrix_pallas``, over an [N, D] matrix in
place: grid (row tiles, column tiles), a per-row k column, the ragged
edges of D and N handled in the kernel and by Pallas, so nothing is
padded or reshaped in HBM. A single vector is its N = 1 case
(``ops.block_topk_sparsify``). Block size must be a multiple of 128
lanes (default 4096).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import sublanes
from .ref import topk_keep

# Most bytes of one input tile. The kernel's VMEM (in and out tiles,
# double-buffered, and the bisection's temporaries) comes to about eight
# times the tile: 16.04 MB at a 2 MiB tile, over the 16 MiB a v5e kernel
# may use by default.
_TILE_BYTES = 7 << 18
# Most blocks per tile: each pass of the bisection is a chain of
# dependent reductions, and the tile's blocks run their chains side by
# side (on a v5e at N = 50, one 4096 block per tile took 4.7 ms over the
# CNN's [50, D], two 3.4 ms, four 3.3 ms with a raised VMEM limit).
_MAX_BLOCKS = 4


def matrix_tile(n: int, block: int, dtype) -> tuple[int, int]:
    """(rows, blocks) per tile of ``topk_sparsify_matrix_pallas``: the
    whole row (client) axis when one block of it, in float32, fits
    ``_TILE_BYTES``, else the most whole native row tiles of ``dtype``
    that do; then as
    many blocks as fit, up to ``_MAX_BLOCKS`` (N = 50 at a 4096 block:
    two blocks of 800 KB; N = 200: 112 rows of one block)."""
    block_bytes = block * 4               # the body works in float32
    rows = n
    if n * block_bytes > _TILE_BYTES:
        tile = sublanes(dtype)
        rows = max(tile, _TILE_BYTES // block_bytes // tile * tile)
    blocks = min(_MAX_BLOCKS, max(1, _TILE_BYTES // (rows * block_bytes)))
    return rows, blocks


def _topk_matrix_kernel(skip_ref, k_ref, x_ref, out_ref, *, d: int,
                        block: int, blocks: int):
    # x_ref is column tile j of a tile of rows (clients): ``blocks`` blocks
    # side by side. k_ref is the (rows, 1) column of their k, so every row
    # keeps its own count. Columns at or past d are the ragged edge of the
    # last tile: set to the zeros the padded block view holds there
    # (whatever the edge DMA left in VMEM).
    j = pl.program_id(1)

    @pl.when(skip_ref[0] == 0)
    def _():
        xs = []
        for b in range(blocks):
            x = x_ref[:, b * block:(b + 1) * block]
            if d % (blocks * block):
                col = (j * blocks + b) * block + jax.lax.broadcasted_iota(
                    jnp.int32, x.shape, 1)
                x = jnp.where(col < d, x.astype(jnp.float32), 0.0)
            xs.append(x)
        for b, out in enumerate(topk_keep(xs, k_ref[...])):
            out_ref[:, b * block:(b + 1) * block] = out.astype(out_ref.dtype)

    # skipped: every step maps to tile (0, 0), which is fetched once and
    # written back as it was; the rest of ``mat``'s buffer is not touched
    @pl.when(skip_ref[0] != 0)
    def _():
        out_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def topk_sparsify_matrix_pallas(mat: jnp.ndarray, ks: jnp.ndarray,
                                skip: jnp.ndarray | bool = False, *,
                                block: int = 4096,
                                interpret: bool = False) -> jnp.ndarray:
    """mat: [N, D]; ks: [N] int32 in [1, block] (traced). Keeps the top
    ks[i] magnitudes of row i in each ``block``-wide column block, as
    ``ref.block_topk_rows_ref`` does over the zero-padded [N * nb, block]
    view, but reads and writes ``mat``'s own tiles: no pad, reshape or
    slice. The output takes ``mat``'s buffer (each grid step writes only
    the tile it read), so where ``mat`` is dead after the call, as the
    round's updates are, no [N, D] buffer is added.

    ``skip`` (traced bool): return ``mat`` as it is, touching one tile;
    the caller's test that every k is the whole block (an identity).

    Grid (row tiles, column tiles), tiles from ``matrix_tile``. The last
    column tile is ragged where it does not divide D: the kernel zeroes
    its columns past D (the view's pad zeros, which compete for the k)
    and Pallas drops the tile's writes outside ``mat``. Rows are
    independent, so the rows of a ragged last row tile that lie outside
    ``mat`` change no real row."""
    assert mat.ndim == 2 and ks.shape == (mat.shape[0],), (mat.shape, ks.shape)
    assert block % 128 == 0, block
    n, d = mat.shape
    rows, blocks = matrix_tile(n, block, mat.dtype)
    # a skipped call maps every step to tile (0, 0): with the block index
    # unchanged from step to step, the pipeline copies nothing more
    live = lambda s: 1 - s[0]                                    # noqa: E731
    spec = pl.BlockSpec((rows, blocks * block),
                        lambda i, j, s: (i * live(s), j * live(s)))
    return pl.pallas_call(
        functools.partial(_topk_matrix_kernel, d=d, block=block,
                          blocks=blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, rows), pl.cdiv(d, blocks * block)),
            in_specs=[pl.BlockSpec((rows, 1),
                                   lambda i, j, s: (i * live(s), 0)), spec],
            out_specs=spec),
        out_shape=jax.ShapeDtypeStruct(mat.shape, mat.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(jnp.asarray(skip, jnp.int32).reshape(1), ks.astype(jnp.int32)[:, None],
      mat)
