"""The chunked SSD scan: the Pallas kernel pair on a TPU where the shapes
tile, the jnp ``ref`` everywhere else (the CPU, chunks of under 128
steps)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import ssd_chunk_bwd_pallas, ssd_chunk_fwd_pallas, tiles
from .ref import ssd_ref


def ssd_scan(xs, Bmat, Cmat, dt, log_a, *, chunk: int, groups: int,
             head_dim: int, remat: bool = True, use_pallas=None):
    """xs: [B, S, H * P]; Bmat, Cmat: [B, S, G * N]; dt, log_a = dt * A:
    [B, S, H]. Returns y [B, S, H * P] and the final state [B, H, N, P],
    both float32, as ``ref.ssd_ref`` computes them.

    ``use_pallas`` None: the kernels on a TPU when ``kernel.tiles`` takes
    the shapes, else the jnp ``ref`` (with ``remat``, its chunk step is
    rematerialised). Forced on elsewhere, the kernels run in the Pallas
    interpreter."""
    B, S, width = xs.shape
    H = width // head_dim
    if use_pallas is None:
        use_pallas = not interpret_mode() and tiles(S, chunk, H, groups,
                                                    head_dim)
    if not use_pallas:
        y, state = ssd_ref(xs.reshape(B, S, H, head_dim), Bmat, Cmat, dt,
                           log_a, chunk=chunk, groups=groups, remat=remat)
        return y.reshape(B, S, width), state
    interpret = interpret_mode()
    # a float32 product at default precision: one bfloat16 pass on the
    # chip, float32 on the CPU, where the interpreter runs
    mxu_dtype = jnp.float32 if interpret else jnp.bfloat16
    L = jnp.cumsum(log_a.reshape(B, S // chunk, chunk, H), axis=2)
    f32 = lambda t: t.astype(jnp.float32)                       # noqa: E731
    y, final = _ssd(f32(xs), f32(Bmat), f32(Cmat), dt.swapaxes(1, 2),
                    L.reshape(B, S, H).swapaxes(1, 2), chunk, groups,
                    head_dim, mxu_dtype, interpret)
    N = final.shape[1]
    return y, final.reshape(B, N, H, head_dim).swapaxes(1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ssd(x, b, c, dt, L, chunk, groups, head_dim, mxu_dtype, interpret):
    y, _, final = ssd_chunk_fwd_pallas(
        x, b, c, dt, L, chunk=chunk, groups=groups, head_dim=head_dim,
        mxu_dtype=mxu_dtype, interpret=interpret)
    return y, final


def _ssd_fwd(x, b, c, dt, L, chunk, groups, head_dim, mxu_dtype, interpret):
    y, starts, final = ssd_chunk_fwd_pallas(
        x, b, c, dt, L, chunk=chunk, groups=groups, head_dim=head_dim,
        mxu_dtype=mxu_dtype, interpret=interpret)
    return (y, final), (x, b, c, dt, L, starts)


def _ssd_bwd(chunk, groups, head_dim, mxu_dtype, interpret, res, cts):
    dy, dfinal = cts
    return ssd_chunk_bwd_pallas(
        *res, dy, dfinal, chunk=chunk, groups=groups, head_dim=head_dim,
        mxu_dtype=mxu_dtype, interpret=interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)
