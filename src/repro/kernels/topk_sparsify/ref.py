"""Pure-jnp oracle for block-local magnitude top-k sparsification.

Semantics (shared bit-for-bit with the Pallas kernel): the flat vector is
split into fixed blocks; in each block exactly ``k = ceil(gamma*block)``
coefficients are kept — those with the largest |x|, ties broken by index
order (earlier index wins). Trailing padding (zeros) competes like any
other value but the result is truncated back to the input length.

``topk_threshold_mask`` is the shared sort-free implementation used by
both the dynamic-k jnp fast path and the Pallas kernel bodies: it finds
the exact k-th largest magnitude by bisecting on the fp32 *bit pattern*
(non-negative floats order identically to their int32 bits, so 31 integer
halvings pin the threshold exactly — no epsilon band, any dynamic range).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def _first_n_mask(flags: Array, n: Array) -> Array:
    """Mask of the first ``n`` set ``flags`` per row (index order) — the
    same as ``cumsum(flags) <= n`` where ``n <= sum(flags)``, without a
    prefix sum, which the chip's Pallas lowering lacks. Bisects on the
    cut index j: the smallest j with ``count(flags[:j]) >= n``; then
    ``index < j`` covers exactly the first n flags."""
    idx = jax.lax.broadcasted_iota(jnp.int32, flags.shape, flags.ndim - 1)
    flags = flags.astype(jnp.int32)
    width = flags.shape[-1]

    # invariant: count(flags[:lo]) < n <= count(flags[:hi])
    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(jnp.where(idx < mid, flags, 0), axis=-1,
                         keepdims=True) >= n
        return jnp.where(enough, lo, mid), jnp.where(enough, mid, hi)

    lo = jnp.zeros_like(n)
    hi = jnp.full_like(n, width)
    _, hi = jax.lax.fori_loop(0, max(1, (width - 1).bit_length()), body,
                              (lo, hi))
    return idx < hi


def _prefix_count(flags: Array) -> Array:
    """``jnp.cumsum(flags, axis=-1)`` in int32, spelled as the
    reduce-window that ``cumsum`` lowers to. ``cumsum`` emits it as an
    outlined function whose ops carry no name stack of the caller; this
    one is lowered in place, so the caller's ``jax.named_scope`` names
    it in the compiled program. Same op, same result."""
    width = flags.shape[-1]
    lead = (1,) * (flags.ndim - 1)
    return jax.lax.reduce_window(
        flags.astype(jnp.int32), 0, jax.lax.add, lead + (width,),
        lead + (1,), [(0, 0)] * (flags.ndim - 1) + [(width - 1, 0)])


def topk_threshold_mask(x: Array, k: Array, *,
                        prefix_sum: bool = True) -> Array:
    """Keep-mask of the top-k magnitudes per row, ties to the lower index.

    x: [..., block] float; k: int32 broadcastable to [..., 1] (clipped by
    the caller to [1, block]). Matches the exact-sort oracle bit-for-bit:
    the k-th largest |x| is found by integer bisection on the fp32 bit
    pattern, which is monotone for non-negative floats. Ties at the
    threshold are filled in index order with a prefix sum, or, with
    ``prefix_sum=False`` (the Pallas kernels), with the equivalent
    index bisection of ``_first_n_mask``.
    """
    mag = jnp.abs(x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(mag, jnp.int32)      # >= 0 for |x|
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), mag.shape[:-1] + (1,))

    # invariant: count(bits >= lo) >= k, count(bits >= hi) < k
    lo = jnp.zeros_like(k)
    hi = jnp.max(bits, axis=-1, keepdims=True) + 1

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum((bits >= mid).astype(jnp.int32), axis=-1,
                         keepdims=True) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    lo, hi = jax.lax.fori_loop(0, 31, body, (lo, hi))
    thresh = jax.lax.bitcast_convert_type(lo, jnp.float32)   # k-th largest |x|
    greater = mag > thresh
    n_greater = jnp.sum(greater.astype(jnp.int32), axis=-1, keepdims=True)
    equal = mag == thresh
    if prefix_sum:
        fill = _prefix_count(equal) <= (k - n_greater)
    else:
        fill = _first_n_mask(equal, k - n_greater)
    return greater | (equal & fill)


def _pad_to_blocks(vec: Array, block: int) -> tuple[Array, int]:
    n = vec.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec.reshape(nb, block), n


def block_topk_ref(vec: Array, gamma: float, *, block: int = 4096) -> tuple[Array, int]:
    """Returns (masked dense vector, kept-per-block k)."""
    assert vec.ndim == 1
    k = max(1, min(block, math.ceil(float(gamma) * block)))
    rows, n = _pad_to_blocks(vec, block)
    mag = jnp.abs(rows.astype(jnp.float32))
    # k-th largest per row
    kth = jnp.sort(mag, axis=1)[:, block - k]                    # [nb]
    greater = mag > kth[:, None]
    n_greater = greater.sum(axis=1, keepdims=True)
    equal = mag == kth[:, None]
    fill = jnp.cumsum(equal.astype(jnp.int32), axis=1) <= (k - n_greater)
    mask = greater | (equal & fill)
    out = (rows * mask.astype(rows.dtype)).reshape(-1)[:n]
    return out, k


def block_topk_mask_ref(vec: Array, gamma: float, *, block: int = 4096) -> Array:
    out, _ = block_topk_ref(vec, gamma, block=block)
    return out != 0


def block_topk_rows_ref(rows: Array, ks: Array) -> Array:
    """Traced-k variant: rows [R, block], ks [R] int32 (1 <= k <= block).

    Same keep rule as ``block_topk_ref`` — per row, the ``ks[r]`` largest
    magnitudes, ties broken by index order — but k is a runtime array, so
    the call is jittable with per-row compression ratios (the round engine
    feeds one gamma per client).
    """
    assert rows.ndim == 2 and ks.ndim == 1 and rows.shape[0] == ks.shape[0]
    block = rows.shape[1]
    ks = jnp.clip(ks.astype(jnp.int32), 1, block)
    mag = jnp.abs(rows.astype(jnp.float32))
    srt = jnp.sort(mag, axis=1)                                  # ascending
    kth = jnp.take_along_axis(srt, (block - ks)[:, None], axis=1)  # [R,1]
    greater = mag > kth
    n_greater = greater.sum(axis=1, keepdims=True)
    equal = mag == kth
    fill = jnp.cumsum(equal.astype(jnp.int32), axis=1) <= (ks[:, None] - n_greater)
    mask = greater | (equal & fill)
    return rows * mask.astype(rows.dtype)
