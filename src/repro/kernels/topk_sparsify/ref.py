"""Pure-jnp oracle for block-local magnitude top-k sparsification.

Semantics (shared bit-for-bit with the Pallas kernel): the flat vector is
split into fixed blocks; in each block exactly ``k = ceil(gamma*block)``
coefficients are kept — those with the largest |x|, ties broken by index
order (earlier index wins). Trailing padding (zeros) competes like any
other value but the result is truncated back to the input length.

``topk_threshold_mask`` (the dynamic-k jnp fast path) and ``topk_keep``
(the Pallas kernel's body) are the shared sort-free implementation: both
find the exact k-th largest magnitude by bisecting on the fp32 *bit
pattern* (``_kth_magnitudes``: non-negative floats order identically to
their int32 bits, so 31 integer halvings pin the threshold exactly — no
epsilon band, any dynamic range).
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


def _kth_magnitudes(xs: list[Array], k: Array
                    ) -> tuple[list[Array], list[Array], Array]:
    """For blocks ``xs`` (each [..., block], one k per row for all): |x|
    in float32 per block, the int32 bit pattern of each block's k-th
    largest |x| per row ([..., 1]), and k broadcast to [..., 1].
    Non-negative floats order as their int32 bits, so 31 integer halvings
    pin the k-th value exactly, at any dynamic range. The blocks share
    one loop: on the chip each pass's reductions are a chain of
    dependent steps, and independent blocks fill its gaps."""
    mags = [jnp.abs(x.astype(jnp.float32)) for x in xs]
    bits = [jax.lax.bitcast_convert_type(m, jnp.int32) for m in mags]  # >= 0
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), mags[0].shape[:-1] + (1,))

    # invariant: count(bits >= lo) >= k, count(bits >= hi) < k
    los = [jnp.zeros_like(k) for _ in bits]
    his = [jnp.max(b, axis=-1, keepdims=True) + 1 for b in bits]

    def body(_, lohi):
        new = [], []
        for b, lo, hi in zip(bits, *lohi):
            mid = lo + (hi - lo) // 2
            enough = jnp.sum((b >= mid).astype(jnp.int32), axis=-1,
                             keepdims=True) >= k
            new[0].append(jnp.where(enough, mid, lo))
            new[1].append(jnp.where(enough, hi, mid))
        return new

    los, _ = jax.lax.fori_loop(0, 31, body, (los, his))
    return mags, los, k


def topk_threshold_mask(x: Array, k: Array) -> Array:
    """Keep-mask of the top-k magnitudes per row, ties to the lower index.

    x: [..., block] float; k: int32 broadcastable to [..., 1] (clipped by
    the caller to [1, block]). Matches the exact-sort oracle bit-for-bit:
    the k-th largest |x| is found by integer bisection on the fp32 bit
    pattern (``_kth_magnitudes``). Ties at the threshold are filled in
    index order with a prefix sum.
    """
    (mag,), (lo,), k = _kth_magnitudes([x], k)
    thresh = jax.lax.bitcast_convert_type(lo, jnp.float32)   # k-th largest |x|
    greater = mag > thresh
    n_greater = jnp.sum(greater.astype(jnp.int32), axis=-1, keepdims=True)
    equal = mag == thresh
    fill = _prefix_count(equal) <= (k - n_greater)
    return greater | (equal & fill)


def topk_keep(xs: list[Array], k: Array) -> list[Array]:
    """Each block of ``xs`` with its top-k magnitudes per row kept and
    every other entry +0.0, in float32: ``x * topk_threshold_mask(x, k)``
    as XLA compiles it (its simplifier turns the product with a converted
    mask into this select), and so bit for bit the jitted jnp path. This
    is the Pallas kernel's body. The chip's Pallas lowering has no prefix
    sum, so ties are filled in index order by the index bisection of
    ``_first_n_mask``, and that fill runs only where it can change the
    result: where some row has more ties than its k leaves room for, at a
    nonzero threshold or among zeros one of which is -0.0. Elsewhere
    every tie is kept, or the ties are zeros that come out as +0.0
    whether kept or not."""
    mags, los, k = _kth_magnitudes(xs, k)
    out = []
    for x, mag, lo in zip(xs, mags, los):
        x = x.astype(jnp.float32)
        thresh = jax.lax.bitcast_convert_type(lo, jnp.float32)
        greater = mag > thresh
        equal = mag == thresh
        room = k - jnp.sum(greater.astype(jnp.int32), axis=-1, keepdims=True)
        n_equal = jnp.sum(equal.astype(jnp.int32), axis=-1, keepdims=True)
        signed = jax.lax.bitcast_convert_type(x, jnp.int32) == -2 ** 31
        signed = jnp.sum(signed.astype(jnp.int32), axis=-1, keepdims=True)
        crowded = (n_equal > room) & ((lo > 0) | (signed > 0))
        crowded = jnp.max(crowded.astype(jnp.int32)) > 0
        out.append(jax.lax.cond(
            crowded,
            lambda x=x, greater=greater, equal=equal, room=room: jnp.where(
                greater | (equal & _first_n_mask(equal, room)), x, 0.0),
            lambda x=x, greater=greater, equal=equal: jnp.where(
                greater | equal, x, 0.0)))
    return out


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
