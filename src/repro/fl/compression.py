"""Update compression: magnitude top-k sparsification (+ optional int8
quantization of kept values).

Two top-k variants with identical payload accounting:

* ``global_topk`` — exact top-(gamma*n) over the whole vector (the paper's
  idealized scheme; O(n log n) sort);
* ``block_topk`` — top-(gamma*block) per fixed-size block — the TPU-native
  scheme implemented by kernels/topk_sparsify (DESIGN.md §4.1). Payload is
  exactly gamma per block, which makes the energy model's gamma*S payload
  deterministic.

Both return a dense masked vector (simulation form) plus the kept count;
``payload_bits`` mirrors the channel model's gamma*S + I accounting.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

Array = jnp.ndarray

DEFAULT_BLOCK = 4096


@functools.partial(jax.jit, static_argnames=("k",))
def _global_topk_mask(vec: Array, k: int) -> Array:
    mag = jnp.abs(vec)
    thresh = jax.lax.top_k(mag, k)[0][-1]
    mask = mag >= thresh
    # tie-break: keep exactly k by stable cumulative count
    over = jnp.cumsum(mask.astype(jnp.int32)) <= k
    return mask & over


def global_topk(vec: Array, gamma: float) -> tuple[Array, int]:
    # ceil keep rule, identical to block_topk/batch_block_topk/
    # effective_gamma: round() transmitted *less* than the gamma*S
    # payload the energy model charges at off-integer gamma*n
    n = vec.shape[0]
    k = min(n, max(1, int(math.ceil(float(gamma) * n))))
    mask = _global_topk_mask(vec, k)
    return vec * mask.astype(vec.dtype), k


def block_topk(vec: Array, gamma: float, block: int = DEFAULT_BLOCK,
               use_pallas: bool = False) -> tuple[Array, int]:
    """Keep the top ceil(gamma*block) magnitudes inside each block."""
    if use_pallas:
        from repro.kernels.topk_sparsify.ops import block_topk_sparsify
        return block_topk_sparsify(vec, gamma, block=block)
    from repro.kernels.topk_sparsify.ref import block_topk_ref
    return block_topk_ref(vec, gamma, block=block)


def _rows_topk_bisect(rows: Array, ks: Array) -> Array:
    """Sort-free per-row top-k via ``topk_threshold_mask`` (fp32 bit-space
    bisection — exact k-th magnitude, shared with the Pallas kernel body).
    XLA's CPU sort is scalar-slow (~170 ms for 150x4096 rows); this is
    pure vector compare+reduce passes.
    """
    from repro.kernels.topk_sparsify.ref import topk_threshold_mask
    mask = topk_threshold_mask(rows, ks[:, None])
    return rows * mask.astype(rows.dtype)


def batch_block_topk(mat: Array, gamma: Array, block: int = DEFAULT_BLOCK,
                     use_pallas: Optional[bool] = None,
                     skip_full: bool = True) -> Array:
    """Per-client block top-k with *traced* per-client gamma.

    mat: [N, D] stacked flat updates; gamma: [N] compression ratios (may be
    traced, e.g. straight out of a jitted controller decision). Each
    client's row is sparsified to k = ceil(gamma_i * block) kept per block
    — identical keep rule to ``block_topk`` — in a single fused call with
    a per-client k, so the whole decide -> sparsify -> aggregate round
    stays one jitted program.

    ``use_pallas`` picks the implementation; both give the same bits (the
    same bisection, keep rule and pad zeros; ``ref.topk_keep``). ``None``
    (default) picks by backend: on a TPU the Pallas kernel
    ``topk_sparsify_matrix_pallas``, which reads ``mat``'s own tiles in
    place; elsewhere the jnp bisection over the zero-padded
    [N*nb, block] view. ``True`` forces the kernel (the interpreter on
    the CPU), ``False`` the jnp path.

    ``skip_full`` (default): when *every* client's k equals the block
    (gamma = 1, i.e. full precision — ScoreMax/RandomFull/ChannelGreedy
    rounds), the sparsify pass is an identity and is skipped at runtime:
    by a ``lax.cond`` on the jnp path, inside the kernel on the kernel
    path. (Under ``vmap``, e.g. the seed sweep, the cond lowers to a
    select and both branches run, while the kernel skips per lane; the
    result is unchanged.)
    """
    n, d = mat.shape
    ks = jnp.clip(jnp.ceil(gamma * block).astype(jnp.int32), 1, block)   # [N]
    skip = jnp.all(ks >= block) if skip_full else False
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from repro.kernels.topk_sparsify.ops import block_topk_sparsify_matrix
        return block_topk_sparsify_matrix(mat, ks, skip, block=block)
    nb = -(-d // block)
    pad = nb * block - d
    rows = jnp.pad(mat, ((0, 0), (0, pad))).reshape(n * nb, block)
    ks_rows = jnp.repeat(ks, nb)                                         # [N*nb]
    sparsify = lambda r: _rows_topk_bisect(r, ks_rows)                   # noqa: E731
    if skip_full:
        out = jax.lax.cond(skip, lambda r: r, sparsify, rows)
    else:
        out = sparsify(rows)
    return out.reshape(n, nb * block)[:, :d]


def quantize_int8(vec: Array) -> tuple[Array, Array]:
    """Symmetric per-tensor int8 quantization of kept values.

    Non-finite entries (fault-injected NaN/Inf payloads) are screened to
    zero *before* the scale max — a single NaN would otherwise make
    ``max(|vec|)`` NaN and silently poison every quantized lane — so the
    finite coefficients always survive the round-trip.
    """
    vec = jnp.where(jnp.isfinite(vec), vec, 0.0)
    scale = jnp.maximum(jnp.max(jnp.abs(vec)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(vec / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def quantize_rows(rows: Array, bits: Array) -> Array:
    """Simulated symmetric quantize->dequantize of each row at a traced
    per-row bit-width (the decided ``RoundDecision.bits``).

    Same scale rule as ``quantize_int8`` generalized to qmax =
    2^(bits-1) - 1 (the int8 fast path is bits=8), applied per row with
    non-finite screening; rows with bits >= 32 pass through untouched
    (float32 is the uncompressed wire format), so a bits=32 lane is
    bit-for-bit the unquantized payload. Zeros stay exactly zero, which
    the kept-mask accounting relies on.
    """
    finite = jnp.isfinite(rows)
    clean = jnp.where(finite, rows, 0.0)
    qmax = jnp.maximum(jnp.exp2(bits - 1.0) - 1.0, 1.0)[:, None]     # [N,1]
    scale = jnp.maximum(jnp.max(jnp.abs(clean), axis=1, keepdims=True),
                        1e-12) / qmax
    deq = jnp.clip(jnp.round(clean / scale), -qmax, qmax) * scale
    return jnp.where(bits[:, None] >= 32.0, clean, deq)


def payload_bits(n_params: int, gamma: float, *, value_bits: int = 32,
                 bitmap_index: bool = True) -> float:
    """gamma*S*(value_bits/32) + I with S = 32*n_params and a
    1-bit-per-coefficient kept-mask — a thin shim over the single
    channel-model accounting in ``repro.core.channel.payload_bits`` so
    the two can never drift."""
    from repro.core import channel
    return float(channel.payload_bits(
        jnp.float32(gamma), 32.0 * n_params,
        float(n_params) if bitmap_index else 0.0,
        value_bits=float(value_bits)))


def effective_gamma(gamma, block: int = DEFAULT_BLOCK):
    """The keep fraction the block scheme actually realizes:
    ``clip(ceil(gamma*block), 1, block) / block`` — the same k rule as
    ``block_topk``/``batch_block_topk``, jnp-traceable.

    The energy model charges ``gamma*S*(bits/32) + I`` with the
    *controller's* gamma and decided bit-width
    (``repro.core.channel.payload_bits``); the transmitted payload is
    ``effective_gamma(gamma)*S*(bits/32) + I``. The bit-width factor is
    common to both sides, so it scales the value-bits charge error but
    never introduces one. The two agree exactly whenever
    ``gamma*block`` is integral (e.g. gamma in {0.25, 0.5, 0.75, 1.0} at
    the default 4096 block); otherwise the ceil rounds the realized
    payload up to at most ``S/block`` bits above the charge (~0.01% of S
    at the default block — e.g. grid gamma 0.1 keeps 410/4096), plus the
    k >= 1 floor at vanishing gamma. Audit helper: use it to bound the
    charge error."""
    return jnp.clip(jnp.ceil(jnp.asarray(gamma) * block), 1, block) / block
