"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret=True) vs
the pure-jnp ref oracles."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.score_norm.ops import l2_norm
from repro.kernels.score_norm.ref import l2_norm_ref
from repro.kernels.topk_sparsify import kernel as topk_kernel
from repro.kernels.topk_sparsify.ops import (block_topk_sparsify,
                                             block_topk_sparsify_matrix)
from repro.kernels.topk_sparsify.ref import block_topk_ref, block_topk_rows_ref


# ------------------------------------------------------------------ topk ----
@pytest.mark.parametrize("n,block", [(4096, 4096), (8192, 2048), (10000, 4096),
                                     (300, 256), (65536, 4096)])
@pytest.mark.parametrize("gamma", [0.1, 0.37, 0.5, 1.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_matches_ref(n, block, gamma, dtype):
    v = jax.random.normal(jax.random.PRNGKey(n + int(gamma * 10)), (n,), dtype)
    got, k1 = block_topk_sparsify(v, gamma, block=block)
    want, k2 = block_topk_ref(v, gamma, block=block)
    assert k1 == k2
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_keeps_exactly_k_per_block():
    v = jax.random.normal(jax.random.PRNGKey(0), (8192,))
    got, k = block_topk_sparsify(v, 0.25, block=2048)
    nnz = np.asarray(got != 0).reshape(4, 2048).sum(axis=1)
    assert (nnz == k).all()


def test_topk_with_ties():
    v = jnp.array([1.0, -1.0, 1.0, 0.5, 1.0, 0.0, -1.0, 0.25] * 32)
    got, k = block_topk_sparsify(v, 0.5, block=256)
    want, _ = block_topk_ref(v, 0.5, block=256)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int((got != 0).sum()) == k


def test_topk_rows_dynamic_k_matches_ref():
    """Pallas matrix kernel at D = block (one block per row, a per-row k
    column per tile) and the jitted bisection fast path both match the
    sort-based rows oracle."""
    from repro.fl.compression import _rows_topk_bisect
    rows = jax.random.normal(jax.random.PRNGKey(3), (12, 1024))
    ks = jnp.asarray([1, 7, 64, 100, 512, 1000, 1024, 3, 333, 900, 2, 50],
                     jnp.int32)
    want = block_topk_rows_ref(rows, ks)
    got_pallas = block_topk_sparsify_matrix(rows, ks, block=1024)
    got_bisect = jax.jit(_rows_topk_bisect)(rows, ks)
    np.testing.assert_array_equal(np.asarray(got_pallas), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_bisect), np.asarray(want))


def test_topk_rows_extreme_dynamic_range():
    """Bit-space bisection must stay exact with huge outliers — a naive
    value-space bisection leaves an epsilon band ~max*2^-iters wide and
    keeps the wrong coefficients here."""
    row = np.ones(4096, np.float32)
    row[-1] = 1e30
    row[-11:-1] = 2.0
    rows = jnp.asarray(row)[None, :]
    ks = jnp.asarray([11], jnp.int32)
    want = block_topk_rows_ref(rows, ks)
    np.testing.assert_array_equal(
        np.asarray(block_topk_sparsify_matrix(rows, ks, block=4096)),
        np.asarray(want))
    from repro.fl.compression import _rows_topk_bisect
    np.testing.assert_array_equal(np.asarray(jax.jit(_rows_topk_bisect)(rows, ks)),
                                  np.asarray(want))
    # and the oracle itself keeps exactly the outlier + the ten 2.0s
    kept = np.nonzero(np.asarray(want)[0])[0]
    np.testing.assert_array_equal(kept, np.arange(4085, 4096))


@functools.partial(jax.jit, static_argnums=2)
def _block_view_ref(mat, ks, block):
    """The [N, D] top-k as the jnp path spells it: zero-pad D to whole
    blocks, view as [N * nb, block] rows, sort-based rows oracle, back.
    Jitted, so a dropped entry is +0.0, as in the jitted jnp path."""
    n, d = mat.shape
    nb = -(-d // block)
    rows = jnp.pad(mat, ((0, 0), (0, nb * block - d))).reshape(n * nb, block)
    out = block_topk_rows_ref(rows, jnp.repeat(ks, nb))
    return out.reshape(n, nb * block)[:, :d]


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


def _matrix_case(name, n, d, block, rng):
    mat = rng.normal(size=(n, d)).astype(np.float32)
    ks = rng.integers(1, block + 1, size=n).astype(np.int32)
    ks[0], ks[-1] = 1, block
    if name == "ties":
        # exact zeros and a repeated value, so thresholds fall on ties at
        # zero and above it, more of them than k leaves room for
        mat[:, ::5] = 0.0
        mat[:, 1::3] = np.float32(0.75)
        mat[:, 2::7] = -0.0
        ks[1:] = np.linspace(1, block, n - 1).astype(np.int32)
    elif name == "zeros":
        # more zeros than the k leave room for, some of them -0.0, whose
        # sign survives only where the index fill keeps it
        mat[:, ::2] = 0.0
        mat[:, 1::6] = -0.0
        ks[1:] = block - np.arange(1, n) * 3
    elif name == "range":
        mat[0, :] = 1.0
        mat[0, -1] = 1e30
        mat[0, -11:-1] = 2.0
        ks[0] = 11
    elif name == "nan":
        mat[n // 2, 3] = np.nan
        mat[n // 2, d - 1] = np.nan
    return jnp.asarray(mat), jnp.asarray(ks)


# D ragged against the block and against 128 lanes; N below, at and off a
# row tile; ties, zeros of both signs, a 1e30 outlier beside ones and
# twos, and NaN; "row-tiles" forces 8-row tiles, so the last row tile is
# ragged too
@pytest.mark.parametrize("name,n,d,block", [
    ("random", 1, 1000, 256),
    ("random", 5, 3001, 1024),
    ("random", 13, 2100, 512),
    ("ties", 5, 3001, 1024),
    ("ties", 13, 1300, 256),
    ("zeros", 5, 3001, 1024),
    ("range", 1, 4096 + 77, 4096),
    ("nan", 5, 3001, 1024),
    ("row-tiles", 13, 1100, 256),   # a shape of its own: jit caches by shape
])
def test_topk_matrix_matches_block_view(name, n, d, block, monkeypatch):
    """The matrix kernel over [N, D]'s own tiles equals the block view's
    top-k bit for bit; with NaN in a row, it equals the jnp path."""
    from repro.fl.compression import batch_block_topk
    if name == "row-tiles":
        monkeypatch.setattr(topk_kernel, "_TILE_BYTES", 8 * block * 4)
        assert topk_kernel.matrix_tile(n, block, jnp.float32) == (8, 1)
    mat, ks = _matrix_case(name, n, d, block, np.random.default_rng(n + d))
    got = block_topk_sparsify_matrix(mat, ks, block=block)
    if name == "nan":
        want = jax.jit(lambda m, g: batch_block_topk(
            m, g, block=block, use_pallas=False, skip_full=False))(
                mat, ks.astype(jnp.float32) / block)
        # NaN is neither above nor at any threshold, so neither path
        # keeps it
        assert np.isfinite(np.asarray(got)).all()
    else:
        want = _block_view_ref(mat, ks, block)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("gamma", ["mixed", "full"])
def test_batch_block_topk_pallas_matches_jnp(gamma):
    """batch_block_topk gives the same bits through the kernel and the jnp
    path at a ragged shape, with the full-precision skip taken or not,
    and under vmap (the seed sweep)."""
    from repro.fl.compression import batch_block_topk
    rng = np.random.default_rng(11)
    mat = jnp.asarray(rng.normal(size=(2, 7, 2500)).astype(np.float32))
    g = (jnp.asarray([[0.05, 0.1, 0.3, 1.0, 0.5, 0.77, 1.0]] * 2, jnp.float32)
         if gamma == "mixed" else jnp.ones((2, 7), jnp.float32))
    for skip_full in (True, False):
        outs = [jax.jit(lambda m, g, p=p: batch_block_topk(
                    m, g, block=1024, use_pallas=p, skip_full=skip_full))(
                    mat[0], g[0]) for p in (True, False)]
        np.testing.assert_array_equal(_bits(outs[0]), _bits(outs[1]))
    swept = [jax.jit(jax.vmap(lambda m, g, p=p: batch_block_topk(
                m, g, block=1024, use_pallas=p)))(mat, g) for p in (True, False)]
    np.testing.assert_array_equal(_bits(swept[0]), _bits(swept[1]))


def test_topk_rows_matches_per_vector_static():
    """batch_block_topk with traced gamma == per-client static block_topk."""
    from repro.fl.compression import batch_block_topk, block_topk
    rng = np.random.default_rng(4)
    mat = jnp.asarray(rng.normal(size=(5, 3000)).astype(np.float32))
    gamma = jnp.asarray([0.05, 0.2, 0.5, 0.77, 1.0], jnp.float32)
    want = jnp.stack([block_topk(mat[i], float(gamma[i]), block=1024)[0]
                      for i in range(5)])
    got = jax.jit(lambda m, g: batch_block_topk(m, g, block=1024))(mat, gamma)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_keeps_largest_magnitudes():
    v = jnp.asarray(np.random.default_rng(0).normal(size=4096).astype(np.float32))
    got, k = block_topk_sparsify(v, 0.1, block=4096)
    kept = np.abs(np.asarray(v))[np.asarray(got != 0)]
    dropped = np.abs(np.asarray(v))[np.asarray(got == 0)]
    assert kept.min() >= dropped.max() - 1e-6


# ------------------------------------------------------------- score norm ----
@pytest.mark.parametrize("n", [1, 100, 4096, 65536, 1 << 20])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_norm(n, dtype):
    v = jax.random.normal(jax.random.PRNGKey(n), (n,), dtype)
    got = float(l2_norm(v))
    want = float(l2_norm_ref(v))
    assert got == pytest.approx(want, rel=1e-5)


# ------------------------------------------------------ flash attention ----
@pytest.mark.parametrize("B,S,H,KV,D", [
    (2, 512, 8, 2, 64), (1, 1024, 4, 4, 128), (2, 512, 6, 6, 64),
    (1, 2048, 8, 1, 64),
])
def test_flash_causal(B, S, H, KV, D):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, S, KV, D)) * 0.3
    v = jax.random.normal(ks[2], (B, S, KV, D)) * 0.3
    got = flash_attention(q, k, v, causal=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("window", [128, 256])
def test_flash_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 1024, 4, 64)) * 0.3
    k = jax.random.normal(ks[1], (1, 1024, 2, 64)) * 0.3
    v = jax.random.normal(ks[2], (1, 1024, 2, 64)) * 0.3
    got = flash_attention(q, k, v, causal=True, window=window)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_flash_bfloat16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = (jax.random.normal(ks[0], (1, 512, 4, 64)) * 0.3).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], (1, 512, 4, 64)) * 0.3).astype(jnp.bfloat16)
    v = (jax.random.normal(ks[2], (1, 512, 4, 64)) * 0.3).astype(jnp.bfloat16)
    got = flash_attention(q, k, v)
    want = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_model_flash_path_matches_direct():
    """The model-internal chunked flash (jnp custom-vjp) vs direct."""
    import repro.models.attention as A
    from repro.configs import get_smoke
    cfg = get_smoke("tinyllama-1.1b").replace(dtype="float32")
    p = A.attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2048, cfg.d_model)) * 0.3
    y_flash = A.attention_forward(p, x, cfg)
    old = A._FLASH_THRESHOLD
    A._FLASH_THRESHOLD = 10 ** 9
    try:
        y_direct = A.attention_forward(p, x, cfg)
    finally:
        A._FLASH_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_direct), atol=2e-5)
