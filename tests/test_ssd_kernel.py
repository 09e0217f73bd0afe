"""The chunked SSD kernel pair (``repro.kernels.ssd``) against the jnp
scan it replaces on a TPU (``ref.ssd_ref``), in the Pallas interpreter.

In the interpreter the kernels' products are float32, as XLA's on the
CPU are at default precision, so the kernels and the jnp scan compute the
same sums in another order: values and gradients agree to about 1e-5
relative. With bfloat16 operands, as on the chip, they agree to the same
order with a jnp scan whose every product, forward and backward, takes
bfloat16-rounded operands and accumulates in float32: the one pass XLA
makes of the jnp scan's float32 products on the chip. Covered: 1, 2 and 4 chunks of 128, one and two B/C groups, a
head whose decay underflows within a chunk (L reaches about -400), the
final state, gradients through the mixer's parameters and LoRA adapters,
and the Zamba2 smoke model at a chunk that tiles.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels.ssd import kernel, ops, ref as ref_mod
from repro.models import lora, ssm as ssm_mod, transformer as tfm

Q = 128


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _inputs(seed, batch, seq, heads, head_dim, groups, state, hot=False):
    """xs, B, C, dt and A of a mixer; ``hot``: the last head decays at
    A = -16 with dt = 0.1, so L falls by 1.6 a step."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    xs = jax.random.normal(ks[0], (batch, seq, heads * head_dim))
    Bm = jax.random.normal(ks[1], (batch, seq, groups * state)) * 0.5
    Cm = jax.random.normal(ks[2], (batch, seq, groups * state)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)) - 2.0)
    A = -jnp.linspace(1.0, 16.0, heads)
    if hot:
        dt = dt.at[..., -1].set(0.1)
    return xs, Bm, Cm, dt, A


def _scan(use_pallas, groups, head_dim, chunk=Q):
    def f(xs, Bm, Cm, dt, A):
        return ops.ssd_scan(xs, Bm, Cm, dt, dt * A, chunk=chunk, groups=groups,
                            head_dim=head_dim, use_pallas=use_pallas)
    return f


def _bfloat16_kernels(groups, head_dim, chunk=Q):
    """``ssd_scan``'s kernel path with the products' operands in
    bfloat16, as it is compiled for the chip, run in the interpreter."""
    def f(xs, Bm, Cm, dt, A):
        batch, seq, width = xs.shape
        heads = width // head_dim
        L = jnp.cumsum((dt * A).reshape(batch, seq // chunk, chunk, heads),
                       axis=2).reshape(batch, seq, heads)
        y, final = ops._ssd(xs, Bm, Cm, dt.swapaxes(1, 2), L.swapaxes(1, 2),
                            chunk, groups, head_dim, jnp.bfloat16, True)
        return y, final.reshape(batch, -1, heads, head_dim).swapaxes(1, 2)
    return f


def _weighted(f, shapes):
    """sum(y * wy) + sum(state * ws): every output element gets its own
    cotangent."""
    wy, ws = (jnp.cos(jnp.arange(np.prod(s)).reshape(s) * 0.37 + k)
              for k, s in enumerate(shapes))

    def loss(*args):
        y, state = f(*args)
        return jnp.sum(y * wy) + jnp.sum(state * ws)
    return loss


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_kernels_match_the_jnp_scan(chunks, groups):
    args = _inputs(chunks, 2, chunks * Q, 8 * groups, 64, groups, 16)
    got = jax.jit(_scan(True, groups, 64))(*args)
    want = jax.jit(_scan(False, groups, 64))(*args)
    assert got[0].shape == want[0].shape and got[1].shape == want[1].shape
    assert _rel(got[0], want[0]) < 1e-5
    assert _rel(got[1], want[1]) < 1e-5
    shapes = [w.shape for w in want]
    grads = [jax.jit(jax.grad(_weighted(_scan(p, groups, 64), shapes),
                              argnums=(0, 1, 2, 3, 4)))(*args)
             for p in (True, False)]
    for name, g, w in zip(["x", "B", "C", "dt", "A"], *grads):
        assert _rel(g, w) < 2e-5, name


@jax.custom_vjp
def _round_operand(t):
    """bfloat16-rounded in the forward; its cotangent passes unchanged."""
    return t.astype(jnp.bfloat16).astype(jnp.float32)


_round_operand.defvjp(lambda t: (_round_operand(t), None),
                      lambda _, g: (g,))


@jax.custom_vjp
def _round_cotangent(t):
    """The identity; its cotangent is bfloat16-rounded."""
    return t


_round_cotangent.defvjp(lambda t: (t, None),
                        lambda _, g: (g.astype(jnp.bfloat16).astype(jnp.float32),))


class _OnePassJnp:
    """jnp for ``ref``, with einsums that take bfloat16-rounded operands
    and accumulate in float32, in the forward and in both products of the
    backward (whose operands are the rounded cotangent and the other
    rounded operand)."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def einsum(spec, a, b):
        return _round_cotangent(jnp.einsum(spec, _round_operand(a),
                                           _round_operand(b)))


@pytest.mark.parametrize("chunks,groups", [(2, 1), (4, 2)])
def test_bfloat16_products_round_as_the_jnp_scan_on_the_chip(
        monkeypatch, chunks, groups):
    """The kernels with bfloat16 operands, as compiled for the chip,
    against the jnp scan with one bfloat16 pass per product (values and
    every gradient within 5e-5 relative: no product of the kernels rounds
    more than the scan's), and against the float32 scan at bfloat16's
    tolerance."""
    args = _inputs(chunks, 2, chunks * Q, 8 * groups, 64, groups, 16)
    kernels = _bfloat16_kernels(groups, 64)
    y = jax.jit(kernels)(*args)
    shapes = [v.shape for v in y]
    grad = lambda f: jax.jit(jax.grad(_weighted(f, shapes),     # noqa: E731
                                      argnums=(0, 1, 2, 3, 4)))
    got = list(y) + list(grad(kernels)(*args))
    exact = list(jax.jit(_scan(False, groups, 64))(*args)) + list(
        grad(_scan(False, groups, 64))(*args))
    monkeypatch.setattr(ref_mod, "jnp", _OnePassJnp())
    one_pass = list(jax.jit(_scan(False, groups, 64))(*args)) + list(
        grad(_scan(False, groups, 64))(*args))
    names = ["y", "state", "x", "B", "C", "dt", "A"]
    for name, g, o, e in zip(names, got, one_pass, exact):
        assert np.all(np.isfinite(np.asarray(g))), name
        assert _rel(g, o) < 5e-5, name
        assert _rel(g, e) < 2e-2, name


def test_underflowing_decay_stays_finite_and_causal():
    """L reaches about -400 in a chunk of 256 (A = -16, dt = 0.1): every
    output and cotangent is finite, and the entries above the diagonal
    are exactly 0: y before the first nonzero input is 0, and so are the
    cotangents of the inputs after the last nonzero output cotangent."""
    chunk, t0 = 256, 100
    xs, Bm, Cm, dt, A = _inputs(5, 1, chunk, 8, 64, 1, 16, hot=True)
    xs = xs.at[:, :t0].set(0.0)
    f = _scan(True, 1, 64, chunk)
    (y, state), vjp = jax.vjp(f, xs, Bm, Cm, dt, A)
    want, _ = _scan(False, 1, 64, chunk)(xs, Bm, Cm, dt, A)
    assert np.all(np.isfinite(np.asarray(y)))
    assert not np.any(np.asarray(y[:, :t0]))
    assert _rel(y, want) < 1e-5
    dy = jax.random.normal(jax.random.PRNGKey(6), y.shape).at[:, t0:].set(0.0)
    dx, dB, dC, ddt, dA = vjp((dy, jnp.zeros_like(state)))
    for g in (dx, dB, dC, ddt, dA):
        assert np.all(np.isfinite(np.asarray(g)))
    assert not np.any(np.asarray(dx[:, t0:]))
    assert not np.any(np.asarray(dB[:, t0:]))
    assert not np.any(np.asarray(dC[:, t0:]))
    assert np.any(np.asarray(dx[:, :t0]))


def _mixer_cfg(groups):
    # 8 heads of 32 per group: one 256-lane head block per group
    return get_smoke("zamba2-7b").replace(
        d_model=128 * groups, ssm_head_dim=32, ssm_state=16, ssm_groups=groups,
        ssm_chunk=Q)


def _forced(monkeypatch):
    monkeypatch.setattr(ops, "ssd_scan",
                        functools.partial(ops.ssd_scan, use_pallas=True))


@pytest.mark.parametrize("groups", [1, 2])
def test_mixer_gradients_match_through_the_kernels(monkeypatch, groups):
    """The mixer's gradients with respect to its input, A_log, dt_bias
    and its LoRA adapters, and its final state (``return_state``)."""
    cfg = _mixer_cfg(groups)
    params = ssm_mod.mamba2_init(jax.random.PRNGKey(0), cfg)
    adapters = lora.init(jax.random.PRNGKey(1), params, {"in_proj", "out_proj"},
                         rank=4)
    adapters = jax.tree_util.tree_map(lambda t: t + 0.05, adapters)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 2 * Q, cfg.d_model))

    def loss(x, p, a):
        out, cache = ssm_mod.mamba2_forward(p, x, cfg, return_state=True,
                                            adapters=a, lora_scale=2.0)
        return jnp.sum(out * jnp.cos(out)), (out, cache["state"])

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    want, (want_y, want_state) = step(x, params, adapters)
    _forced(monkeypatch)
    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
    got, (got_y, got_state) = step(x, params, adapters)
    assert _rel(got_y, want_y) < 1e-5
    assert got_state.shape == want_state.shape
    assert _rel(got_state, want_state) < 1e-5
    gx, gp, ga = got
    wx, wp, wa = want
    assert _rel(gx, wx) < 2e-5
    for name in ("A_log", "dt_bias", "D", "conv_w"):
        assert _rel(gp[name], wp[name]) < 2e-5, name
    for g, w in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(wa)):
        assert _rel(g, w) < 2e-5


def test_zamba2_smoke_forward_with_the_kernels(monkeypatch):
    """The Zamba2 smoke model (widened to 16 heads of 16 so that a head
    block fills 128 lanes) at a chunk of 128 over 256 tokens: the logits
    with the kernels forced on equal the jnp path's."""
    cfg = get_smoke("zamba2-7b").replace(d_model=128, ssm_chunk=Q)
    assert kernel.tiles(2 * Q, Q, 2 * cfg.d_model // cfg.ssm_head_dim,
                        cfg.ssm_groups, cfg.ssm_head_dim)
    params = tfm.zamba2_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 2 * Q), 0,
                                cfg.vocab_size)
    want = jax.jit(lambda p, t: tfm.zamba2_forward(p, t, cfg))(params, tokens)
    _forced(monkeypatch)
    got = jax.jit(lambda p, t: tfm.zamba2_forward(p, t, cfg))(params, tokens)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("heads,groups,head_dim,want", [
    (112, 2, 64, 8),     # Zamba2-7B: 56 heads a group, 8 x 64 = 512 lanes
    (16, 2, 16, 8),      # 8 x 16 = 128 lanes
    (8, 2, 16, None),    # 4 heads of 16 a group fill no 128 lanes
    (4, 1, 128, 4),      # every head: 4 x 128 = 512 lanes
    (8, 1, 256, None),   # a head wider than 128 lanes
    (12, 1, 48, None),   # 48 does not divide 128
])
def test_head_block(heads, groups, head_dim, want):
    assert kernel.head_block(heads, groups, head_dim) == want


def test_cpu_default_is_the_jnp_scan(monkeypatch):
    """On the CPU the scan takes the jnp path unless forced: the kernels
    are never traced."""
    def refuse(*a, **k):
        raise AssertionError("kernel traced on the CPU")
    monkeypatch.setattr(ops, "_ssd", refuse)
    args = _inputs(0, 1, 2 * Q, 8, 64, 1, 16)
    y, state = _scan(None, 1, 64)(*args)
    assert y.shape == (1, 2 * Q, 8 * 64) and state.shape == (1, 8, 16, 64)
