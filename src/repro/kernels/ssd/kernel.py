"""Pallas TPU kernels: Mamba2's chunked SSD scan, forward and backward.

The jnp form (``ref.ssd_ref``) builds each chunk's decay-masked weights
M[t, s] = (C_t . B_s) exp(L_t - L_s) dt_s per head in HBM, and autodiff
writes their cotangent there too. These kernels keep a chunk's [Q, Q]
arrays in VMEM: grid (batch row, chunk, head block), the chunk axis
sequential, the running state of every head block in VMEM scratch from
one chunk to the next.

They read the mixer's own layouts: x as [B, S, H * P] (a block of Hb
heads is (Q, Hb * P); P divides 128, so 128 / P heads share a lane
tile), B and C as [B, S, G * N] (the whole row; the kernel picks the
head block's group), and dt and the chunk-local cumulative log decay L
as [B, H, S], time on the lanes.

Forward (``ssd_chunk_fwd_pallas``): for each head of the block, the
decays exp(L_t - L_s) for s <= t (exactly 0 above the diagonal), then
y = (CB * decay * dt_s) x + exp(L_t) C S_prev and the next state. It
writes y, the state at each chunk's start (the backward's residual) and
the final state.

Backward (``ssd_chunk_bwd_pallas``): chunks in reverse, the state's
cotangent carried in VMEM. Each step rebuilds the chunk's decays from L,
transposed ([s, t]) so that every product is a plain or right-transposed
matmul, forms dM^T = x dy^T in VMEM, and writes dx, d dt, dL and dB, dC.
The cotangents of dt and L take the row and column sums of dM * M as
the jnp scan's autodiff does, so they round as it does. dB and dC sum
over the head blocks in an output block that stays in VMEM across them
(the head-block axis is innermost).

Every product rounds its operands to ``mxu_dtype`` and accumulates in
float32 (bfloat16 on the chip: the one pass XLA makes of a float32 matmul
at default precision); the element-wise decay math is float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Most lanes of a head block: 8 heads of 64 (the unrolled per-head loop
# and its [Q, Q] temporaries stay well inside the default scoped VMEM).
_BLOCK_LANES = 512
# exp of a masked (s > t) entry: 0 exactly, with no inf on the way.
_MASKED = -1e9
# Rows of the [Q, Q] arrays per sub-block: the blocks wholly above the
# diagonal (s > t) are all masked, and neither kernel builds them.
_SUB = 128

_F32 = jnp.float32


def head_block(heads: int, groups: int, head_dim: int) -> int | None:
    """Heads per grid step: the most that divide a group's heads, fill
    whole 128-lane tiles (a head divides 128 lanes) and are a whole
    number of sublane tiles of the [B, H, S] dt and L (a multiple of 8,
    or every head), within ``_BLOCK_LANES`` lanes. None where no block
    tiles."""
    if heads % groups or _LANES % head_dim:
        return None
    per_group = heads // groups
    for hb in range(per_group, 0, -1):
        lanes = hb * head_dim
        if (per_group % hb == 0 and lanes % _LANES == 0
                and lanes <= _BLOCK_LANES and (hb % 8 == 0 or hb == heads)):
            return hb
    return None


def tiles(seq: int, chunk: int, heads: int, groups: int, head_dim: int) -> bool:
    """Whether the kernels take these shapes: whole chunks of a multiple
    of 128 steps, and a head block (``head_block``)."""
    return (chunk % _LANES == 0 and seq % chunk == 0
            and head_block(heads, groups, head_dim) is not None)


def _dot(a, b, mxu_dtype):                     # a @ b
    return jax.lax.dot_general(a.astype(mxu_dtype), b.astype(mxu_dtype),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_nt(a, b, mxu_dtype):                  # a @ b.T
    return jax.lax.dot_general(a.astype(mxu_dtype), b.astype(mxu_dtype),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32)


def _dot_tn(a, b, mxu_dtype):                  # a.T @ b
    return jax.lax.dot_general(a.astype(mxu_dtype), b.astype(mxu_dtype),
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=_F32)


def _group(ref, g, n: int, groups: int):
    """Group ``g``'s N columns of a (Q, G * N) block, in float32."""
    out = ref[:, :n].astype(_F32)
    for gg in range(1, groups):
        out = jnp.where(g == gg, ref[:, gg * n:(gg + 1) * n].astype(_F32), out)
    return out


def _spread(values, lane_head):
    """One value per head of a lane tile, each over its head's lanes:
    ``values`` are (rows, 1) or (1, 1), ``lane_head`` from ``_lane_head``."""
    out = values[0]
    for i, v in enumerate(values[1:], 1):
        out = jnp.where(lane_head == i, v, out)
    return out


def _as_rows(cols):
    """(Q, 1) columns, one per head, as the rows of an (Hb, Q) array."""
    q, hb = cols[0].shape[0], len(cols)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hb), 1)
    m = jnp.zeros((q, hb), _F32)
    for j, col in enumerate(cols):
        m = jnp.where(lane == j, col, m)
    return m.T


def _stack(rows):
    """(1, w) rows, one per head, stacked as an (Hb, w) array."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (len(rows), 1), 0)
    m = jnp.zeros((len(rows), rows[0].shape[1]), _F32)
    for j, row in enumerate(rows):
        m = jnp.where(sub == j, row, m)
    return m


def _causal(r: int, cols0: int, q: int, transposed: bool = False):
    """Mask of row block ``r`` (``_SUB`` rows) over columns ``cols0:q``
    of a chunk's [t, s] array (``s <= t``) or its transpose (``s`` the
    row, ``t`` the column)."""
    shape = (_SUB, q - cols0)
    row = r * _SUB + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = cols0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row <= col if transposed else row >= col


def _lane_head(head_dim: int):
    """(1, 128): the head of each lane of a tile, 128 / P heads a tile."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // head_dim


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, y_ref, st_ref, fin_ref,
                s_scr, *, head_dim: int, state: int, groups: int,
                blocks_per_group: int, mxu_dtype):
    c, h = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], _F32)

    S = s_scr[h]                                   # [N, Hb*P], chunk start
    st_ref[...] = S
    g = h // blocks_per_group
    Bg = _group(b_ref, g, state, groups)           # [Q, N]
    Cg = _group(c_ref, g, state, groups)
    x = x_ref[...].astype(_F32)                    # [Q, Hb*P]
    dt, L = dt_ref[...], l_ref[...]                # [Hb, Q], time on lanes
    dtT, LT = dt.T, L.T                            # [Q, Hb]
    Q = x.shape[0]
    CB = _dot_nt(Cg, Bg, mxu_dtype)                # [t, s]
    CS = _dot(Cg, S, mxu_dtype)                    # [t, Hb*P]
    per_tile, lane_head = _LANES // head_dim, _lane_head(head_dim)

    ys, states = [], []
    for k in range(x.shape[1] // _LANES):
        lanes = slice(k * _LANES, (k + 1) * _LANES)
        heads = range(k * per_tile, (k + 1) * per_tile)
        x_t = x[:, lanes]
        lc = [LT[:, j:j + 1] for j in heads]       # L_t as columns
        ll = [L[j:j + 1, Q - 1:Q] for j in heads]  # L at the chunk's end
        xm = [jnp.where(lane_head == i, x_t, 0.0) for i in range(per_tile)]
        y_rows = []
        for r in range(Q // _SUB):                 # t in this block, s <= t
            rows, cols = slice(r * _SUB, (r + 1) * _SUB), (r + 1) * _SUB
            causal = _causal(r, 0, cols)
            y_r = None
            for i, j in enumerate(heads):
                decay = jnp.exp(jnp.where(
                    causal, lc[i][rows] - L[j:j + 1, :cols], _MASKED))
                M = CB[rows, :cols] * decay * dt[j:j + 1, :cols]
                part = _dot(M, xm[i][:cols], mxu_dtype)
                y_r = part if y_r is None else y_r + part
            y_rows.append(y_r)
        y_t = jnp.concatenate(y_rows, axis=0)
        y_t = y_t + CS[:, lanes] * _spread([jnp.exp(v) for v in lc], lane_head)
        w = _spread([jnp.exp(e - v) * dtT[:, j:j + 1]
                     for e, v, j in zip(ll, lc, heads)], lane_head)
        a = _spread([jnp.exp(e) for e in ll], lane_head)
        states.append(a * S[:, lanes] + _dot_tn(Bg, x_t * w, mxu_dtype))
        ys.append(y_t)

    y_ref[...] = jnp.concatenate(ys, axis=1).astype(y_ref.dtype)
    S_new = jnp.concatenate(states, axis=1)
    s_scr[h] = S_new

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        fin_ref[...] = S_new


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, st_ref, dy_ref, dfin_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dl_ref, ds_scr, *,
                head_dim: int, state: int, groups: int, blocks_per_group: int,
                mxu_dtype):
    c, h = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        ds_scr[h] = dfin_ref[...]

    @pl.when(h == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, db_ref.dtype)
        dc_ref[...] = jnp.zeros(dc_ref.shape, dc_ref.dtype)

    dS = ds_scr[h]                       # [N, Hb*P], cotangent at chunk end
    S = st_ref[...]                      # the state at the chunk's start
    g = h // blocks_per_group
    Bg = _group(b_ref, g, state, groups)
    Cg = _group(c_ref, g, state, groups)
    x = x_ref[...].astype(_F32)
    dy = dy_ref[...].astype(_F32)
    dt, L = dt_ref[...], l_ref[...]
    dtT, LT = dt.T, L.T
    Q = x.shape[0]
    CBT = _dot_nt(Bg, Cg, mxu_dtype)               # [s, t]
    CS = _dot(Cg, S, mxu_dtype)                    # [t, Hb*P]
    GS = _dot(Bg, dS, mxu_dtype)                   # B dS: cotangent of w x
    per_tile, lane_head = _LANES // head_dim, _lane_head(head_dim)
    nb = Q // _SUB

    # dCB^T by row block r (s in it, t from r * _SUB on)
    dCBT = [jnp.zeros((_SUB, Q - r * _SUB), _F32) for r in range(nb)]
    dB = jnp.zeros((Q, state), _F32)
    dC = jnp.zeros((Q, state), _F32)
    dxs, dSs = [], []
    ddt_cols, dl_cols, dl_rows, dl_last = [], [], [], []
    for k in range(x.shape[1] // _LANES):
        lanes = slice(k * _LANES, (k + 1) * _LANES)
        heads = range(k * per_tile, (k + 1) * per_tile)
        x_t, dy_t, S_t, dS_t = x[:, lanes], dy[:, lanes], S[:, lanes], dS[:, lanes]
        lc = [LT[:, j:j + 1] for j in heads]       # L_s as columns
        dtc = [dtT[:, j:j + 1] for j in heads]
        ll = [L[j:j + 1, Q - 1:Q] for j in heads]
        e = [jnp.exp(v) for v in lc]
        a = [jnp.exp(v) for v in ll]
        rem = [jnp.exp(u - v) for u, v in zip(ll, lc)]
        w = [r * d for r, d in zip(rem, dtc)]
        W = _spread(w, lane_head)

        # y += exp(L_t) C S_prev
        edy = dy_t * _spread(e, lane_head)
        dSs.append(_spread(a, lane_head) * dS_t + _dot_tn(Cg, edy, mxu_dtype))
        dC = dC + _dot_nt(edy, S_t, mxu_dtype)
        # S_new = exp(L_last) S_prev + B^T (w x)
        dB = dB + _dot_nt(x_t * W, dS_t, mxu_dtype)
        dx_t = W * GS[:, lanes]
        dx_rows = [dx_t[r * _SUB:(r + 1) * _SUB] for r in range(nb)]
        gx, dycs, dss = GS[:, lanes] * x_t, dy_t * CS[:, lanes], dS_t * S_t

        for i, j in enumerate(heads):
            pick = lambda v, i=i: jnp.where(lane_head == i, v, 0.0)  # noqa: E731
            dw = jnp.sum(pick(gx), axis=1, keepdims=True)           # [Q, 1]
            de = jnp.sum(pick(dycs), axis=1, keepdims=True)
            da = jnp.sum(jnp.sum(pick(dss), axis=1, keepdims=True), axis=0,
                         keepdims=True)                             # [1, 1]
            dym = pick(dy_t)
            # y += M x, M^T[s, t] = CB[t, s] exp(L_t - L_s) dt_s, by row
            # block r of s over t >= r * _SUB
            rs, cs = [], jnp.zeros((1, Q), _F32)
            for r in range(nb):
                rows, t0 = slice(r * _SUB, (r + 1) * _SUB), r * _SUB
                dtr = dtc[i][rows]
                decay = jnp.exp(jnp.where(_causal(r, t0, Q, transposed=True),
                                          L[j:j + 1, t0:] - lc[i][rows], _MASKED))
                CD = CBT[rows, t0:] * decay
                dx_rows[r] = dx_rows[r] + _dot(CD * dtr, dym[t0:], mxu_dtype)
                dMT = _dot_nt(x_t[rows], dym[t0:], mxu_dtype)        # [s, t]
                dCBT[r] = dCBT[r] + dMT * decay * dtr
                # dM * M, summed over t (for d dt_s) and over s (for dL_t)
                Y = dMT * CD
                rs.append(jnp.sum(Y, axis=1, keepdims=True))
                c_r = jnp.sum(Y * dtr, axis=0, keepdims=True)
                cs = cs + (c_r if t0 == 0 else jnp.concatenate(
                    [jnp.zeros((1, t0), _F32), c_r], axis=1))
            rs = jnp.concatenate(rs, axis=0)
            dl_rows.append(cs)
            ddt_cols.append(rs + dw * rem[i])
            dl_cols.append(e[i] * de - dtc[i] * rs - dw * w[i])
            dl_last.append(a[i] * da + jnp.sum(dw * w[i], axis=0, keepdims=True))
        dxs.append(jnp.concatenate(dx_rows, axis=0))

    for r in range(nb):
        rows, t0 = slice(r * _SUB, (r + 1) * _SUB), r * _SUB
        dC_r = _dot_tn(dCBT[r], Bg[rows], mxu_dtype)           # t >= t0
        dC = dC + (dC_r if t0 == 0 else jnp.concatenate(
            [jnp.zeros((t0, state), _F32), dC_r], axis=0))
    dB = dB + jnp.concatenate(
        [_dot(dCBT[r], Cg[r * _SUB:], mxu_dtype) for r in range(nb)], axis=0)
    dx_ref[...] = jnp.concatenate(dxs, axis=1).astype(dx_ref.dtype)
    ds_scr[h] = jnp.concatenate(dSs, axis=1)
    for gg in range(groups):
        @pl.when(g == gg)
        def _(gg=gg):
            cols = slice(gg * state, (gg + 1) * state)
            db_ref[:, cols] += dB
            dc_ref[:, cols] += dC
    ddt_ref[...] = _as_rows(ddt_cols)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    dl_ref[...] = (_as_rows(dl_cols) + _stack(dl_rows)
                   + jnp.where(last, _stack(dl_last), 0.0))


def _geometry(x, b, chunk: int, groups: int, head_dim: int):
    batch, seq, lanes = x.shape
    heads = lanes // head_dim
    state = b.shape[-1] // groups
    hb = head_block(heads, groups, head_dim)
    assert tiles(seq, chunk, heads, groups, head_dim), (seq, chunk, heads)
    n_blocks = heads // hb
    return dict(batch=batch, nc=seq // chunk, hb=hb, lanes=hb * head_dim,
                n_blocks=n_blocks, state=state,
                blocks_per_group=n_blocks // groups)


@functools.partial(jax.jit, static_argnames=("chunk", "groups", "head_dim",
                                             "mxu_dtype", "interpret"))
def ssd_chunk_fwd_pallas(x, b, c, dt, L, *, chunk: int, groups: int,
                         head_dim: int, mxu_dtype=jnp.bfloat16,
                         interpret: bool = False):
    """x: [B, S, H * P]; b, c: [B, S, G * N]; dt, L: [B, H, S], L the
    cumulative log decay within each chunk; all float32. Returns y
    [B, S, H * P], the state at each chunk's start [B, S / chunk, N, H * P]
    and the final state [B, N, H * P]."""
    geo = _geometry(x, b, chunk, groups, head_dim)
    nc, hb, lanes, N = geo["nc"], geo["hb"], geo["lanes"], geo["state"]
    GN = b.shape[-1]
    row = pl.BlockSpec((None, chunk, lanes), lambda i, t, h: (i, t, h))
    bc = pl.BlockSpec((None, chunk, GN), lambda i, t, h: (i, t, 0))
    per_head = pl.BlockSpec((None, hb, chunk), lambda i, t, h: (i, h, t))
    starts = pl.BlockSpec((None, None, N, lanes), lambda i, t, h: (i, t, 0, h))
    # the final state's block is written at the last chunk only; before
    # it every step maps to block 0, which is not written back until then
    final = pl.BlockSpec((None, N, lanes),
                         lambda i, t, h: (i, 0, jnp.where(t == nc - 1, h, 0)))
    batch, seq, width = x.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=head_dim, state=N,
                          groups=groups,
                          blocks_per_group=geo["blocks_per_group"],
                          mxu_dtype=mxu_dtype),
        grid=(batch, nc, geo["n_blocks"]),
        in_specs=[row, bc, bc, per_head, per_head],
        out_specs=[row, starts, final],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((batch, nc, N, width), _F32),
                   jax.ShapeDtypeStruct((batch, N, width), _F32)],
        scratch_shapes=[pltpu.VMEM((geo["n_blocks"], N, lanes), _F32)],
        interpret=interpret,
        name="ssd_chunk_fwd_pallas",
    )(x, b, c, dt, L)


@functools.partial(jax.jit, static_argnames=("chunk", "groups", "head_dim",
                                             "mxu_dtype", "interpret"))
def ssd_chunk_bwd_pallas(x, b, c, dt, L, starts, dy, dfinal, *, chunk: int,
                         groups: int, head_dim: int, mxu_dtype=jnp.bfloat16,
                         interpret: bool = False):
    """The cotangents of ``ssd_chunk_fwd_pallas``'s inputs from those of
    y (``dy``) and of the final state (``dfinal``), with its chunk-start
    states ``starts``: (dx, db, dc, d dt, dL) in the inputs' shapes."""
    geo = _geometry(x, b, chunk, groups, head_dim)
    nc, hb, lanes, N = geo["nc"], geo["hb"], geo["lanes"], geo["state"]
    GN = b.shape[-1]
    rev = lambda t: nc - 1 - t                                   # noqa: E731
    row = pl.BlockSpec((None, chunk, lanes), lambda i, t, h: (i, rev(t), h))
    bc = pl.BlockSpec((None, chunk, GN), lambda i, t, h: (i, rev(t), 0))
    per_head = pl.BlockSpec((None, hb, chunk), lambda i, t, h: (i, h, rev(t)))
    start = pl.BlockSpec((None, None, N, lanes),
                         lambda i, t, h: (i, rev(t), 0, h))
    # read at the first (last in time) chunk only
    final = pl.BlockSpec((None, N, lanes),
                         lambda i, t, h: (i, 0, jnp.where(t == 0, h, 0)))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=head_dim, state=N,
                          groups=groups,
                          blocks_per_group=geo["blocks_per_group"],
                          mxu_dtype=mxu_dtype),
        grid=(geo["batch"], nc, geo["n_blocks"]),
        in_specs=[row, bc, bc, per_head, per_head, start, row, final],
        out_specs=[row, bc, bc, per_head, per_head],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, _F32),
                   jax.ShapeDtypeStruct(c.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(L.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((geo["n_blocks"], N, lanes), _F32)],
        interpret=interpret,
        name="ssd_chunk_bwd_pallas",
    )(x, b, c, dt, L, starts, dy, dfinal)
