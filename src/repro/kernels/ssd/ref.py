"""Pure-jnp chunked SSD scan (Mamba2's state-space dual form).

The sequence is split into chunks of ``chunk``; within a chunk the
recurrence is evaluated against a decay-masked [Q, Q] matrix per head,
and the state is carried across chunks by one ``lax.scan``. This is the
oracle of the Pallas kernels in ``kernel.py`` and the path wherever they
do not run (the CPU, chunks that do not tile).

Recurrence (per head h, state size N, head dim P):
    a_t = exp(dt_t * A_h)                       (scalar decay per step)
    S_t = a_t S_{t-1} + dt_t * B_t (x) x_t      (S in R^{N x P})
    y_t = C_t^T S_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def ssd_ref(xh: Array, Bmat: Array, Cmat: Array, dt: Array, log_a: Array, *,
            chunk: int, groups: int, remat: bool = True
            ) -> tuple[Array, Array]:
    """xh: [B, S, H, P]; Bmat, Cmat: [B, S, G * N]; dt, log_a = dt * A:
    [B, S, H]. Head h reads group h // (H / G)'s B and C. Returns y
    [B, S, H, P] and the final state [B, H, N, P], both float32. S must
    be divisible by ``chunk``."""
    B, S, H, P = xh.shape
    G = groups
    N = Bmat.shape[-1] // G
    Hg = H // G
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q

    def to_chunks(t, trailing):
        return t.reshape((B, nc, Q) + trailing).swapaxes(0, 1)

    # heads as [G, Hg]: head h reads group h // Hg's B and C
    xc = to_chunks(xh.astype(jnp.float32), (G, Hg, P))       # [nc,B,Q,G,Hg,P]
    Bc = to_chunks(Bmat.astype(jnp.float32), (G, N))         # [nc,B,Q,G,N]
    Cc = to_chunks(Cmat.astype(jnp.float32), (G, N))
    dtc = to_chunks(dt, (G, Hg))                              # [nc,B,Q,G,Hg]
    lac = to_chunks(log_a, (G, Hg))

    def chunk_step(S_prev, inputs):
        xq, Bq, Cq, dtq, laq = inputs
        # per head, time last: [B,G,Hg,Q] cumulative log decay
        L = jnp.moveaxis(jnp.cumsum(laq, axis=1), 1, -1)
        # intra-chunk: M[t,s] = (C_t.B_s) exp(L_t - L_s) dt_s, s<=t, laid
        # out [B,G,Hg,t,s] so the lane axis is the chunk, not the heads
        CB = jnp.einsum("bqgn,bsgn->bgqs", Cq, Bq)           # [B,G,Q,Q]
        diff = L[..., :, None] - L[..., None, :]             # [B,G,Hg,t,s]
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        # mask INSIDE the exp: where(mask, exp(diff), 0) has a 0*inf = NaN
        # cotangent for the masked (diff>0) entries
        decay = jnp.exp(jnp.where(mask, diff, -1e9))
        M = CB[:, :, None] * decay * jnp.moveaxis(dtq, 1, -1)[..., None, :]
        y_intra = jnp.einsum("bghts,bsghp->btghp", M, xq)
        # inter-chunk: y_inter[t] = exp(L_t) * C_t^T S_prev
        y_inter = jnp.einsum("bqgn,bghnp->bqghp", Cq, S_prev) * \
            jnp.moveaxis(jnp.exp(L), -1, 1)[..., None]
        # state update
        rem = jnp.moveaxis(jnp.exp(L[..., -1:] - L), -1, 1)  # exp(L_Q - L_s)
        Sc = jnp.einsum("bsgn,bsghp->bghnp", Bq, xq * (rem * dtq)[..., None])
        S_new = jnp.exp(L[..., -1])[..., None, None] * S_prev + Sc
        return S_new, y_intra + y_inter

    if remat:   # the backward keeps one chunk's [Q, Q] decays at a time
        chunk_step = jax.checkpoint(chunk_step)

    S0 = jnp.zeros((B, G, Hg, N, P), jnp.float32)
    S_final, ys = jax.lax.scan(chunk_step, S0, (xc, Bc, Cc, dtc, lac))
    y = ys.swapaxes(0, 1).reshape(B, S, H, P)
    return y, S_final.reshape(B, H, N, P)
