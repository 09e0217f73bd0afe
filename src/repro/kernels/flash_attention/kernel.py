"""Pallas TPU kernel: block-tiled causal/sliding-window GQA flash attention.

Tiling (DESIGN.md §4.3): grid = (B*H, nq). Each program owns one query tile
[bq, D] in VMEM plus the full K/V rows for its (batch, kv-head) — sized for
VMEM residency (S*D*2 bytes*2 <= ~4 MB for S<=8k, D=128 bf16; longer
sequences use the chunked jnp path in models/attention.py, and a production
TPU deployment would add an HBM-streaming variant). The kernel walks K/V in
``bk`` chunks with the online-softmax recurrence in fp32 VREG accumulators;
QK^T and PV hit the MXU with 128-aligned tiles.

GQA is expressed through the BlockSpec index map: query head h reads KV head
h // group_size — no KV duplication in HBM or VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  seq_kv: int, causal: bool, window, scale: float):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                   # [bq, D]
    D = q.shape[-1]
    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq)

    nk = seq_kv // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)   # [bk, D]
        v = v_ref[0, pl.dslice(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                                  # [bq, bk]
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int | None = None,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, Sq, H, D]; k/v: [B, Skv, KV, D]; returns [B, Sq, H, D]."""
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0

    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, D)

    grid = (B * H, Sq // bq)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, seq_kv=Skv,
                          causal=causal, window=window,
                          scale=1.0 / (D ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((1, Skv, D), lambda bh, iq, G=G: (bh // G, 0, 0)),
            pl.BlockSpec((1, Skv, D), lambda bh, iq, G=G: (bh // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
