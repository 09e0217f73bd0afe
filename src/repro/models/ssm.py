"""Mamba2 (SSD) block — TPU-native chunked-scan implementation.

GPU Mamba uses a fused selective-scan CUDA kernel; the TPU adaptation
(DESIGN.md §4.5) uses the SSD chunkwise form: the sequence is split into
chunks of ``cfg.ssm_chunk``; within a chunk the recurrence is evaluated as
dense (MXU-friendly) matmuls against a decay-masked [Q,Q] matrix, and state
is propagated across chunks with a single ``lax.scan``.

Recurrence (per head h, state size N, head dim P):
    a_t = exp(dt_t * A_h)                       (scalar decay per step)
    S_t = a_t S_{t-1} + dt_t * B_t (x) x_t      (S in R^{N x P})
    y_t = C_t^T S_t + D_h * x_t

With ``cfg.ssm_groups = G`` the heads fall into G equal groups that each
read their own B and C (Mamba2's ``ngroups``), and the gated RMSNorm
normalises each group's channels on their own. ``cfg.ssm_dt_min`` floors
the step after the softplus, as Zamba2's mixer does. ``adapters`` (LoRA
pairs keyed ``in_proj``/``out_proj``, ``repro.models.lora``) add their
low-rank path to the two projections.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import lora
from .layers import rmsnorm_init
from .module import Params, dense_init

Array = jnp.ndarray


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    G = cfg.ssm_groups
    return d_inner, cfg.ssm_state, d_inner // cfg.ssm_head_dim, G


def mamba2_init(key, cfg) -> Params:
    d_inner, N, n_heads, G = _dims(cfg)
    conv_dim = d_inner + 2 * G * N
    k_in, k_conv, k_out, k_a, k_dt = jax.random.split(key, 5)
    return {
        # fused input projection: [z, xBC, dt]
        "in_proj": dense_init(k_in, cfg.d_model, 2 * d_inner + 2 * G * N + n_heads),
        "conv_w": jax.random.normal(k_conv, (cfg.ssm_conv, conv_dim), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads).astype(jnp.float32)),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k_dt, (n_heads,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "D": jnp.ones((n_heads,), jnp.float32),
        "norm": rmsnorm_init(d_inner),
        "out_proj": dense_init(k_out, d_inner, cfg.d_model),
    }


def _causal_conv(xBC: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv, width K. xBC: [B,S,C]; w: [K,C]."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i].astype(xBC.dtype) for i in range(K))
    return jax.nn.silu(out + b.astype(xBC.dtype))


def _split_proj(params, x, cfg, adapters=None, scale=1.0):
    d_inner, N, n_heads, G = _dims(cfg)
    zxbcdt = lora.linear(params["in_proj"], x, (adapters or {}).get("in_proj"),
                         scale)
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * G * N], axis=-1)
    return z, xBC, dt, d_inner, N, n_heads


def _step_size(dt, params, cfg):
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    return jnp.maximum(dt, cfg.ssm_dt_min) if cfg.ssm_dt_min else dt


def _gated_norm(params, y, z, cfg):
    """RMSNorm of ``y * silu(z)`` over each group's channels, then the
    per-channel scale."""
    G = cfg.ssm_groups
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = g.reshape(*g.shape[:-1], G, g.shape[-1] // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    return (g.reshape(y.shape) * params["norm"]["scale"]).astype(y.dtype)


def _out_proj(params, y, adapters, scale):
    return lora.linear(params["out_proj"], y, (adapters or {}).get("out_proj"),
                       scale)


def mamba2_forward(params: Params, x: Array, cfg, *, return_state: bool = False,
                   adapters: Params | None = None, lora_scale: float = 1.0):
    """x: [B, S, d_model] -> [B, S, d_model]. S must be divisible by chunk.
    With return_state=True also returns a decode-ready cache dict."""
    B, S, _ = x.shape
    P = cfg.ssm_head_dim
    G = cfg.ssm_groups
    z, xBC, dt, d_inner, N, H = _split_proj(params, x, cfg, adapters, lora_scale)
    Hg = H // G
    xBC_raw = xBC
    xBC = _causal_conv(xBC, params["conv_w"], params["conv_b"])
    xs, Bmat, Cmat = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    xh = xs.reshape(B, S, H, P)

    dt = _step_size(dt, params, cfg)                                      # [B,S,H]
    A = -jnp.exp(params["A_log"])                                          # [H]
    log_a = dt * A[None, None, :]                                          # [B,S,H] (<0)

    Q = min(cfg.ssm_chunk, S)
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

    if cfg.remat:   # the backward keeps one chunk's [Q, Q] decays at a time
        chunk_step = jax.checkpoint(chunk_step)

    S0 = jnp.zeros((B, G, Hg, N, P), jnp.float32)
    S_final, ys = jax.lax.scan(chunk_step, S0, (xc, Bc, Cc, dtc, lac))
    y = ys.swapaxes(0, 1).reshape(B, S, H, P)
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    out = _out_proj(params, _gated_norm(params, y, z, cfg), adapters, lora_scale)
    if return_state:
        K = cfg.ssm_conv
        conv_tail = xBC_raw[:, S - (K - 1):, :] if S >= K - 1 else jnp.pad(
            xBC_raw, ((0, 0), (K - 1 - S, 0), (0, 0)))
        return out, {"conv": conv_tail, "state": S_final.reshape(B, H, N, P)}
    return out


# ------------------------------------------------------------- decoding ----
def make_ssm_cache(cfg, batch: int, dtype) -> Params:
    d_inner, N, H, G = _dims(cfg)
    conv_dim = d_inner + 2 * G * N
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, H, N, cfg.ssm_head_dim), jnp.float32),
    }


def mamba2_decode(params: Params, x: Array, cache: Params, cfg) -> tuple[Array, Params]:
    """x: [B, 1, d_model] single step."""
    B = x.shape[0]
    P = cfg.ssm_head_dim
    G = cfg.ssm_groups
    z, xBC, dt, d_inner, N, H = _split_proj(params, x, cfg)

    window = jnp.concatenate([cache["conv"], xBC], axis=1)   # [B,K,conv_dim]
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          params["conv_w"]) + params["conv_b"]
    xBC1 = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)
    new_conv = window[:, 1:, :]

    xs, Bmat, Cmat = jnp.split(xBC1, [d_inner, d_inner + G * N], axis=-1)
    xh = xs.reshape(B, H, P).astype(jnp.float32)
    # each head reads its group's B and C
    Bv = jnp.repeat(Bmat[:, 0].reshape(B, G, N), H // G, axis=1).astype(jnp.float32)
    Cv = jnp.repeat(Cmat[:, 0].reshape(B, G, N), H // G, axis=1).astype(jnp.float32)

    dtv = _step_size(dt[:, 0], params, cfg)                  # [B,H]
    a = jnp.exp(dtv * (-jnp.exp(params["A_log"]))[None, :])  # [B,H]
    S_new = a[:, :, None, None] * cache["state"] + \
        jnp.einsum("bhn,bhp->bhnp", Bv, xh * dtv[..., None])
    y = jnp.einsum("bhn,bhnp->bhp", Cv, S_new) + params["D"][None, :, None] * xh
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    out = _out_proj(params, _gated_norm(params, y, z, cfg), None, 1.0)
    return out, {"conv": new_conv, "state": S_new}
