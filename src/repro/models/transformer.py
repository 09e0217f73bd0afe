"""Decoder-only LM assembly for all assigned families.

Families:
  dense   — GQA attention + SwiGLU          (tinyllama, qwen2.5-32b, glm4-9b,
                                             qwen2-72b, phi-3-vision backbone)
  moe     — GQA attention + MoE FFN          (qwen2-moe, mixtral-8x22b)
  ssm     — RWKV6 time-mix + channel-mix     (rwkv6-1.6b)
  hybrid  — Mamba2 backbone + ONE shared attention block applied every
            ``attn_every`` layers (parameters shared — zamba2-style), or,
            with ``cfg.layers_block_type``, Zamba2 itself
            (``zamba2_forward``, training forward only). Its departures
            from ``transformers``' ``modeling_zamba2``: the SSD runs in
            chunks of ``ssm_chunk`` (the same sums in another order; the
            public multi-chunk path itself departs from its one-chunk
            result by about 1e-5), and there is no attention mask, cache
            or shared attention adapter (Zamba2-7B uses none)

Layers are stacked and consumed by ``lax.scan`` (with ``jax.checkpoint``
when cfg.remat) so the HLO stays small and the remat policy is explicit.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import lora
from . import moe as moe_mod
from . import rwkv as rwkv_mod
from . import ssm as ssm_mod
from .layers import (apply_rope, layernorm, layernorm_init, rmsnorm,
                     rmsnorm_init, swiglu, swiglu_init)
from .module import (Params, dtype_of, embed, embed_init, stack_init, unembed,
                     dense_init, dense, scan_layers)
from repro.sharding.act import constrain

Array = jnp.ndarray


# ------------------------------------------------------------ layer defs ----
def _dense_layer_init(key, cfg) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model), "attn": attn.attention_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff)}


def _moe_layer_init(key, cfg) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model), "attn": attn.attention_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "moe": moe_mod.moe_init(k2, cfg)}


def _rwkv_layer_init(key, cfg) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": layernorm_init(cfg.d_model), "time": rwkv_mod.rwkv6_init(k1, cfg),
            "ln2": layernorm_init(cfg.d_model), "ffn": rwkv_mod.rwkv_ffn_init(k2, cfg)}


def _mamba_layer_init(key, cfg) -> Params:
    return {"ln": rmsnorm_init(cfg.d_model), "mamba": ssm_mod.mamba2_init(key, cfg)}


def _shared_attn_block_init(key, cfg) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": rmsnorm_init(cfg.d_model), "attn": attn.attention_init(k1, cfg),
            "ln2": rmsnorm_init(cfg.d_model), "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff)}


# ------------------------------------------------------------------ init ----
def init_lm(key, cfg) -> Params:
    if cfg.layers_block_type:
        return zamba2_init(key, cfg)
    ke, kl, kh, ks = jax.random.split(key, 4)
    p: Params = {"embed": embed_init(ke, cfg.vocab_size, cfg.d_model),
                 "ln_f": (layernorm_init if cfg.family == "ssm" else rmsnorm_init)(cfg.d_model)}
    if not cfg.tie_embeddings:
        p["lm_head"] = {"table": jax.random.normal(kh, (cfg.vocab_size, cfg.d_model),
                                                   jnp.float32) * 0.02}
    if cfg.family in ("dense", "vlm"):
        p["layers"] = stack_init(_dense_layer_init, kl, cfg.n_layers, cfg)
    elif cfg.family == "moe":
        p["layers"] = stack_init(_moe_layer_init, kl, cfg.n_layers, cfg)
    elif cfg.family == "ssm":
        p["layers"] = stack_init(_rwkv_layer_init, kl, cfg.n_layers, cfg)
    elif cfg.family == "hybrid":
        assert cfg.attn_every and cfg.n_layers % cfg.attn_every == 0
        p["layers"] = stack_init(_mamba_layer_init, kl, cfg.n_layers, cfg)
        p["shared_attn"] = _shared_attn_block_init(ks, cfg)
    else:
        raise ValueError(cfg.family)
    if cfg.family == "vlm":
        p["vision_proj"] = dense_init(ks, cfg.d_model, cfg.d_model)
    return p


# --------------------------------------------------------------- forward ----
def _maybe_ckpt(fn, cfg, **kw):
    return jax.checkpoint(fn, **kw) if cfg.remat else fn


def _window_for(cfg, seq_len: int) -> Optional[int]:
    if cfg.sliding_window is not None:
        return cfg.sliding_window
    return None


def _dense_block(layer, x, cfg, window):
    x = x + attn.attention_forward(layer["attn"], rmsnorm(layer["ln1"], x, cfg.norm_eps),
                                   cfg, window=window)
    x = x + swiglu(layer["mlp"], rmsnorm(layer["ln2"], x, cfg.norm_eps))
    return x


def lm_forward(params: Params, tokens: Array, cfg, *,
               extra_embeds: Optional[Array] = None,
               window: Optional[int] = None) -> tuple[Array, Array]:
    """tokens: [B, S_text] int32. extra_embeds (vlm/audio): [B, S_vis, d]
    prepended to the token embeddings. Returns (logits [B,S,V], aux_loss)."""
    if cfg.layers_block_type:
        return zamba2_forward(params, tokens, cfg), jnp.float32(0)
    dt = dtype_of(cfg)
    x = embed(params["embed"], tokens, dt)
    if extra_embeds is not None:
        vis = dense(params["vision_proj"], extra_embeds.astype(dt))
        x = jnp.concatenate([vis, x], axis=1)
    x = constrain(x, "batch", None, None)
    if window is None:
        window = _window_for(cfg, x.shape[1])

    fam = cfg.family
    if fam in ("dense", "vlm"):
        def body(h, layer):
            h = constrain(h, "batch", "seq_tp", None)
            return _dense_block(layer, h, cfg, window), jnp.float32(0)
    elif fam == "moe":
        def body(h, layer):
            h = constrain(h, "batch", "seq_tp", None)
            h = h + attn.attention_forward(layer["attn"],
                                           rmsnorm(layer["ln1"], h, cfg.norm_eps),
                                           cfg, window=window)
            y, aux = moe_mod.moe_forward(layer["moe"], rmsnorm(layer["ln2"], h, cfg.norm_eps), cfg)
            return h + y, aux
    elif fam == "ssm":
        def body(h, layer):
            h = constrain(h, "batch", "seq_tp", None)
            h = h + rwkv_mod.rwkv6_forward(layer["time"], layernorm(layer["ln1"], h, cfg.norm_eps), cfg)
            h = h + rwkv_mod.rwkv_ffn(layer["ffn"], layernorm(layer["ln2"], h, cfg.norm_eps))
            return h, jnp.float32(0)
    elif fam == "hybrid":
        shared = params["shared_attn"]
        k = cfg.attn_every

        def body(h, group):          # group: k stacked mamba layers
            h = constrain(h, "batch", "seq_tp", None)
            def mamba_body(hh, layer):
                return hh + ssm_mod.mamba2_forward(
                    layer["mamba"], rmsnorm(layer["ln"], hh, cfg.norm_eps), cfg), None
            h, _ = scan_layers(mamba_body, h, group, cfg, ckpt=cfg.remat)
            h = _dense_block(shared, h, cfg, window)
            return h, jnp.float32(0)
    else:
        raise ValueError(fam)

    layers = params["layers"]
    if fam == "hybrid":
        layers = jax.tree_util.tree_map(
            lambda t: t.reshape((cfg.n_layers // cfg.attn_every, cfg.attn_every) + t.shape[1:]),
            layers)
    x, auxs = scan_layers(body, x, layers, cfg, ckpt=cfg.remat)

    x = (layernorm if fam == "ssm" else rmsnorm)(params["ln_f"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = constrain(unembed(head, x), "batch", None, "vocab")
    return logits, jnp.sum(auxs)


def lm_loss(params: Params, batch: dict, cfg) -> tuple[Array, dict]:
    """Next-token cross-entropy. batch: {"tokens": [B,S]} (+ optional
    "extra_embeds"). Positions with label < 0 are masked out."""
    tokens = batch["tokens"]
    logits, aux = lm_forward(params, tokens, cfg,
                             extra_embeds=batch.get("extra_embeds"))
    if "extra_embeds" in batch and batch["extra_embeds"] is not None:
        logits = logits[:, batch["extra_embeds"].shape[1]:]  # text region only
    labels = batch.get("labels")
    if labels is None:
        labels = tokens[:, 1:]
        logits = logits[:, :-1]
    mask = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------- zamba2 ----
# Zamba2 (arXiv:2411.15242) as ``transformers``' ``modeling_zamba2`` computes
# it: every layer has a Mamba2 mixer (pre-RMSNorm, residual); a "hybrid"
# layer first runs shared transformer block ``j % num_mem_blocks`` (j its
# index among the hybrids) on ``[h, embedding]`` (width 2d): RMSNorm,
# attention of ``n_heads`` heads of ``head_dim`` with RoPE and scale
# ``(head_dim / 2) ** -0.5``, no residual, RMSNorm, a gated-GELU MLP whose
# ``gate_up`` adds the invocation's own rank-``adapter_rank`` adapter, no
# residual; then the invocation's own ``linear`` (d -> d), whose output
# is added to the Mamba layer's input (not to its residual). Final
# RMSNorm and an embedding-tied head. Departures: module docstring.
ZAMBA2_LORA_TARGETS = ("in_proj", "out_proj", "q", "k", "v", "o", "gate_up",
                       "down", "linear")


def _zamba2_block_init(key, cfg) -> Params:
    d, d2, hd = cfg.d_model, 2 * cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko, kg, kd = jax.random.split(key, 6)
    return {"attn_norm": rmsnorm_init(d2),
            "q": dense_init(kq, d2, cfg.n_heads * hd),
            "k": dense_init(kk, d2, cfg.n_kv_heads * hd),
            "v": dense_init(kv, d2, cfg.n_kv_heads * hd),
            "o": dense_init(ko, cfg.n_heads * hd, d),
            "mlp_norm": rmsnorm_init(d),
            "gate_up": dense_init(kg, d, 2 * cfg.d_ff),
            "down": dense_init(kd, cfg.d_ff, d)}


def _zamba2_hybrid_init(key, cfg) -> Params:
    d, r = cfg.d_model, cfg.adapter_rank
    ka, kb, kl = jax.random.split(key, 3)
    return {"adapter_a": dense_init(ka, d, r),
            "adapter_b": dense_init(kb, r, 2 * cfg.d_ff),
            "linear": dense_init(kl, d, d)}


def zamba2_init(key, cfg) -> Params:
    """``embed`` (tied head), ``final_norm``, ``layers`` (the Mamba layers,
    stacked), ``blocks`` (the shared blocks) and ``hybrid`` (each
    invocation's MLP adapter and ``linear``)."""
    n_hybrid = cfg.layers_block_type.count("hybrid")
    ke, kl, kb, kh = jax.random.split(key, 4)
    return {"embed": embed_init(ke, cfg.vocab_size, cfg.d_model),
            "final_norm": rmsnorm_init(cfg.d_model),
            "layers": stack_init(_mamba_layer_init, kl, cfg.n_layers, cfg),
            "blocks": [_zamba2_block_init(k, cfg)
                       for k in jax.random.split(kb, cfg.num_mem_blocks)],
            "hybrid": [_zamba2_hybrid_init(k, cfg)
                       for k in jax.random.split(kh, n_hybrid)]}


def _zamba2_mamba(layer, adapters, h, t, cfg, scale):
    """One Mamba layer: ``h + mamba(norm(h + t))``, t the shared block's
    contribution on a hybrid layer."""
    with jax.named_scope("fl.mamba"):
        x = rmsnorm(layer["ln"], h if t is None else h + t, cfg.norm_eps)
        return h + ssm_mod.mamba2_forward(
            layer["mamba"], x, cfg, adapters=adapters and adapters["mamba"],
            lora_scale=scale)


def _zamba2_shared(block, hybrid, ad_block, ad_hybrid, h, emb, cfg, scale):
    """A shared block's invocation and its ``linear``: [B,S,d] -> [B,S,d]."""
    def lin(params, adapters, name, x):
        return lora.linear(params[name], x, (adapters or {}).get(name), scale)

    with jax.named_scope("fl.shared_block"):
        B, S, d = h.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        x = rmsnorm(block["attn_norm"], jnp.concatenate([h, emb], -1),
                    cfg.norm_eps)
        pos = jnp.arange(S)
        q = apply_rope(lin(block, ad_block, "q", x).reshape(B, S, H, hd), pos,
                       cfg.rope_theta)
        k = apply_rope(lin(block, ad_block, "k", x).reshape(B, S, KV, hd), pos,
                       cfg.rope_theta)
        v = lin(block, ad_block, "v", x).reshape(B, S, KV, hd)
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd / 2) ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores.astype(jnp.float32),
                                         attn.NEG_INF), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        o = lin(block, ad_block, "o", o.reshape(B, S, H * hd))
        x = rmsnorm(block["mlp_norm"], o, cfg.norm_eps)
        gate_up = lin(block, ad_block, "gate_up", x) + dense(
            hybrid["adapter_b"], dense(hybrid["adapter_a"], x))
        gate, up = jnp.split(gate_up, 2, axis=-1)
        y = lin(block, ad_block, "down",
                jax.nn.gelu(gate, approximate=False) * up)
        return lin(hybrid, ad_hybrid, "linear", y)


def zamba2_forward(params: Params, tokens: Array, cfg,
                   adapters: Optional[Params] = None,
                   lora_scale: float = 1.0) -> Array:
    """tokens [B, S] -> logits [B, S, V] (float32). ``adapters``: LoRA
    pairs over ``ZAMBA2_LORA_TARGETS`` (``lora.init``), applied unmerged
    with scale ``lora_scale``. Runs of Mamba layers go through one
    ``lax.scan`` each (the layer indexed out of the stack inside the
    body); with ``cfg.remat`` every layer is rematerialised."""
    ad = adapters or {}
    ckpt = partial(_maybe_ckpt, cfg=cfg)
    emb = embed(params["embed"], tokens, dtype_of(cfg))
    stack, ad_stack = params["layers"], ad.get("layers")
    ad_blocks = ad.get("blocks") or [None] * len(params["blocks"])
    ad_hybrid = ad.get("hybrid") or [None] * len(params["hybrid"])

    def layer_at(i):
        take = lambda t: jax.lax.dynamic_index_in_dim(t, i, keepdims=False)
        return (jax.tree_util.tree_map(take, stack),
                jax.tree_util.tree_map(take, ad_stack))

    def mamba_body(h, i):
        layer, a = layer_at(i)
        return _zamba2_mamba(layer, a, h, None, cfg, lora_scale), None

    def hybrid_layer(h, i, j):
        b = j % cfg.num_mem_blocks
        t = _zamba2_shared(params["blocks"][b], params["hybrid"][j],
                           ad_blocks[b], ad_hybrid[j], h, emb, cfg,
                           lora_scale)
        layer, a = layer_at(i)
        return _zamba2_mamba(layer, a, h, t, cfg, lora_scale)

    h, run, j = emb, [], 0
    for i, kind in enumerate(tuple(cfg.layers_block_type) + ("end",)):
        if kind == "mamba":
            run.append(i)
            continue
        if run:
            h, _ = jax.lax.scan(ckpt(mamba_body), h,
                                jnp.arange(run[0], run[-1] + 1))
            run = []
        if kind == "hybrid":
            h = ckpt(hybrid_layer, static_argnums=(1, 2))(h, i, j)
            j += 1
    x = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return unembed(params["embed"], x)


def zamba2_loss(params: Params, batch: dict, cfg,
                adapters: Optional[Params] = None,
                lora_scale: float = 1.0) -> tuple[Array, dict]:
    """Mean next-token cross-entropy of ``batch["tokens"]`` [B, S + 1]
    over its S targets."""
    tokens = batch["tokens"]
    logits = zamba2_forward(params, tokens[:, :-1], cfg, adapters, lora_scale)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss, {"xent": loss}


# --------------------------------------------------------------- prefill ----
def lm_prefill(params: Params, tokens: Array, cfg, *, cache_len: int,
               extra_embeds: Optional[Array] = None,
               window: Optional[int] = None) -> tuple[Array, Params]:
    """Serving prefill: forward pass that also materializes a decode-ready
    cache (ring KV / SSM state). Returns (last-token logits [B,1,V], cache)."""
    if cfg.layers_block_type:
        raise NotImplementedError("zamba2_forward has no serving cache")
    dt = dtype_of(cfg)
    x = embed(params["embed"], tokens, dt)
    if extra_embeds is not None:
        vis = dense(params["vision_proj"], extra_embeds.astype(dt))
        x = jnp.concatenate([vis, x], axis=1)
    if window is None:
        window = _window_for(cfg, x.shape[1])
    x = constrain(x, "batch", None, None)
    fam = cfg.family

    if fam in ("dense", "vlm", "moe"):
        def body(h, layer):
            h = constrain(h, "batch", "seq_tp", None)
            y, (k, v) = attn.attention_forward(
                layer["attn"], rmsnorm(layer["ln1"], h, cfg.norm_eps), cfg,
                window=window, return_kv=True)
            h = h + y
            mlp_in = rmsnorm(layer["ln2"], h, cfg.norm_eps)
            if fam == "moe":
                y2, _ = moe_mod.moe_forward(layer["moe"], mlp_in, cfg)
            else:
                y2 = swiglu(layer["mlp"], mlp_in)
            return h + y2, attn.fill_kv_cache(k, v, cache_len, dt)
        x, caches = scan_layers(body, x, params["layers"], cfg, ckpt=cfg.remat)
        cache = {"layers": caches}
    elif fam == "ssm":
        def body(h, layer):
            ln1 = layernorm(layer["ln1"], h, cfg.norm_eps)
            y, st = rwkv_mod.rwkv6_forward(layer["time"], ln1, cfg, return_state=True)
            h = h + y
            ln2 = layernorm(layer["ln2"], h, cfg.norm_eps)
            h = h + rwkv_mod.rwkv_ffn(layer["ffn"], ln2)
            return h, dict(st, ffn_shift=ln2[:, -1:, :])
        x, caches = scan_layers(body, x, params["layers"], cfg, ckpt=cfg.remat)
        cache = {"layers": caches}
    elif fam == "hybrid":
        shared = params["shared_attn"]
        k_every = cfg.attn_every
        glayers = jax.tree_util.tree_map(
            lambda t: t.reshape((cfg.n_layers // k_every, k_every) + t.shape[1:]),
            params["layers"])

        def body(h, group):
            def mamba_body(hh, layer):
                y, st = ssm_mod.mamba2_forward(
                    layer["mamba"], rmsnorm(layer["ln"], hh, cfg.norm_eps), cfg,
                    return_state=True)
                return hh + y, st
            h, sts = scan_layers(mamba_body, h, group, cfg, ckpt=cfg.remat)
            y, (k, v) = attn.attention_forward(
                shared["attn"], rmsnorm(shared["ln1"], h, cfg.norm_eps), cfg,
                window=window, return_kv=True)
            h = h + y
            h = h + swiglu(shared["mlp"], rmsnorm(shared["ln2"], h, cfg.norm_eps))
            return h, (sts, attn.fill_kv_cache(k, v, cache_len, dt))
        x, (ssm_caches, kv_caches) = scan_layers(body, x, glayers, cfg, ckpt=cfg.remat)
        cache = {
            "layers": jax.tree_util.tree_map(
                lambda t: t.reshape((cfg.n_layers,) + t.shape[2:]), ssm_caches),
            "shared_attn": kv_caches,
        }
    else:
        raise ValueError(fam)

    x = (layernorm if fam == "ssm" else rmsnorm)(params["ln_f"], x[:, -1:], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), cache


# ---------------------------------------------------------------- decode ----
def init_lm_cache(cfg, batch: int, cache_len: int) -> Params:
    """Stacked per-layer caches for scan-over-layers decode."""
    dt = dtype_of(cfg)

    def stack(make_one, n):
        one = make_one()
        return jax.tree_util.tree_map(lambda t: jnp.broadcast_to(t, (n,) + t.shape), one)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        return {"layers": stack(lambda: attn.make_kv_cache(cfg, batch, cache_len, dt),
                                cfg.n_layers)}
    if fam == "ssm":
        return {"layers": stack(lambda: rwkv_mod.make_rwkv_cache(cfg, batch, dt),
                                cfg.n_layers)}
    if fam == "hybrid":
        n_groups = cfg.n_layers // cfg.attn_every
        return {
            "layers": stack(lambda: ssm_mod.make_ssm_cache(cfg, batch, dt), cfg.n_layers),
            "shared_attn": stack(lambda: attn.make_kv_cache(cfg, batch, cache_len, dt),
                                 n_groups),
        }
    raise ValueError(fam)


def lm_decode(params: Params, token: Array, cache: Params, pos: Array, cfg
              ) -> tuple[Array, Params]:
    """One decode step. token: [B,1] int32; pos: scalar int32.
    Returns (logits [B,1,V], new cache)."""
    dt = dtype_of(cfg)
    x = embed(params["embed"], token, dt)
    fam = cfg.family

    if fam in ("dense", "vlm", "moe"):
        def body(h, xs):
            layer, kv = xs
            y, kv2 = attn.attention_decode(layer["attn"],
                                           rmsnorm(layer["ln1"], h, cfg.norm_eps),
                                           kv, pos, cfg)
            h = h + y
            mlp_in = rmsnorm(layer["ln2"], h, cfg.norm_eps)
            if fam == "moe":
                y2, _ = moe_mod.moe_forward(layer["moe"], mlp_in, cfg)
            else:
                y2 = swiglu(layer["mlp"], mlp_in)
            return h + y2, kv2
        x, new_kv = scan_layers(body, x, (params["layers"], cache["layers"]), cfg)
        new_cache = {"layers": new_kv}

    elif fam == "ssm":
        def body(h, xs):
            layer, c = xs
            y, c2 = rwkv_mod.rwkv6_decode(layer["time"],
                                          layernorm(layer["ln1"], h, cfg.norm_eps), c, cfg)
            h = h + y
            ffn_in = layernorm(layer["ln2"], h, cfg.norm_eps)
            y2 = rwkv_mod.rwkv_ffn(layer["ffn"], ffn_in, prev=c2["ffn_shift"])
            c2 = dict(c2, ffn_shift=ffn_in)
            return h + y2, c2
        x, new_c = scan_layers(body, x, (params["layers"], cache["layers"]), cfg)
        new_cache = {"layers": new_c}

    elif fam == "hybrid":
        shared = params["shared_attn"]
        k = cfg.attn_every
        n_groups = cfg.n_layers // k
        glayers = jax.tree_util.tree_map(
            lambda t: t.reshape((n_groups, k) + t.shape[1:]), params["layers"])
        gcaches = jax.tree_util.tree_map(
            lambda t: t.reshape((n_groups, k) + t.shape[1:]), cache["layers"])

        def group_body(h, xs):
            group, gcache, kv = xs

            def mamba_body(hh, ys):
                layer, c = ys
                y, c2 = ssm_mod.mamba2_decode(layer["mamba"],
                                              rmsnorm(layer["ln"], hh, cfg.norm_eps), c, cfg)
                return hh + y, c2
            h, gcache2 = scan_layers(mamba_body, h, (group, gcache), cfg)
            y, kv2 = attn.attention_decode(shared["attn"],
                                           rmsnorm(shared["ln1"], h, cfg.norm_eps),
                                           kv, pos, cfg)
            h = h + y
            h = h + swiglu(shared["mlp"], rmsnorm(shared["ln2"], h, cfg.norm_eps))
            return h, (gcache2, kv2)

        x, (new_g, new_kv) = scan_layers(group_body, x, (glayers, gcaches, cache["shared_attn"]), cfg)
        new_cache = {
            "layers": jax.tree_util.tree_map(
                lambda t: t.reshape((cfg.n_layers,) + t.shape[2:]), new_g),
            "shared_attn": new_kv,
        }
    else:
        raise ValueError(fam)

    x = (layernorm if fam == "ssm" else rmsnorm)(params["ln_f"], x, cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return unembed(head, x), new_cache
