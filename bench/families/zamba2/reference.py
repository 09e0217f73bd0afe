"""The Zamba2 family's half of the plain reference: federated LoRA
fine-tuning of Zamba2 over a frozen base, in straightforward
``jax.numpy``. Independent of the program: nothing here imports ``repro``.

The model, as ``transformers``' ``modeling_zamba2`` computes it: every
layer has a Mamba2 mixer behind an RMSNorm and a residual; a "hybrid"
layer first runs shared block ``j % num_mem_blocks`` (j its index among
the hybrids) on the concatenation ``[h, embedding]``: RMSNorm,
attention (RoPE, scale ``(head_dim / 2) ** -0.5``), RMSNorm, a
gated-GELU MLP with the invocation's own adapter on ``gate_up``, then
the invocation's ``linear``, whose output is added to the Mamba layer's
input. The mixer's SSD is computed here in its quadratic form over the
whole sequence (every pair ``s <= t`` at once), not in chunks. Tied head.

The trained tree is the LoRA adapters (rank ``lora_rank``, scale
``lora_alpha / lora_rank``) on every linear projection: the mixers'
``in_proj`` and ``out_proj``, the shared blocks' ``q``, ``k``, ``v``,
``o``, ``gate_up`` and ``down``, and each invocation's ``linear``. The
base (``data["base"]``, made in ``data.py``) is read as stored, bfloat16,
and cast to the computing dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import family

shapes = family.load("zamba2", "data").shapes

INIT_STREAM = 0x4C6F52   # the benchmark's own adapter stream
BLOCK = 4                # clients per block of the reference's step


# ------------------------------------------------------------ weights ----
def adapter_shapes(model: dict) -> dict:
    """``{path: (lead, d_in, d_out)}`` of every adapted projection, nested
    as the base is: ``blocks`` and ``hybrid`` lists, ``layers`` stacked."""
    s = shapes(model)
    q, kv = s["heads"] * s["hd"], s["kv"] * s["hd"]
    block = {"q": (s["d2"], q), "k": (s["d2"], kv), "v": (s["d2"], kv),
             "o": (q, s["d"]), "gate_up": (s["d"], 2 * s["F"]),
             "down": (s["F"], s["d"])}
    return {"blocks": [dict(block) for _ in range(s["M"])],
            "hybrid": [{"linear": (s["d"], s["d"])}
                       for _ in range(s["n_hybrid"])],
            "layers": {"mamba": {"in_proj": (s["L"], s["d"], s["proj"]),
                                 "out_proj": (s["L"], s["d_inner"], s["d"])}}}


def init_params(model: dict, seed: int):
    """The LoRA adapters from the seed, float32, in one jitted call: ``A``
    uniform on [-1/sqrt(d_in), 1/sqrt(d_in)] and ``B`` zero."""
    r = model["lora_rank"]
    spec = adapter_shapes(model)
    leaves, tree = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(key):
        out = []
        for k, shape in zip(jax.random.split(key, len(leaves)), leaves):
            *lead, d_in, d_out = shape
            bound = 1.0 / math.sqrt(d_in)
            out.append({"a": jax.random.uniform(k, (*lead, d_in, r),
                                                jnp.float32, -bound, bound),
                        "b": jnp.zeros((*lead, r, d_out), jnp.float32)})
        return jax.tree_util.tree_unflatten(tree, out)

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), INIT_STREAM))


# -------------------------------------------------------------- model ----
class Model:
    """Forward of one configuration at one dtype and matmul precision."""

    def __init__(self, model: dict, dtype, precision):
        self.s, self.dtype, self.precision = shapes(model), dtype, precision
        self.types = model["layers_block_type"]
        self.eps = model["rms_norm_eps"]
        self.theta = model["rope_theta"]
        self.dt_min = model["time_step_min"]
        self.scale = model["lora_alpha"] / model["lora_rank"]

    def mm(self, a, b):
        return jnp.matmul(a, b, precision=self.precision)

    def w(self, v):
        return v.astype(self.dtype)

    def lin(self, module, x, adapter):
        y = self.mm(x, self.w(module["w"]))
        if adapter is not None:
            y = y + self.scale * self.mm(self.mm(x, self.w(adapter["a"])),
                                         self.w(adapter["b"]))
        return y

    def norm(self, params, x):
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                + self.eps)
        return (xf * params["scale"].astype(jnp.float32)).astype(x.dtype)

    def mixer(self, p, ad, x):
        """Mamba2 with grouped B/C, quadratic SSD. x [B, T, d]."""
        s = self.s
        b, t = x.shape[:2]
        zxbcdt = self.lin(p["in_proj"], x, ad["in_proj"])
        di, gn = s["d_inner"], s["G"] * s["N"]
        z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * gn],
                      zxbcdt[..., 2 * di + 2 * gn:])
        k = s["K"]
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + t] * self.w(p["conv_w"][i]) for i in range(k))
        xbc = jax.nn.silu(conv + self.w(p["conv_b"]))
        xs, bm, cm = xbc[..., :di], xbc[..., di:di + gn], xbc[..., di + gn:]
        h, n, pdim, g = s["H"], s["N"], s["P"], s["G"]
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        dt = jnp.maximum(dt, self.dt_min)                          # [b,t,h]
        la = dt * -jnp.exp(p["A_log"].astype(jnp.float32))
        xh = xs.reshape(b, t, h, pdim).astype(jnp.float32)
        bh = jnp.repeat(bm.reshape(b, t, g, n), h // g, axis=2)
        ch = jnp.repeat(cm.reshape(b, t, g, n), h // g, axis=2)
        # the log-decay from s to t, sum of la over (s, t], as a difference
        # of its running sum over the whole sequence; nothing above the
        # diagonal
        run = jnp.cumsum(jnp.moveaxis(la, 1, 2), axis=-1)           # [b,h,t]
        seg = run[..., :, None] - run[..., None, :]
        decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg,
                                  -jnp.inf))                       # [b,h,t,s]
        cb = jnp.einsum("bthn,bshn->bhts", ch.astype(jnp.float32),
                        bh.astype(jnp.float32), precision=self.precision)
        wgt = cb * decay * jnp.moveaxis(dt, 1, 2)[:, :, None, :]
        y = jnp.einsum("bhts,bshp->bthp", wgt, xh, precision=self.precision)
        y = y + p["D"].astype(jnp.float32)[:, None] * xh
        y = y.reshape(b, t, di) * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(b, t, g, di // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                + self.eps)
        y = (yg.reshape(b, t, di)
             * p["norm"]["scale"].astype(jnp.float32)).astype(self.dtype)
        return self.lin(p["out_proj"], y, ad["out_proj"])

    def rope(self, x):
        """x [b, t, heads, hd], rotate-half convention."""
        hd, t = x.shape[-1], x.shape[1]
        inv = 1.0 / (self.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                    / hd))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
        cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
        sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
        xf = x.astype(jnp.float32)
        rot = jnp.concatenate([-xf[..., hd // 2:], xf[..., :hd // 2]], -1)
        return (xf * cos + rot * sin).astype(x.dtype)

    def shared(self, blk, ad_blk, hyb, ad_hyb, h, emb):
        s = self.s
        b, t = h.shape[:2]
        x = self.norm(blk["attn_norm"], jnp.concatenate([h, emb], -1))
        q = self.rope(self.lin(blk["q"], x, ad_blk["q"]).reshape(
            b, t, s["heads"], s["hd"]))
        k = self.rope(self.lin(blk["k"], x, ad_blk["k"]).reshape(
            b, t, s["kv"], s["hd"]))
        v = self.lin(blk["v"], x, ad_blk["v"]).reshape(b, t, s["kv"], s["hd"])
        rep = s["heads"] // s["kv"]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=self.precision
                        ).astype(jnp.float32) * (s["hd"] / 2) ** -0.5
        sc = jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(self.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=self.precision)
        o = self.lin(blk["o"], o.reshape(b, t, -1), ad_blk["o"])
        x = self.norm(blk["mlp_norm"], o)
        gu = self.lin(blk["gate_up"], x, ad_blk["gate_up"]) + self.mm(
            self.mm(x, self.w(hyb["adapter_a"]["w"])),
            self.w(hyb["adapter_b"]["w"]))
        gate, up = gu[..., :s["F"]], gu[..., s["F"]:]
        y = self.lin(blk["down"],
                     jax.nn.gelu(gate, approximate=False) * up,
                     ad_blk["down"])
        return self.lin(hyb["linear"], y, ad_hyb["linear"])

    def logits(self, ad, base, tokens):
        """tokens [b, t] -> logits [b, t, V] float32. Each layer is
        rematerialised in the backward pass."""
        emb = self.w(base["embed"]["table"])[tokens]
        h, j = emb, 0
        for i, kind in enumerate(self.types):
            layer = jax.tree_util.tree_map(lambda v: v[i], base["layers"])
            lad = jax.tree_util.tree_map(lambda v: v[i],
                                         ad["layers"]["mamba"])
            if kind == "hybrid":
                m = j % self.s["M"]

                def step(h, layer, lad, blk, ad_blk, hyb, ad_hyb):
                    x = h + self.shared(blk, ad_blk, hyb, ad_hyb, h, emb)
                    return h + self.mixer(layer["mamba"], lad,
                                          self.norm(layer["ln"], x))
                h = jax.checkpoint(step)(h, layer, lad, base["blocks"][m],
                                         ad["blocks"][m], base["hybrid"][j],
                                         ad["hybrid"][j])
                j += 1
            else:
                def step(h, layer, lad):
                    return h + self.mixer(layer["mamba"], lad,
                                          self.norm(layer["ln"], h))
                h = jax.checkpoint(step)(h, layer, lad)
        x = self.norm(base["final_norm"], h)
        return self.mm(x, self.w(base["embed"]["table"]).T).astype(
            jnp.float32)

    def loss(self, ad, base, tokens):
        """Mean next-token cross-entropy of tokens [b, T + 1]."""
        logp = jax.nn.log_softmax(self.logits(ad, base, tokens[:, :-1]), -1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


# ------------------------------------------------------------- rounds ----
def minibatch(data: dict, rows):
    """The clients' minibatches for row indices ``rows`` [N, S, B], with
    the base, unbatched, and the model's settings beside them (the
    client step is handed nothing else)."""
    return dict(tokens=data["tokens"][rows], base=data["base"],
                model=data["model"])


def client_step(lr: float, dtype, precision):
    """Local SGD of every client on its adapters, ``BLOCK`` clients at a
    time: (adapters, minibatch) -> (flat updates [N, D] float32 in
    sorted-key leaf order, last-step losses [N])."""
    compiled = {}

    def step(m, ad0, tokens, base):
        def one(toks):
            p = ad0
            for s in range(toks.shape[0]):
                ls, g = jax.value_and_grad(m.loss)(p, base, toks[s])
                p = jax.tree_util.tree_map(
                    lambda a, b: a - jnp.asarray(lr, dtype) * b.astype(dtype),
                    p, g)
            d = jax.tree_util.tree_map(lambda a, b: a - b, p, ad0)
            return jnp.concatenate([v.astype(jnp.float32).reshape(-1)
                                    for v in jax.tree_util.tree_leaves(d)]), ls

        n = tokens.shape[0]
        blk = max(b for b in range(1, min(BLOCK, n) + 1) if n % b == 0)
        upd, ls = jax.lax.map(jax.vmap(one), tokens.reshape(
            (n // blk, blk) + tokens.shape[1:]))
        return upd.reshape(n, -1), ls.reshape(n)

    def call(ad0, batch):
        if "step" not in compiled:
            m = Model(batch["model"], dtype, precision)
            compiled["step"] = jax.jit(lambda *a: step(m, *a))
        return compiled["step"](ad0, batch["tokens"], batch["base"])

    return call


def accuracy(data: dict, dtype, precision):
    """adapters -> next-token accuracy over the held-out sequences."""
    m = Model(data["model"], dtype, precision)

    @jax.jit
    def acc(ad, base, tokens):
        pred = jnp.argmax(m.logits(ad, base, tokens[:, :-1]), -1)
        return jnp.mean((pred == tokens[:, 1:]).astype(jnp.float32))

    test = jnp.asarray(data["test_tokens"])
    return lambda ad: acc(ad, data["base"], test)
