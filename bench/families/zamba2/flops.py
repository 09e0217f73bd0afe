"""The Zamba2 family's model FLOPs, from the configuration's shapes.

A multiply-add counts two FLOPs. Training one sequence of ``seq_len``
tokens costs the forward pass; the input gradients of every matrix
product (through the frozen base too: the adapters sit inside it), but
not of the embedding lookup; and the weight gradients of the LoRA
adapters alone, the base being frozen. The recomputation of each layer
in the backward pass (rematerialisation) is not counted: it is not work
the model needs. The SSD is counted in its chunked form at the
configuration's ``chunk_size``, attention over the whole causal square.
Norms, activations, the conv and the softmax do no matrix work and are
not counted.
"""
from __future__ import annotations

import family

shapes = family.load("zamba2", "data").shapes


def macs_per_token(model: dict, seq_len: int) -> dict:
    """Multiply-adds per token of the forward pass, by kind:
    ``frozen`` (products with a base weight), ``lora`` (the adapters'
    two thin products) and ``activations`` (products of two activations:
    the SSD and attention's scores and mixing)."""
    s = shapes(model)
    r = model["lora_rank"]
    q = min(model["chunk_size"], seq_len)
    hq = s["heads"] * s["hd"]
    mamba_proj = [(s["d"], s["proj"]), (s["d_inner"], s["d"])]
    block_proj = [(s["d2"], hq), (s["d2"], s["kv"] * s["hd"]),
                  (s["d2"], s["kv"] * s["hd"]), (hq, s["d"]),
                  (s["d"], 2 * s["F"]), (s["F"], s["d"])]
    n_m, n_h = s["L"], s["n_hybrid"]
    frozen = (n_m * sum(i * o for i, o in mamba_proj)
              + n_h * (sum(i * o for i, o in block_proj)
                       + s["d"] * s["R"] + s["R"] * 2 * s["F"]   # its adapter
                       + s["d"] * s["d"])                        # linear
              + s["d"] * s["V"])                                 # tied head
    lora = r * (n_m * sum(i + o for i, o in mamba_proj)
                + n_h * (sum(i + o for i, o in block_proj) + 2 * s["d"]))
    ssd = (q * s["G"] * s["N"] + q * s["H"] * s["P"]       # C.B, M x
           + 2 * s["H"] * s["N"] * s["P"])                 # C S, state
    attn = 2 * seq_len * hq                                # scores, mixing
    return dict(frozen=frozen, lora=lora, activations=n_m * ssd + n_h * attn)


def forward_flops(model: dict, seq_len: int) -> int:
    return 2 * seq_len * sum(macs_per_token(model, seq_len).values())


def train_flops(model: dict) -> int:
    """One training sequence: forward; input gradients of every product;
    weight gradients of the adapters; both operands' gradients of the
    activation products."""
    t = model["seq_len"]
    m = macs_per_token(model, t)
    return 2 * t * (2 * m["frozen"] + 3 * m["lora"] + 3 * m["activations"])


def eval_flops(config: dict) -> int:
    """One evaluation: the forward pass over the held-out sequences."""
    return config["data"]["n_test"] * forward_flops(config["model"],
                                                    config["model"]["seq_len"])
