"""LoRA adapters over named leaves of a frozen base (Hu et al., 2021,
arXiv:2106.09685).

An adapted projection keeps its base weight ``W [..., d_in, d_out]`` and
adds a rank-``r`` path: ``y = x W + (alpha / r) (x A) B`` with
``A [..., d_in, r]`` drawn from the seed and ``B [..., r, d_out]`` zero,
so a fresh adapter leaves the base's output unchanged. Leading axes of
``W`` (stacked layers) are kept on ``A`` and ``B``.

The adapters are applied unmerged: merging ``W + (alpha / r) A B`` per
client under a client ``vmap`` would build one copy of every adapted
weight per client, where the unmerged form shares ``W`` and adds two
thin matmuls.
"""
from __future__ import annotations

import itertools
import math
from typing import Collection, Optional

import jax
import jax.numpy as jnp

from .module import Params, dense


def init(key, base, names: Collection[str], rank: int) -> Params:
    """Adapters ``{"a", "b"}`` for every dense module of ``base`` (a
    ``{"w": ...}`` node) whose key is in ``names``, nested as in
    ``base`` with every other branch left out (lists keep their length):
    ``A`` uniform on ``[-1/sqrt(d_in), 1/sqrt(d_in)]`` (the bound of
    PEFT's default Kaiming-uniform draw), ``B`` zero, float32."""
    count = itertools.count()

    def pair(w):
        *lead, d_in, d_out = w.shape
        bound = 1.0 / math.sqrt(d_in)
        k = jax.random.fold_in(key, next(count))
        return {"a": jax.random.uniform(k, (*lead, d_in, rank), jnp.float32,
                                        -bound, bound),
                "b": jnp.zeros((*lead, rank, d_out), jnp.float32)}

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k in sorted(node):
                sub = pair(node[k]["w"]) if k in names else walk(node[k])
                if sub:
                    out[k] = sub
            return out
        if isinstance(node, (list, tuple)):
            return [walk(v) for v in node]
        return None

    return walk(base)


def linear(module: Params, x, adapter: Optional[Params] = None,
           scale: float = 1.0):
    """The dense module's ``x W`` in the dtype of ``x``, plus
    ``scale (x A) B`` where an adapter is given."""
    y = dense(module, x)
    if adapter is not None:
        y = y + scale * ((x @ adapter["a"].astype(x.dtype))
                         @ adapter["b"].astype(x.dtype))
    return y
