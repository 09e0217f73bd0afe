"""jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .. import interpret_mode
from .kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    bq: int = 256, bk: int = 256) -> jnp.ndarray:
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk, interpret=interpret_mode())
