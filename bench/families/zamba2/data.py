"""The Zamba2 family's workload data: token streams over topics, and the
frozen base, made from the seed.

Each of ``n_topics`` topics is a Zipf(``zipf_a``) unigram over its own
permutation of the vocabulary. The fleet is the configuration's and is
drawn from its ``fleet_seed``: the topic permutations, each training
sequence's topic, their Dirichlet partition over the clients and the
held-out sequences. ``seed`` draws the training tokens and the base.

The base is the whole model but its LoRA adapters: the model's weights
at their published shapes, drawn on the device in one jitted call and
stored in bfloat16, as a frozen base is held. It is an entry of the data
(``base``) because the reference's model half sees only what
``make_data`` returns: ``minibatch`` hands it on beside the tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from data import dirichlet_partition

BASE_STREAM = 0x7A3B42   # the benchmark's own base-weight stream
STD = 0.02               # the source's initializer_range


def shapes(model: dict) -> dict:
    """Widths of the configuration, by name."""
    d, heads, hd = model["hidden_size"], model["num_attention_heads"], \
        model["attention_head_dim"]
    d_inner = model["mamba_expand"] * d
    g, n = model["mamba_ngroups"], model["mamba_d_state"]
    h = model["n_mamba_heads"]
    types = model["layers_block_type"]
    return dict(d=d, d2=2 * d, heads=heads, kv=model["num_key_value_heads"],
                hd=hd, F=model["intermediate_size"], V=model["vocab_size"],
                d_inner=d_inner, G=g, N=n, H=h, P=model["mamba_headdim"],
                K=model["mamba_d_conv"], conv=d_inner + 2 * g * n,
                proj=2 * d_inner + 2 * g * n + h, L=len(types),
                n_hybrid=types.count("hybrid"),
                M=model["num_mem_blocks"], R=model["adapter_rank"])


def make_base(model: dict, seed: int):
    """The frozen base from the seed, bfloat16 on the device: linear and
    embedding weights N(0, 0.02^2), norm scales 1, the conv as torch's
    default uniform draw, ``A_log = log(1..H)``, ``D = 1`` and ``dt_bias``
    the inverse softplus of a log-uniform step in [time_step_min,
    time_step_max] (the source's initialisation)."""
    s = shapes(model)
    t_min, t_max = model["time_step_min"], model["time_step_max"]
    floor = model["time_step_floor"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 64))

        def normal(*shape):
            return (STD * jax.random.normal(next(keys), shape, jnp.float32)
                    ).astype(jnp.bfloat16)

        def dense(*shape):
            return {"w": normal(*shape)}

        def ones(*shape):
            return {"scale": jnp.ones(shape, jnp.bfloat16)}

        L, H = s["L"], s["H"]
        bound = 1.0 / math.sqrt(s["K"])
        log_dt = jax.random.uniform(next(keys), (L, H), jnp.float32,
                                    math.log(t_min), math.log(t_max))
        dt = jnp.maximum(jnp.exp(log_dt), floor)
        mamba = {
            "in_proj": dense(L, s["d"], s["proj"]),
            "conv_w": jax.random.uniform(next(keys), (L, s["K"], s["conv"]),
                                         jnp.float32, -bound, bound
                                         ).astype(jnp.bfloat16),
            "conv_b": jax.random.uniform(next(keys), (L, s["conv"]),
                                         jnp.float32, -bound, bound
                                         ).astype(jnp.bfloat16),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, H + 1, dtype=
                                                         jnp.float32)),
                                      (L, H)).astype(jnp.bfloat16),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.bfloat16),
            "D": jnp.ones((L, H), jnp.bfloat16),
            "norm": ones(L, s["d_inner"]),
            "out_proj": dense(L, s["d_inner"], s["d"]),
        }
        blocks = [{"attn_norm": ones(s["d2"]),
                   "q": dense(s["d2"], s["heads"] * s["hd"]),
                   "k": dense(s["d2"], s["kv"] * s["hd"]),
                   "v": dense(s["d2"], s["kv"] * s["hd"]),
                   "o": dense(s["heads"] * s["hd"], s["d"]),
                   "mlp_norm": ones(s["d"]),
                   "gate_up": dense(s["d"], 2 * s["F"]),
                   "down": dense(s["F"], s["d"])} for _ in range(s["M"])]
        hybrid = [{"adapter_a": dense(s["d"], s["R"]),
                   "adapter_b": dense(s["R"], 2 * s["F"]),
                   "linear": dense(s["d"], s["d"])}
                  for _ in range(s["n_hybrid"])]
        return {"embed": {"table": normal(s["V"], s["d"])},
                "final_norm": ones(s["d"]),
                "layers": {"ln": ones(L, s["d"]), "mamba": mamba},
                "blocks": blocks, "hybrid": hybrid}

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), BASE_STREAM))


def _unigrams(n_topics: int, vocab: int, a: float, rng: np.random.Generator):
    """Per topic, the vocabulary ids in rank order and the Zipf CDF over
    the ranks."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(w / w.sum())
    return np.stack([rng.permutation(vocab) for _ in range(n_topics)]), cdf


def _sequences(topics, perms, cdf, rng, length):
    ranks = np.searchsorted(cdf, rng.random((len(topics), length)))
    ranks = np.minimum(ranks, len(cdf) - 1)
    return perms[topics[:, None], ranks].astype(np.int32)


def make_data(config: dict, seed: int) -> dict:
    """``tokens`` [n_train, T + 1] and ``test_tokens`` [n_test, T + 1]
    int32, ``parts`` (each client's rows), ``base``, and ``model`` (the
    configuration's model settings, which the reference's client step
    reads from its minibatch)."""
    m, d, fleet = config["model"], config["data"], config["fleet_seed"]
    length = d["seq_len"] + 1
    perms, cdf = _unigrams(d["n_topics"], m["vocab_size"], d["zipf_a"],
                           np.random.default_rng([fleet, 0]))
    topics = np.random.default_rng([fleet, 1]).integers(
        0, d["n_topics"], d["n_train"])
    parts = dirichlet_partition(topics, config["n_clients"],
                                d["dirichlet_beta"],
                                np.random.default_rng([fleet, 2]),
                                d["min_client_size"])
    tokens = _sequences(topics, perms, cdf, np.random.default_rng([seed, 0]),
                        length)
    test_rng = np.random.default_rng([fleet, 3])
    test = _sequences(test_rng.integers(0, d["n_topics"], d["n_test"]), perms,
                      cdf, test_rng, length)
    return dict(tokens=tokens, test_tokens=test, parts=parts,
                base=make_base(m, seed), model=m)
