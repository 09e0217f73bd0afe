"""Zamba2 against its public implementation: ``transformers``'
``Zamba2ForCausalLM`` at a tiny size, its random weights copied into the
repo's tree. The program's ``zamba2_forward`` and the benchmark family's
plain reference (``bench/families/zamba2/reference.py``, loaded by path)
must both give its logits, in float32 at ``highest`` precision; LoRA
adapters with ``B = 0`` must leave the base's logits as they are, bit for
bit.

The public model runs its SSD as one chunk over the whole sequence (its
quadratic form), while the program keeps its own chunks of 16: the
public multi-chunk path departs from its own one-chunk result by about
1e-5 even in float64, where the program's chunks agree with it to 1e-7
(measured on this case)."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import lora, transformer as tfm

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
TYPES = ["mamba"] * 6 + ["hybrid"] + ["mamba"] * 4 + ["hybrid"]
HF = dict(vocab_size=128, hidden_size=64, num_hidden_layers=12,
          layers_block_type=TYPES, mamba_d_state=16, mamba_d_conv=4,
          mamba_expand=2, mamba_ngroups=2, n_mamba_heads=8, chunk_size=32,
          intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
          num_mem_blocks=2, use_shared_attention_adapter=False,
          use_shared_mlp_adapter=True, adapter_rank=8, use_mem_rope=True,
          rope_theta=10000, rms_norm_eps=1e-5, time_step_min=0.001,
          time_step_max=0.1, time_step_floor=1e-4, hidden_act="gelu",
          max_position_embeddings=64)
SEQ = 32
REL = 1e-5


def _hf_model():
    cfg = transformers.Zamba2Config(**HF)
    cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.Zamba2ForCausalLM(cfg).eval()
    with torch.no_grad():   # weights large enough that every path matters
        for name, p in model.named_parameters():
            if p.ndim == 2:
                p.normal_(0.0, 0.2 if "embed" in name else 0.08)
            elif name.endswith(("norm.weight", "layernorm.weight")):
                p.uniform_(0.5, 1.5)
    return model


def _repo_config(hf) -> ModelConfig:
    c = hf.config
    return ModelConfig(
        name="zamba2-tiny", family="hybrid", n_layers=c.num_hidden_layers,
        d_model=c.hidden_size, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads, head_dim=c.attention_head_dim,
        d_ff=c.intermediate_size, vocab_size=c.vocab_size,
        rope_theta=c.rope_theta, norm_eps=c.rms_norm_eps, tie_embeddings=True,
        ssm_state=c.mamba_d_state, ssm_conv=c.mamba_d_conv,
        ssm_expand=c.mamba_expand, ssm_head_dim=c.mamba_headdim,
        ssm_chunk=16, ssm_groups=c.mamba_ngroups,
        ssm_dt_min=c.time_step_min, layers_block_type=tuple(TYPES),
        num_mem_blocks=c.num_mem_blocks, adapter_rank=c.adapter_rank,
        dtype="float32")


def _repo_tree(hf) -> dict:
    """The HF weights in the tree ``zamba2_init`` makes."""
    t = lambda p: jnp.asarray(p.detach().numpy().T)          # noqa: E731
    v = lambda p: jnp.asarray(p.detach().numpy())            # noqa: E731
    m = hf.model
    decoders, blocks, hybrid = [], {}, []
    for layer in m.layers:
        if hasattr(layer, "shared_transformer"):
            blk = layer.shared_transformer
            blocks.setdefault(blk.block_id, blk)
            ad = blk.feed_forward.gate_up_proj_adapter_list[len(hybrid)]
            hybrid.append({"adapter_a": {"w": t(ad[0].weight)},
                           "adapter_b": {"w": t(ad[1].weight)},
                           "linear": {"w": t(layer.linear.weight)}})
            layer = layer.mamba_decoder
        mx = layer.mamba
        decoders.append({"ln": {"scale": v(layer.input_layernorm.weight)},
                         "mamba": {
                             "in_proj": {"w": t(mx.in_proj.weight)},
                             "conv_w": t(mx.conv1d.weight[:, 0, :]),
                             "conv_b": v(mx.conv1d.bias),
                             "A_log": v(mx.A_log), "dt_bias": v(mx.dt_bias),
                             "D": v(mx.D), "norm": {"scale": v(mx.norm.weight)},
                             "out_proj": {"w": t(mx.out_proj.weight)}}})
    stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *decoders)

    def block(b):
        a, f = b.self_attn, b.feed_forward
        return {"attn_norm": {"scale": v(b.input_layernorm.weight)},
                "q": {"w": t(a.q_proj.weight)}, "k": {"w": t(a.k_proj.weight)},
                "v": {"w": t(a.v_proj.weight)}, "o": {"w": t(a.o_proj.weight)},
                "mlp_norm": {"scale": v(b.pre_ff_layernorm.weight)},
                "gate_up": {"w": t(f.gate_up_proj.weight)},
                "down": {"w": t(f.down_proj.weight)}}

    return {"embed": {"table": v(m.embed_tokens.weight)},
            "final_norm": {"scale": v(m.final_layernorm.weight)},
            "layers": stack, "blocks": [block(blocks[k]) for k in sorted(blocks)],
            "hybrid": hybrid}


@pytest.fixture(scope="module")
def case():
    hf = _hf_model()
    tokens = np.random.default_rng(0).integers(0, HF["vocab_size"], (2, SEQ))
    with torch.no_grad():
        want = hf(torch.as_tensor(tokens)).logits.numpy()
    return hf, _repo_config(hf), _repo_tree(hf), jnp.asarray(tokens), want


def _rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _family_reference():
    sys.path.insert(0, os.path.abspath(BENCH))
    spec = importlib.util.spec_from_file_location(
        "zamba2_family_reference",
        os.path.join(BENCH, "families", "zamba2", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _family_model(hf, lora_rank=4):
    c = hf.config
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads,
        attention_head_dim=c.attention_head_dim, mamba_expand=c.mamba_expand,
        mamba_ngroups=c.mamba_ngroups, mamba_d_state=c.mamba_d_state,
        n_mamba_heads=c.n_mamba_heads, mamba_headdim=c.mamba_headdim,
        mamba_d_conv=c.mamba_d_conv, intermediate_size=c.intermediate_size,
        vocab_size=c.vocab_size, layers_block_type=TYPES,
        num_mem_blocks=c.num_mem_blocks, adapter_rank=c.adapter_rank,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        time_step_min=c.time_step_min, chunk_size=c.chunk_size,
        lora_rank=lora_rank, lora_alpha=2 * lora_rank, seq_len=SEQ)


@pytest.mark.parametrize("side", ["program", "family_reference"])
def test_logits_match_transformers(case, side):
    hf, cfg, base, tokens, want = case
    with jax.default_matmul_precision("highest"):
        if side == "program":
            got = tfm.zamba2_forward(base, tokens, cfg)
        else:
            ref = _family_reference()
            model = _family_model(hf)
            ad = ref.init_params(model, 0)
            got = ref.Model(model, jnp.float32, jax.lax.Precision.HIGHEST
                            ).logits(ad, base, tokens)
    assert got.shape == want.shape
    assert _rel(got, want) <= REL


def test_lora_with_zero_b_leaves_the_base_bit_for_bit(case):
    _, cfg, base, tokens, _ = case
    adapters = lora.init(jax.random.PRNGKey(1), base,
                         tfm.ZAMBA2_LORA_TARGETS, rank=4)
    pairs = jax.tree_util.tree_leaves(
        adapters, is_leaf=lambda d: isinstance(d, dict) and "a" in d)
    assert all(float(jnp.max(jnp.abs(d["a"]))) > 0
               and not np.any(np.asarray(d["b"])) for d in pairs)
    with jax.default_matmul_precision("highest"):
        plain = tfm.zamba2_forward(base, tokens, cfg)
        adapted = tfm.zamba2_forward(base, tokens, cfg, adapters, 2.0)
    np.testing.assert_array_equal(np.asarray(adapted), np.asarray(plain))


def test_adapters_cover_every_projection_and_count(case):
    """One adapter per linear projection: the mixers' in/out, the shared
    blocks' q, k, v, o, gate_up, down and each invocation's linear. At
    the published widths, rank 32 over the 12-layer cut is 18,262,016
    parameters."""
    _, cfg, base, _, _ = case
    adapters = lora.init(jax.random.PRNGKey(1), base,
                         tfm.ZAMBA2_LORA_TARGETS, rank=4)
    assert set(adapters) == {"layers", "blocks", "hybrid"}
    assert set(adapters["layers"]["mamba"]) == {"in_proj", "out_proj"}
    assert all(set(b) == {"q", "k", "v", "o", "gate_up", "down"}
               for b in adapters["blocks"])
    assert all(set(h) == {"linear"} for h in adapters["hybrid"])
    from repro.configs import get_config
    full = get_config("zamba2-7b").replace(
        n_layers=12, layers_block_type=tuple(TYPES))
    shapes = jax.eval_shape(lambda: tfm.zamba2_init(jax.random.PRNGKey(0),
                                                    full))
    ad = jax.eval_shape(lambda: lora.init(jax.random.PRNGKey(0), shapes,
                                          tfm.ZAMBA2_LORA_TARGETS, 32))
    assert sum(int(np.prod(v.shape))
               for v in jax.tree_util.tree_leaves(ad)) == 18_262_016
