"""Per-architecture smoke tests: REDUCED variant of each assigned family,
one forward/train step on CPU, output shapes + no NaNs (assignment req)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.launch import steps as steps_mod
from repro.models import cnn, encdec, transformer as tfm
from repro.optim import adamw_init

LM_ARCHS = [a for a in ARCH_IDS if a != "whisper-tiny"]


def _batch_for(cfg, B=2, S=64):
    if cfg.family == "audio":
        return {"frames": jax.random.normal(jax.random.PRNGKey(1),
                                            (B, cfg.n_audio_frames, cfg.d_model)),
                "tokens": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                             cfg.vocab_size)}
    b = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        b["extra_embeds"] = jax.random.normal(jax.random.PRNGKey(3),
                                              (B, cfg.n_vision_tokens, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_smoke(arch)
    assert cfg.n_layers <= 4 and cfg.d_model <= 512 and cfg.n_experts <= 4
    params = steps_mod.init_for(cfg)(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    loss, metrics = steps_mod.loss_for(cfg)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    params = steps_mod.init_for(cfg)(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(steps_mod.build_train_step(cfg, lr=1e-3))
    batch = _batch_for(cfg)
    p2, opt2, loss1 = step(params, opt, batch)
    p3, _, loss2 = step(p2, opt2, batch)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss1), f"{arch}: {loss1} -> {loss2}"
    # params actually changed
    changed = any(not np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree_util.tree_leaves(params),
                                  jax.tree_util.tree_leaves(p2)))
    assert changed


def test_smoke_logit_shapes():
    cfg = get_smoke("tinyllama-1.1b")
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.ones((2, 32), jnp.int32)
    logits, aux = tfm.lm_forward(params, toks, cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)


def test_cnn_param_count_near_paper():
    """Full FMNIST CNN should be ~2M params (paper Sec. VII)."""
    from repro.configs.fmnist_cnn import CONFIG
    from repro.models.module import param_count
    p = cnn.init_cnn(jax.random.PRNGKey(0), CONFIG)
    n = param_count(p)
    assert 1.2e6 < n < 3e6, n


def test_full_config_shapes_match_assignment():
    """The FULL configs carry the exact published hyper-parameters."""
    from repro.configs import get_config
    spec = {
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 151936),
        "tinyllama-1.1b": (22, 2048, 32, 4, 32000),
        "whisper-tiny": (4, 384, 6, 6, 51865),
        "rwkv6-1.6b": (24, 2048, 0, 0, 65536),
        "zamba2-2.7b": (54, 2560, 32, 32, 32000),
        "zamba2-7b": (81, 3584, 32, 32, 32000),
        "mixtral-8x22b": (56, 6144, 48, 8, 32768),
        "qwen2.5-32b": (64, 5120, 40, 8, 152064),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 32064),
        "glm4-9b": (40, 4096, 32, 2, 151552),
        "qwen2-72b": (80, 8192, 64, 8, 152064),
    }
    for arch, (L, d, H, KV, V) in spec.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == d, arch
        assert cfg.n_heads == H, arch
        assert cfg.n_kv_heads == KV, arch
        assert cfg.vocab_size == V, arch
    assert get_config("qwen2-moe-a2.7b").n_experts == 60
    assert get_config("qwen2-moe-a2.7b").n_experts_per_tok == 4
    assert get_config("qwen2-moe-a2.7b").n_shared_experts == 4
    assert get_config("mixtral-8x22b").n_experts == 8
    assert get_config("mixtral-8x22b").sliding_window == 4096
    assert get_config("zamba2-2.7b").ssm_state == 64
    assert get_config("zamba2-2.7b").attn_every == 6
