"""The Zamba2 family's inputs to the program under test, built through
its public entry points: Zamba2 of ``repro.models.transformer`` with
LoRA adapters (``repro.models.lora``) over the frozen base, which the
trainer holds as ``frozen_params`` and hands the loss and the eval
beside the adapters (``repro.fl.client.WithFrozen``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.models import transformer as tfm


def model_config(config: dict) -> ModelConfig:
    """The program's configuration of the model the file states."""
    m = config["model"]
    return ModelConfig(
        name=config["name"], family="hybrid",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m["attention_head_dim"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], tie_embeddings=True,
        ssm_state=m["mamba_d_state"], ssm_conv=m["mamba_d_conv"],
        ssm_expand=m["mamba_expand"], ssm_head_dim=m["mamba_headdim"],
        ssm_chunk=m["chunk_size"], ssm_groups=m["mamba_ngroups"],
        ssm_dt_min=m["time_step_min"],
        layers_block_type=tuple(m["layers_block_type"]),
        num_mem_blocks=m["num_mem_blocks"], adapter_rank=m["adapter_rank"],
        dtype="float32")


def trainer_inputs(config: dict, traffic: dict, data: dict, params0) -> dict:
    """``FederatedTrainer``'s model arguments: ``model_loss``,
    ``model_params`` (the adapters), ``client_datasets``, ``eval_fn``,
    and ``frozen_params`` (the base)."""
    m = config["model"]
    mcfg = model_config(config)
    scale = m["lora_alpha"] / m["lora_rank"]
    test = jnp.asarray(data["test_tokens"])

    def model_loss(p, batch):
        return tfm.zamba2_loss(p.frozen, batch, mcfg, p.trained, scale)

    @jax.jit
    def eval_fn(p):
        logits = tfm.zamba2_forward(p.frozen, test[:, :-1], mcfg, p.trained,
                                    scale)
        return jnp.mean((jnp.argmax(logits, -1) == test[:, 1:])
                        .astype(jnp.float32))

    clients = [dict(tokens=data["tokens"][q]) for q in data["parts"]]
    return dict(model_loss=model_loss, model_params=params0,
                client_datasets=clients, eval_fn=eval_fn,
                frozen_params=data["base"])
