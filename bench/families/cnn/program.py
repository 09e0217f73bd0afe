"""The CNN family's inputs to the program under test, built through its
public entry points: the paper's CNN of ``repro.models.cnn``, the
clients' image shards and an eval of the test set."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.models import cnn


def trainer_inputs(config: dict, traffic: dict, data: dict, params0) -> dict:
    """``FederatedTrainer``'s model arguments: ``model_loss``,
    ``model_params``, ``client_datasets`` and ``eval_fn``."""
    m = config["model"]
    mcfg = ModelConfig(name=config["name"], family="cnn",
                       n_layers=len(m["cnn_channels"]), d_model=0,
                       cnn_channels=tuple(m["cnn_channels"]),
                       cnn_dense=m["cnn_dense"],
                       input_hw=tuple(m["input_hw"]),
                       n_classes=m["n_classes"], dtype=m["dtype"])
    clients = [dict(images=data["images"][p], labels=data["labels"][p])
               for p in data["parts"]]
    test_x = jnp.asarray(data["test_images"])
    test_y = jnp.asarray(data["test_labels"])

    @jax.jit
    def eval_fn(p):
        logits = cnn.cnn_forward(p, test_x, mcfg)
        return jnp.mean((jnp.argmax(logits, -1) == test_y)
                        .astype(jnp.float32))

    return dict(model_loss=lambda p, b: cnn.cnn_loss(p, b, mcfg),
                model_params=params0, client_datasets=clients,
                eval_fn=eval_fn)
