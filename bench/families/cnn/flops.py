"""The CNN family's model FLOPs, from the configuration's shapes.

A multiply-add counts two FLOPs. Training one sample costs the forward
pass, the weight gradients (as many multiply-adds as the forward) and
the input gradients of every layer but the first. Pooling and
activations are not counted: they do no matrix work.
"""
from __future__ import annotations


def layer_macs(model: dict) -> list:
    """Multiply-adds per sample of each matrix layer, input to output."""
    h, w, c = model["input_hw"]
    macs = []
    for c_out in model["cnn_channels"]:
        macs.append(h * w * 9 * c * c_out)        # 3x3 SAME conv
        h, w, c = h // 2, w // 2, c_out           # 2x2 max pool
    flat = h * w * c
    macs.append(flat * model["cnn_dense"])
    macs.append(model["cnn_dense"] * model["n_classes"])
    return macs


def forward_flops(model: dict) -> int:
    return 2 * sum(layer_macs(model))


def train_flops(model: dict) -> int:
    macs = layer_macs(model)
    return 2 * (2 * sum(macs) + sum(macs[1:]))


def eval_flops(config: dict) -> int:
    """One evaluation: the forward pass over the test set."""
    return config["data"]["n_test"] * forward_flops(config["model"])
