"""The CNN family's half of the plain reference: the paper's CNN
(conv3x3 -> relu -> maxpool2, twice, then dense -> relu -> dense) in
straightforward code, its weights from the seed, its local SGD and its
evaluation. Independent of the program: nothing here imports ``repro``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

INIT_STREAM = 0x62656E  # the benchmark's own weight stream


def init_params(model: dict, seed: int):
    """The CNN's weights from the seed, in one jitted call on the device:
    conv weights N(0, 1/fan_in), dense weights truncated-normal on
    [-2, 2] over sqrt(fan_in), zero biases. Float32 master copies."""
    h, w, c_in = model["input_hw"]
    chans, dense, n_out = model["cnn_channels"], model["cnn_dense"], \
        model["n_classes"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(chans) + 2)
        p, c_prev, hh, ww = {}, c_in, h, w
        for i, c in enumerate(chans):
            p[f"conv{i}"] = {
                "w": jax.random.normal(keys[i], (3, 3, c_prev, c))
                / jnp.sqrt(9.0 * c_prev),
                "b": jnp.zeros((c,), jnp.float32)}
            c_prev, hh, ww = c, hh // 2, ww // 2
        flat = hh * ww * c_prev
        for name, k, d_in, d_out in (("fc1", keys[-2], flat, dense),
                                     ("fc2", keys[-1], dense, n_out)):
            p[name] = {"w": jax.random.truncated_normal(
                k, -2.0, 2.0, (d_in, d_out)) / math.sqrt(d_in),
                "b": jnp.zeros((d_out,), jnp.float32)}
        return p

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), INIT_STREAM))


def forward(p, x, precision):
    i = 0
    while f"conv{i}" in p:
        y = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"].astype(x.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        y = jax.nn.relu(y + p[f"conv{i}"]["b"].astype(x.dtype))
        x = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        i += 1
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1"]["w"].astype(x.dtype),
                            precision=precision) + p["fc1"]["b"].astype(x.dtype))
    return (jnp.dot(x, p["fc2"]["w"].astype(x.dtype), precision=precision)
            + p["fc2"]["b"].astype(x.dtype))


def loss(p, images, labels, precision):
    logits = forward(p, images, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def minibatch(data: dict, rows):
    """The clients' minibatches for row indices ``rows`` [N, S, B]."""
    return dict(images=data["images"][rows], labels=data["labels"][rows])


def client_step(lr: float, dtype, precision):
    """vmapped local SGD: (params, minibatch [N, S, B, ...]) -> (flat
    updates [N, D] float32, leaves in sorted-key order, last-step losses
    [N])."""
    def one(p0, batch):
        p = p0
        for s in range(batch["images"].shape[0]):
            ls, g = jax.value_and_grad(loss)(
                p, batch["images"][s].astype(dtype), batch["labels"][s],
                precision)
            p = jax.tree_util.tree_map(lambda a, b: a - jnp.asarray(lr, dtype)
                                       * b.astype(dtype), p, g)
        d = jax.tree_util.tree_map(lambda a, b: a - b, p, p0)
        return jnp.concatenate([v.astype(jnp.float32).reshape(-1)
                                for v in jax.tree_util.tree_leaves(d)]), ls

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def accuracy(data: dict, dtype, precision, max_block=2500):
    """params -> accuracy over the test set, in blocks of at most
    ``max_block`` images."""
    @jax.jit
    def acc(p, images, labels):
        block = max(b for b in range(1, min(max_block, images.shape[0]) + 1)
                    if images.shape[0] % b == 0)
        def one(args):
            im, lb = args
            pred = jnp.argmax(forward(p, im.astype(dtype), precision), -1)
            return jnp.sum((pred == lb).astype(jnp.int32))
        n = images.shape[0] // block
        hits = jax.lax.map(one, (images.reshape((n, block) + images.shape[1:]),
                                 labels.reshape(n, block)))
        return jnp.sum(hits) / images.shape[0]

    test_x = jnp.asarray(data["test_images"])
    test_y = jnp.asarray(data["test_labels"])
    return lambda p: acc(p, test_x, test_y)
