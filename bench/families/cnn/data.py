"""The CNN family's workload data: FMNIST-like images, made from the seed.

A vectorised copy of the FMNIST-like generator that the program keeps in
``repro.data``: the same class prototypes (fixed ``proto_seed``),
per-sample shift, scale, confusion blend, pixel noise and label noise,
drawn in bulk rather than one sample at a time, so the workload does not
move when the program's generator does. The same seed gives the same
data (see ``make_data``).
"""
from __future__ import annotations

import numpy as np

from data import dirichlet_partition

PROTO_SEED = 1234
MAX_SHIFT = 2


def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def _prototypes(n_classes: int, hw) -> np.ndarray:
    rng = np.random.default_rng(PROTO_SEED)
    protos = np.stack([_smooth(rng.normal(size=hw), 3)
                       for _ in range(n_classes)])
    protos = ((protos - protos.mean((1, 2), keepdims=True))
              / protos.std((1, 2), keepdims=True))
    # every shifted copy a sample can use: [class, dr, dc, H, W]
    s = range(-MAX_SHIFT, MAX_SHIFT + 1)
    return np.stack([np.stack([np.stack([np.roll(p, (dr, dc), (0, 1))
                                         for dc in s]) for dr in s])
                     for p in protos]).astype(np.float32)


def fmnist_like(labels: np.ndarray, rng: np.random.Generator, *,
                n_classes: int, hw, noise: float, confusion: float,
                label_noise: float):
    """Images [n, H, W, 1] float32 for the given labels, and the labels
    after ``label_noise`` of them are redrawn."""
    n = len(labels)
    shifted = _prototypes(n_classes, tuple(hw))
    dr = rng.integers(0, 2 * MAX_SHIFT + 1, n)
    dc = rng.integers(0, 2 * MAX_SHIFT + 1, n)
    scale = rng.uniform(0.8, 1.2, n).astype(np.float32)[:, None, None]
    img = shifted[labels, dr, dc]
    if confusion > 0:
        other = (labels + rng.integers(1, n_classes, n)) % n_classes
        w = rng.uniform(0.0, confusion, n).astype(np.float32)[:, None, None]
        img = (1 - w) * img + w * shifted[other, dr, dc]
    img = scale * img + np.float32(noise) * rng.standard_normal(
        img.shape, dtype=np.float32)
    labels = labels.astype(np.int32)
    if label_noise > 0:
        flip = rng.random(n) < label_noise
        labels[flip] = rng.integers(0, n_classes, int(flip.sum()))
    return img[..., None].astype(np.float32), labels


def make_data(config: dict, seed: int) -> dict:
    """Train and test sets and the client partition of one configuration.

    The fleet is part of the configuration and is drawn from its
    ``fleet_seed``: the training labels and their Dirichlet partition
    over the clients (so every seed has the same shard sizes), and the
    held-out test set. ``seed`` draws the training images for those
    labels (shift, scale, confusion, noise) and the label noise."""
    d, n_classes = config["data"], config["model"]["n_classes"]
    fleet = config["fleet_seed"]
    kw = dict(n_classes=n_classes, hw=config["model"]["input_hw"][:2],
              noise=d["noise"], confusion=d["confusion"])
    clean = np.random.default_rng([fleet, 0]).integers(
        0, n_classes, d["n_train"])
    parts = dirichlet_partition(clean, config["n_clients"],
                                d["dirichlet_beta"],
                                np.random.default_rng([fleet, 1]),
                                d["min_client_size"])
    images, labels = fmnist_like(clean, np.random.default_rng([seed, 0]),
                                 label_noise=d["label_noise"], **kw)
    test_rng = np.random.default_rng([fleet, 2])
    test_images, test_labels = fmnist_like(
        test_rng.integers(0, n_classes, d["n_test"]), test_rng,
        label_noise=0.0, **kw)
    return dict(images=images, labels=labels, test_images=test_images,
                test_labels=test_labels, parts=parts)
