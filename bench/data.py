"""Workload data for the benchmark, made from the seed.

``make`` hands the configuration's data to its model family
(``bench/families/<family>/data.py``). The Dirichlet partition of a
fleet's examples over its clients is shared by the families: a copy of
the one the program keeps in ``repro.data``, so the workload does not
move when the program's does.
"""
from __future__ import annotations

import numpy as np

import family


def dirichlet_partition(labels: np.ndarray, n_clients: int, beta: float,
                        rng: np.random.Generator, min_size: int):
    """Per-client sorted index arrays: each class split over the clients
    by a Dir(beta) draw, redrawn until every client holds ``min_size``."""
    n_classes = int(labels.max()) + 1
    by_class = [np.flatnonzero(labels == c) for c in range(n_classes)]
    for _ in range(100):
        parts = [[] for _ in range(n_clients)]
        for idx in by_class:
            idx = rng.permutation(idx)
            cuts = (np.cumsum(rng.dirichlet([beta] * n_clients))
                    * len(idx)).astype(int)[:-1]
            for i, chunk in enumerate(np.split(idx, cuts)):
                parts[i].append(chunk)
        parts = [np.sort(np.concatenate(p)) for p in parts]
        if min(len(p) for p in parts) >= min_size:
            return parts
    raise RuntimeError("no Dirichlet partition met min_size")


def make(config: dict, seed: int) -> dict:
    """The configuration's data from the seed, by its family's
    ``make_data``: the clients' shards, ``parts`` and the held-out set."""
    return family.load(config["model"]["family"], "data").make_data(
        config, seed)
