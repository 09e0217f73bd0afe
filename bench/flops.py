"""Model FLOPs of a round, from the configuration's shapes.

The model's own counts come from its family (``bench/families/<family>/
flops.py``): the FLOPs of training one sample and of one evaluation. The
round's non-model stages (decide, sparsify, aggregate) are not counted:
they do no matrix work.
"""
from __future__ import annotations

import family


def round_flops(config: dict, traffic: dict) -> float:
    """FLOPs per round: the training of every client whose update the
    round consumes (all N where the controller scores every update, the
    K selected where it picks blind), and the chunk's one evaluation
    spread over its rounds."""
    fam = family.load(config["model"]["family"], "flops")
    clients = (config["n_clients"] if traffic["consumed_updates"] == "all"
               else traffic["fixed_k"])
    samples = clients * traffic["local_steps"] * traffic["local_batch"]
    return (samples * fam.train_flops(config["model"])
            + fam.eval_flops(config) / traffic["chunk_rounds"])
