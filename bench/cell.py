"""A benchmark cell: the program under test, built from a configuration
and a traffic mix, and driven chunk by chunk as users run it.

Everything here goes through the program's public entry points
(``repro.fl.FederatedTrainer`` and its configs); the data, the weights
and the model's inputs to the trainer come from the configuration's
model family (``family.py``).
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import numpy as np

import data as bench_data
import family
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``, found by name."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def spec(workload: str, benchmark: dict):
    """(cell entry, configuration, traffic mix, limits) of a workload."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    family.directory(config["model"]["family"])
    return cell, config, load("traffic", cell["traffic"]), \
        load("limits", workload)


def inputs(config: dict, seed: int):
    """The data and the initial weights of one seed."""
    return (bench_data.make(config, seed),
            reference.init_params(config["model"], seed))


def trainer_inputs(config: dict, traffic: dict, data: dict, params0) -> dict:
    """The trainer's model arguments, from the configuration's family:
    ``model_loss``, ``model_params``, ``client_datasets``, ``eval_fn``."""
    return family.load(config["model"]["family"], "program").trainer_inputs(
        config, traffic, data, params0)


class Cell:
    """Builds the program's trainer for one seed and runs its chunks."""

    def __init__(self, config: dict, traffic: dict, seed: int, mesh=None):
        from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
        from repro.fl import FederatedTrainer

        self.config, self.traffic = config, traffic
        self.chunk = traffic["chunk_rounds"]
        self.data, self.params0 = inputs(config, seed)
        ch_cfg = ChannelConfig(n_clients=config["n_clients"],
                               **config["channel"])
        fe_cfg = FairEnergyConfig(
            **{k: tuple(v) if isinstance(v, list) else v
               for k, v in traffic.get("fairenergy", {}).items()})
        fl_cfg = FLConfig(rounds=self.chunk, local_steps=traffic["local_steps"],
                          local_batch=traffic["local_batch"], lr=traffic["lr"],
                          dirichlet_beta=config["data"]["dirichlet_beta"])
        self.trainer = FederatedTrainer(
            **trainer_inputs(config, traffic, self.data, self.params0),
            fl_cfg=fl_cfg, fe_cfg=fe_cfg, ch_cfg=ch_cfg,
            controller=traffic["controller"],
            **{k: traffic[k] for k in ("fixed_k", "eco_gamma") if k in traffic},
            seed=config["fleet_seed"], mesh=mesh)

    def run_chunk(self, start: int) -> None:
        """Rounds [start, start + chunk): one call of the scanned engine,
        ending in its host sync of the chunk's logs. Eval runs on the
        chunk's last round (and on round 0)."""
        self.trainer.run_scanned(start + self.chunk, start_round=start,
                                 chunk=self.chunk, eval_every=1 << 30,
                                 verbose=False)

    def logs(self, start: int, stop: int) -> list:
        return [dict(x=np.asarray(lg.selected), gamma=lg.gamma,
                     bandwidth=lg.bandwidth, energy=lg.energy, loss=lg.loss,
                     accuracy=lg.accuracy)
                for lg in self.trainer.history[start:stop]]

    def params(self) -> dict:
        """Host copy of the trainer's parameters (the engine donates its
        device buffers on the next call)."""
        return jax.tree_util.tree_map(lambda v: np.array(v, np.float32),
                                      self.trainer.params)


def failed_rounds(logs) -> int:
    """Rounds whose logged loss or energies are not finite."""
    return sum(not (math.isfinite(lg["loss"])
                    and np.all(np.isfinite(lg["energy"]))) for lg in logs)
