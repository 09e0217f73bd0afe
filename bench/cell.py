"""A benchmark cell: the program under test, built from a configuration
and a traffic mix, and driven chunk by chunk as users run it.

Everything here goes through the program's public entry points
(``repro.fl.FederatedTrainer`` and its configs); the data and the
weights are the benchmark's own (``data.py``, ``reference.init_params``).
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import data as bench_data
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
    return cell, config, load("traffic", cell["traffic"]), \
        load("limits", workload)


class Cell:
    """Builds the program's trainer for one seed and runs its chunks."""

    def __init__(self, config: dict, traffic: dict, seed: int, mesh=None):
        from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
        from repro.configs.base import ModelConfig
        from repro.fl import FederatedTrainer
        from repro.models import cnn

        self.config, self.traffic = config, traffic
        self.chunk = traffic["chunk_rounds"]
        self.data = bench_data.make(config, seed)
        self.params0 = reference.init_params(config["model"], seed)
        m = config["model"]
        mcfg = ModelConfig(name=config["name"], family="cnn",
                           n_layers=len(m["cnn_channels"]), d_model=0,
                           cnn_channels=tuple(m["cnn_channels"]),
                           cnn_dense=m["cnn_dense"],
                           input_hw=tuple(m["input_hw"]),
                           n_classes=m["n_classes"], dtype=m["dtype"])
        ch_cfg = ChannelConfig(n_clients=config["n_clients"],
                               **config["channel"])
        fe_cfg = FairEnergyConfig(
            **{k: tuple(v) if isinstance(v, list) else v
               for k, v in traffic.get("fairenergy", {}).items()})
        fl_cfg = FLConfig(rounds=self.chunk, local_steps=traffic["local_steps"],
                          local_batch=traffic["local_batch"], lr=traffic["lr"],
                          dirichlet_beta=config["data"]["dirichlet_beta"])
        d = self.data
        clients = [dict(images=d["images"][p], labels=d["labels"][p])
                   for p in d["parts"]]
        test_x = jnp.asarray(d["test_images"])
        test_y = jnp.asarray(d["test_labels"])

        @jax.jit
        def eval_fn(p):
            logits = cnn.cnn_forward(p, test_x, mcfg)
            return jnp.mean((jnp.argmax(logits, -1) == test_y)
                            .astype(jnp.float32))

        self.trainer = FederatedTrainer(
            model_loss=lambda p, b: cnn.cnn_loss(p, b, mcfg),
            model_params=self.params0, client_datasets=clients,
            eval_fn=eval_fn, fl_cfg=fl_cfg, fe_cfg=fe_cfg, ch_cfg=ch_cfg,
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
