"""A configuration's model family, found by the ``model.family`` key of its
configuration file.

A family is a directory ``bench/families/<family>/`` of four modules,
loaded by path as the metric readers are:

* ``data.py``: ``make_data(config, seed) -> dict``, the clients' shards
  as arrays with the example axis leading, ``parts`` (each client's row
  indices) and the held-out set, all from the seed;
* ``reference.py``, the reference's model half, which imports nothing of
  the program: ``init_params(model, seed)``; ``client_step(lr, dtype,
  precision)``, vmapped local SGD ``(params, batch [N, S, B, ...]) ->
  (updates [N, D] float32 in sorted-key order, last-step losses [N])``;
  ``minibatch(data, rows)``, that batch for row indices ``[N, S, B]``;
  ``accuracy(data, dtype, precision)``, ``params -> accuracy`` over the
  held-out set, in blocks;
* ``flops.py``: ``train_flops(model)`` per sample and
  ``eval_flops(config)`` per evaluation;
* ``program.py``: ``trainer_inputs(config, traffic, data, params0) ->
  dict(model_loss, model_params, client_datasets, eval_fn)``, built only
  through the program's public entry points: ``model_loss(params,
  batch) -> (loss, aux)`` over one minibatch, ``client_datasets`` one
  dict of arrays per client, and ``eval_fn(params)`` a jitted function of
  that name, the name by which ``trace.classify`` finds the eval layer.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

FAMILIES = Path(__file__).resolve().parent / "families"


def directory(family: str) -> Path:
    """``bench/families/<family>``; exits with a message naming the
    family and the directory looked in where there is none."""
    path = FAMILIES / family
    if not path.is_dir():
        raise SystemExit(f"model family {family!r}: no directory {path}")
    return path


def load(family: str, part: str):
    """The module ``bench/families/<family>/<part>.py``."""
    path = directory(family) / f"{part}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{family}_{part}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
