"""Readings that the limits of ``correct`` are set from (not part of a
benchmark run).

    python3 bench/readings.py --workload <cell> --seeds 12 --faults 3 \\
        --out chiprun_out/readings.<cell>.json

In one process, at the cell's own size: the program's compared numbers
on a dozen seeds or more; the lower-precision control (the reference put
in the program's place, computed in bfloat16) on three; and each planted
fault of the timed path on three. A fault is planted in the program
under test for the trainers built while it is active:

* ``state_unchanged``: the round's apply adds nothing to the parameters;
* ``half_batch``: the client loss is the mean over the first half of
  each minibatch (every leaf of the batch the family's ``model_loss``
  is handed is cut to its first half);
* ``decision_altered``: the controller's bandwidths come out 10 % low;
* ``update_altered``: the round's aggregated update is scaled by 1.5
  where the round produces it, before it is applied.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


@contextlib.contextmanager
def planted(fault: str | None):
    """The program with one fault planted underneath its timed path."""
    import jax
    import jax.numpy as jnp
    from repro.core.controllers import baselines, fairenergy
    from repro.fl import server

    import cell as cell_mod

    saved = []

    def patch(obj, name, new):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    if fault == "state_unchanged":
        orig = server.unflatten_update
        patch(server, "unflatten_update", lambda vec, spec: jax.tree_util.tree_map(
            jnp.zeros_like, orig(vec, spec)))
    elif fault == "half_batch":
        orig = cell_mod.trainer_inputs

        def trainer_inputs(*args):
            ins = orig(*args)
            loss = ins["model_loss"]
            return dict(ins, model_loss=lambda p, b: loss(
                p, jax.tree_util.tree_map(lambda v: v[: v.shape[0] // 2], b)))
        patch(cell_mod, "trainer_inputs", trainer_inputs)
    elif fault == "decision_altered":
        for cls in (fairenergy.FairEnergy, baselines.EcoRandom,
                    baselines.ScoreMax):
            orig_decide = cls.decide

            def decide(self, obs, state, _orig=orig_decide):
                dec, st = _orig(self, obs, state)
                return dec._replace(bandwidth=dec.bandwidth * 0.9), st
            patch(cls, "decide", decide)
    elif fault == "update_altered":
        orig = server.unflatten_update
        patch(server, "unflatten_update", lambda vec, spec: orig(
            vec * 1.5, spec))
    elif fault is not None:
        raise ValueError(fault)
    try:
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)


def program_reading(workload, bench, seed, fault=None):
    import cell as cell_mod
    import reference
    _, config, traffic, _ = cell_mod.spec(workload, bench)
    t0 = time.perf_counter()
    with planted(fault):
        cell = cell_mod.Cell(config, traffic, seed)
        cell.run_chunk(0)
    c = cell.chunk
    logs, params = cell.logs(0, c), cell.params()
    data, params0 = cell.data, cell.params0
    del cell
    t1 = time.perf_counter()
    ref = reference.follow(config, traffic, data, params0, c, logs=logs)
    nums, ok, detail = reference.compare(logs, params, ref, params0, config,
                                         traffic)
    return dict(seed=seed, fault=fault, numbers=nums, finite=ok,
                program_s=t1 - t0, reference_s=time.perf_counter() - t1,
                detail=detail)


def control_reading(workload, bench, seed):
    import jax
    import jax.numpy as jnp

    import cell as cell_mod
    import reference
    _, config, traffic, _ = cell_mod.spec(workload, bench)
    c = traffic["chunk_rounds"]
    data, params0 = cell_mod.inputs(config, seed)
    low = reference.follow(config, traffic, data, params0, c,
                           dtype=jnp.bfloat16,
                           precision=jax.lax.Precision.DEFAULT)
    logs = reference.as_logs(low)
    ref = reference.follow(config, traffic, data, params0, c, logs=logs)
    nums, ok, detail = reference.compare(logs, low["params"], ref, params0,
                                         config, traffic)
    return dict(seed=seed, fault="control_bf16", numbers=nums, finite=ok,
                detail=detail)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--faults", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=1000003)
    p.add_argument("--skip", default="",
                   help="comma-separated faults (or control_bf16) to leave out")
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    import run
    run.use_compile_cache()
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    rows = []

    def keep(row):
        rows.append(row)
        print(json.dumps(row), flush=True)
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1))

    skip = set(a.skip.split(",")) - {""}
    for s in seeds:
        keep(program_reading(a.workload, bench, s))
    fault_seeds = [a.first_seed + 1 + 7919 * i for i in range(a.faults)]
    if "control_bf16" not in skip:
        for s in fault_seeds:
            keep(control_reading(a.workload, bench, s))
    for fault in ("state_unchanged", "half_batch", "decision_altered",
                  "update_altered"):
        if fault in skip:
            continue
        for s in fault_seeds:
            keep(program_reading(a.workload, bench, s, fault))


if __name__ == "__main__":
    main()
