"""Smoke run of the FairEnergy main path on a TPU.

    python3 chip_smoke.py               # one chip: phases a-d
    python3 chip_smoke.py --four-chips  # four chips: the clients-mesh phase

The main path is the paper's: the 1.6M-parameter CNN
(``configs.fmnist_cnn``) trained by ``FederatedTrainer.run_scanned`` with the
``fairenergy`` controller over N=50 non-IID clients (Dirichlet beta=0.3) of
the 12,000-sample FMNIST-like set, as ``benchmarks.fl_experiments.build``
makes it. Weights and data come from seed 0.

Phases, one stdout line each:

  a  JAX sees a TPU. There is no CPU fallback: without one, exit non-zero.
  b  a few rounds on the default paths: the jnp solver, and the top-k
     chosen by backend, which on a TPU is the Pallas kernel over the [N, D]
     update matrix. The compiled round program must hold it
     (``tpu_custom_call``).
  c  the same trainer and seed with the Pallas solver as well. On
     identical round-0 inputs the solver kernel's decisions (selection,
     gamma, bandwidth, energy) must match the jnp solver's to fp32
     tolerance, the default top-k's sparsified rows must be the jnp top-k's
     (``use_pallas=False``) bit for bit, and the first round of the run
     must select the same clients as phase b. Later rounds may drift apart
     through near-threshold ties.
  d  accuracy, energies and params are finite and every round selects a
     client.

``--four-chips`` runs N=200 on ``make_clients_mesh()`` over four chips
against ``mesh=None`` in the same process: identical selection masks, and
params and energies within the tolerances of ``tests/test_sharded_engine.py``.

The times printed are smoke timings by host clock around
``block_until_ready``, not benchmark results. A failing phase raises, and
the script exits non-zero. The last stdout line is the result:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

JAX's compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR`` points,
or else at ``<repo>/.jax_cache``; each phase line counts its cache hits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ROUNDS = 3


def _require_tpu(n_chips: int):
    """Phase a: the devices JAX sees, which must be ``n_chips`` TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devices[0].platform!r}); this smoke run needs the chip")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, JAX sees "
                 f"{len(devices)}")
    return devices[:n_chips]


class _CacheCounter:
    """Counts JAX's persistent-cache hits and misses between reads."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"cache_hits": self.hits, "cache_misses": self.misses}
        self.hits = self.misses = 0
        return out


def _require(ok: bool, what: str):
    """A check of a phase; it raises, and so fails the run, even under -O."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _timed_runs(tr, rounds: int):
    """Compile the trainer's round program, then run it twice: the first
    call loads what was compiled, the second is the steady one. Returns
    the compiled program and the smoke timing (host clock, not a
    benchmark)."""
    import jax
    t0 = time.perf_counter()
    compiled = tr.lower_scanned(rounds).compile()
    compile_s = time.perf_counter() - t0
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        tr.run_scanned(rounds, verbose=False)
        jax.block_until_ready(tr.params)
        walls.append(time.perf_counter() - t0)
    return compiled, {"smoke_timing": "host clock around block_until_ready;"
                                      " not a benchmark",
                      "compile_s": compile_s, "first_call_s": walls[0],
                      "rounds": rounds, "rounds_per_s": rounds / walls[1]}


def _round0_inputs(tr):
    """Round-0 observations exactly as the fused engine computes them:
    the trainer's client step on its round-0 batches, the round-0 channel
    draw, the calibrated controller state. Taken before the trainer runs,
    because ``run_scanned`` donates its carry."""
    import jax
    import jax.numpy as jnp
    from repro.core.controllers import RoundObservation
    tr._maybe_calibrate(0)                  # one-shot eta calibration
    updates, u_norms, _ = tr._client_step(tr.params, tr._round_batches(0))
    obs = RoundObservation(
        u_norms=u_norms, h=jnp.asarray(tr.network.gains(0), jnp.float32),
        P=jnp.asarray(tr.network.power, jnp.float32), round=jnp.int32(0),
        key=jax.random.fold_in(tr.key, 0))
    state = jax.tree_util.tree_map(jnp.copy, tr.ctrl_state)
    return updates, obs, state


def _max_rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _compare_kernels(tr_jnp, tr_pl, updates, obs, state) -> dict:
    """Decide and sparsify round 0 through both paths on the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.fl.compression import batch_block_topk

    dec_j, _ = jax.jit(tr_jnp.controller.decide)(obs, state)
    dec_p, _ = jax.jit(tr_pl.controller.decide)(obs, state)
    np.testing.assert_array_equal(np.asarray(dec_p.x), np.asarray(dec_j.x),
                                  err_msg="round-0 selection")
    np.testing.assert_array_equal(np.asarray(dec_p.gamma),
                                  np.asarray(dec_j.gamma),
                                  err_msg="round-0 gamma")
    for name in ("bandwidth", "energy"):
        np.testing.assert_allclose(np.asarray(getattr(dec_p, name)),
                                   np.asarray(getattr(dec_j, name)),
                                   rtol=1e-4, atol=0, err_msg=name)
    # every client sparsified at its decided gamma (k >= 1 per block), by
    # the default path (the kernel on a TPU) and by the jnp path
    gamma = jnp.clip(dec_j.gamma, 1e-6, 1.0)
    topk = jax.jit(batch_block_topk,
                   static_argnames=("use_pallas", "skip_full"))
    _require(_kernel_calls(topk.lower(updates, gamma, skip_full=False)
                           .compile()) > 0,
             "no Pallas kernel in the default top-k")
    rows_j = topk(updates, gamma, use_pallas=False, skip_full=False)
    rows_d = topk(updates, gamma, skip_full=False)
    np.testing.assert_array_equal(np.asarray(rows_d).view(np.int32),
                                  np.asarray(rows_j).view(np.int32),
                                  err_msg="sparsified rows, default vs jnp")
    sel = np.asarray(dec_j.x)
    return {"round0_selected": int(sel.sum()),
            "bandwidth_max_rel_diff": _max_rel(dec_p.bandwidth,
                                               dec_j.bandwidth),
            "energy_max_rel_diff": _max_rel(dec_p.energy, dec_j.energy),
            "rows_bits_equal": True,
            "rows_kept": int(np.count_nonzero(np.asarray(rows_j)))}


def _kernel_calls(compiled) -> int:
    """Pallas kernel launches in a compiled TPU program."""
    return compiled.as_text().count("tpu_custom_call")


def _check_finite(tr, label: str) -> dict:
    """Phase d for one trainer."""
    import jax
    import numpy as np
    hist = tr.history
    _require(bool(hist), f"{label}: no rounds ran")
    acc = np.array([lg.accuracy for lg in hist])
    _require(np.all(np.isfinite(acc)), f"{label}: accuracy {acc}")
    for lg in hist:
        _require(np.all(np.isfinite(lg.energy)),
                 f"{label}: round {lg.round} energy")
        _require(lg.n_selected >= 1, f"{label}: round {lg.round} selects none")
    for leaf in jax.tree_util.tree_leaves(tr.params):
        _require(np.all(np.isfinite(np.asarray(leaf))), f"{label}: params")
    return {"rounds": len(hist), "final_accuracy": float(acc[-1]),
            "selected_per_round": [lg.n_selected for lg in hist]}


def one_chip(n_clients: int = 50, rounds: int = ROUNDS, **build_kw):
    """Phases b, c and d at ``n_clients``; returns nothing, raises on a
    failed check."""
    import numpy as np
    from benchmarks.fl_experiments import build

    cache = _CacheCounter()
    make, _ = build(n_clients=n_clients, rounds=rounds, seed=SEED, **build_kw)
    make_pl, _ = build(n_clients=n_clients, rounds=rounds, seed=SEED,
                       pallas=True, **build_kw)
    tr = make("fairenergy")
    tr_pl = make_pl("fairenergy")
    updates, obs, state = _round0_inputs(tr)

    compiled, timing = _timed_runs(tr, rounds)
    n_calls = _kernel_calls(compiled)
    _require(n_calls > 0, "no Pallas kernel in the default round program")
    _emit("b", path="default", n_clients=n_clients, n_params=tr.n_params,
          tpu_custom_calls=n_calls, **timing, **cache.take())

    kernels = _compare_kernels(tr, tr_pl, updates, obs, state)
    del updates
    compiled, timing = _timed_runs(tr_pl, rounds)
    n_calls = _kernel_calls(compiled)
    _require(n_calls > 0, "no Pallas kernel in the compiled round program")
    np.testing.assert_array_equal(tr_pl.history[0].selected,
                                  tr.history[0].selected,
                                  err_msg="first-round selection, b vs c")
    _emit("c", path="pallas solver", tpu_custom_calls=n_calls, **kernels,
          first_round_mask_equal=True, **timing, **cache.take())

    _emit("d", default=_check_finite(tr, "b"),
          pallas_solver=_check_finite(tr_pl, "c"))


def four_chips(devices, n_clients: int = 200, rounds: int = ROUNDS,
               **build_kw):
    """The clients-mesh phase: sharded over ``devices`` vs one device."""
    import jax
    import numpy as np
    from benchmarks.fl_experiments import build
    from repro.sharding import make_clients_mesh

    cache = _CacheCounter()
    mesh = make_clients_mesh(len(devices))
    make_ref, _ = build(n_clients=n_clients, rounds=rounds, seed=SEED,
                        **build_kw)
    make_sh, _ = build(n_clients=n_clients, rounds=rounds, seed=SEED,
                       mesh=mesh, **build_kw)
    tr_ref, tr_sh = make_ref("fairenergy"), make_sh("fairenergy")
    # every client stack is split over all the chips, one slice on each
    for leaf in jax.tree_util.tree_leaves(tr_sh._data):
        placed = {s.device for s in leaf.addressable_shards}
        _require(placed == set(devices) and
                 leaf.addressable_shards[0].data.shape[0] * len(devices)
                 == leaf.shape[0],
                 f"client data sits on {sorted(d.id for d in placed)}")
    _, t_ref = _timed_runs(tr_ref, rounds)
    _, t_sh = _timed_runs(tr_sh, rounds)

    _require(len(tr_ref.history) == len(tr_sh.history), "history lengths")
    for a, b in zip(tr_ref.history, tr_sh.history):
        np.testing.assert_array_equal(a.selected, b.selected,
                                      err_msg=f"round {a.round} mask")
        np.testing.assert_allclose(b.energy, a.energy, rtol=1e-5, atol=0,
                                   err_msg=f"round {a.round} energy")
    flat = lambda tr: np.concatenate(  # noqa: E731
        [np.ravel(x) for x in jax.tree_util.tree_leaves(tr.params)])
    p_ref, p_sh = flat(tr_ref), flat(tr_sh)
    np.testing.assert_allclose(p_sh, p_ref, rtol=0, atol=1e-6,
                               err_msg="params")
    _emit("four_chips", n_clients=n_clients, mesh=dict(mesh.shape),
          data_devices=sorted(d.id for d in placed),
          params_max_abs_diff=float(np.max(np.abs(p_sh - p_ref))),
          energy_max_rel_diff=max(_max_rel(b.energy[a.selected],
                                           a.energy[a.selected])
                                  for a, b in zip(tr_ref.history,
                                                  tr_sh.history)
                                  if a.selected.any()),
          selected_per_round=[lg.n_selected for lg in tr_sh.history],
          one_device=t_ref, sharded=t_sh, **cache.take())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the clients-mesh phase, on four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devices = _require_tpu(n_chips)
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]
    from benchmarks._harness import use_compile_cache
    cache_dir = use_compile_cache()
    d0 = devices[0]
    _emit("a", platform=d0.platform, kind=d0.device_kind,
          count=len(devices), compilation_cache=cache_dir)
    if args.four_chips:
        four_chips(devices)
    else:
        one_chip()
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
