"""Shared benchmark harness: the measurement scaffolding every driver in
``benchmarks/`` repeats.

The drivers share one measurement discipline, factored here:

* **subprocess-per-arm** (``run_worker``) — arms that differ in process-
  level state (forced host-device count, huge population shapes) run the
  driver file itself as a ``--worker`` subprocess with a controlled env
  (``XLA_FLAGS=--xla_force_host_platform_device_count=D``,
  ``JAX_PLATFORMS=cpu``, ``PYTHONPATH=src``) and hand back one JSON line
  on stdout;
* **interleaved best-of** (``time_interleaved`` for in-process thunks,
  ``sweep_best`` for subprocess arms) — every arm is warmed/compiled
  first, then repetitions are interleaved across arms and the best rep
  kept, so the throughput drift of shared/throttled CPUs can't skew arms
  measured minutes apart;
* **stamped results** (``stamp``) — every result JSON records
  ``physical_cpus`` (forced host devices cannot beat physical cores; the
  reader needs both numbers) plus any driver-specific context;
* **the output protocol** (``emit`` + ``base_parser``) — print the
  result, write ``BENCH_*.json`` at the repo root unless ``--fast`` (the
  CI smoke mode: tiny sweep, exercises the full path, result not
  meaningful so never persisted).

Drivers keep their workload definitions; this module owns only the
timing/process/IO mechanics.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Callable, Dict, Optional, Sequence

REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` points (JAX reads the variable itself);
    when it is unset, at the fixed ``<repo>/.jax_cache``, so a second run
    of the same program from this checkout loads what the first compiled.
    Call before the first compile; returns the directory in use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(os.path.abspath(REPO_ROOT),
                                       ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def worker_env(devices: int = 1, base: Optional[dict] = None) -> dict:
    """Subprocess env with a forced host-device count: replaces any
    existing ``--xla_force_host_platform_device_count`` flag (device
    count is fixed at process startup — the whole reason workers exist),
    pins the CPU backend, and prepends ``src`` to PYTHONPATH."""
    env = dict(base if base is not None else os.environ)
    other = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={devices}"] + other)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = (os.path.join(REPO_ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    return env


def run_worker(script: str, argv: Sequence[str], *, devices: int = 1,
               timeout: int = 1200) -> dict:
    """Run ``script --worker *argv`` in a fresh interpreter and parse the
    worker's result: the LAST stdout line, one JSON object (earlier lines
    — compile chatter, jax warnings — are ignored)."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(script), "--worker", *map(str, argv)],
        capture_output=True, text=True, env=worker_env(devices),
        cwd=REPO_ROOT, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"worker {argv} (devices={devices}) failed:\n"
                           + out.stdout + out.stderr)
    return json.loads(out.stdout.strip().splitlines()[-1])


def time_best(fn: Callable[[], object], reps: int) -> float:
    """Best wall-clock of ``reps`` calls (caller warms/compiles first)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def time_interleaved(arms: Dict[str, Callable[[], object]],
                     reps: int = 3) -> Dict[str, float]:
    """Best seconds per in-process arm, repetitions interleaved across
    arms. Every arm runs once first (compile + cache warm, untimed)."""
    for fn in arms.values():
        fn()
    best = {name: float("inf") for name in arms}
    for _ in range(reps):
        for name, fn in arms.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def sweep_best(arms: Dict[object, Callable[[], dict]], *, sweeps: int = 2,
               score: Callable[[dict], float] = lambda r: -r.get("best_rep_s",
                                                                 float("inf")),
               progress: Optional[Callable[[int, object, dict], None]] = None,
               ) -> Dict[object, dict]:
    """Best result per subprocess arm over ``sweeps`` interleaved whole
    sweeps (higher ``score`` wins; the default keeps the fastest rep)."""
    best: Dict[object, dict] = {}
    for s in range(sweeps):
        for key, thunk in arms.items():
            r = thunk()
            if key not in best or score(r) > score(best[key]):
                best[key] = r
            if progress is not None:
                progress(s, key, r)
    return best


def stamp(res: dict) -> dict:
    """Attach the host context every result JSON must carry."""
    res.setdefault("physical_cpus", os.cpu_count())
    return res


def base_parser(out_name: str, **extra_defaults) -> argparse.ArgumentParser:
    """The shared driver CLI: ``--worker`` (run as a spawned arm),
    ``--fast`` (CI smoke), ``--out`` (result path, repo root default)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true",
                    help="internal: run as a spawned measurement arm")
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: tiny sweep, result not meaningful")
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, out_name))
    for name, default in extra_defaults.items():
        ap.add_argument(f"--{name.replace('_', '-')}", type=type(default),
                        default=default)
    return ap


def emit(res: dict, out: str, fast: bool) -> None:
    """Print the result; persist it only for real (non ``--fast``) runs."""
    print(json.dumps(res, indent=1))
    if not fast:
        with open(out, "w") as f:
            json.dump(res, f, indent=1)
            f.write("\n")
        print(f"wrote {out}")
