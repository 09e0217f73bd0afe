"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. One run builds the cell's trainer from its configuration and traffic
files (``bench/configs``, ``bench/traffic``, found by the names in
``BENCHMARK.json``), warms it up through the timed entry, measures the
chunked ``FederatedTrainer.run_scanned`` loop for ``--seconds``, checks
the warm-up rounds against the plain reference (``reference.py``) and
prints one JSON line last on stdout. ``--trace 1`` measures under the
profiler and reports the per-layer metrics (``bench/metrics/<name>.py``)
in place of the end-to-end ones. It refuses to run (exit 3, no result)
without a TPU or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` points, else the fixed ``bench/out/
    jax_cache`` of this checkout. Every program is cached, however
    quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(OUT / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts backend compilations (cache loads included) while on."""

    def __init__(self):
        import jax
        self.on, self.n, self.setup = False, 0, {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration" and self.on:
            self.n += 1

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            key = name.rsplit("/", 1)[1]
            self.setup[key] = self.setup.get(key, 0) + 1


def run_cell(workload: str, bench: dict, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True):
    """One run of a cell. Returns the result object (the last stdout
    line) and the lines of compared numbers, or None with a reason when
    the machine cannot run the cell."""
    import jax

    import cell as cell_mod
    import flops
    import reference
    import trace as trace_mod

    entry, config, traffic, limits = cell_mod.spec(workload, bench)
    chips = entry["chips"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        return None, (f"needs {chips} TPU chip(s); JAX sees "
                      f"{len(devices)} {devices[0].platform} device(s)")
    counter = CompileCounter()
    mesh = None
    if chips > 1:
        from repro.sharding import make_clients_mesh
        mesh = make_clients_mesh(chips)
    cell = cell_mod.Cell(config, traffic, seed, mesh=mesh)
    c = cell.chunk
    # set-up: data, weights, trainer, calibration, the round program
    # (compiled or loaded from the cache) and one warm chunk through the
    # timed entry; its rounds are the ones the reference follows
    cell.run_chunk(0)
    warm_logs = cell.logs(0, c)
    warm_params = cell.params()
    setup_s = time.perf_counter() - T_START

    trace_dir = OUT / "trace" / f"{workload}.{seed}"
    max_chunks = traffic["trace_chunks"] if trace else 1 << 30
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    counter.on = True
    start, t0 = c, time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.chunk"):
                cell.run_chunk(start)
            start += c
            window_s = time.perf_counter() - t0
            if window_s >= seconds or (start - c) // c >= max_chunks:
                break
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    rounds = start - c
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    window_logs = cell.logs(c, start)

    summary = None
    if trace:
        path = trace_mod.trace_file(str(trace_dir))
        events, host = trace_mod.device_events(path)
        if events and not any(e["op_name"] for e in events):
            names = trace_mod.hlo_op_names(
                cell.trainer.lower_scanned(c).compile().as_text())
            events, host = trace_mod.device_events(path, names)
        summary = trace_mod.summarize(events, host)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state is freed before the reference runs
    data, params0 = cell.data, cell.params0
    del cell
    gc.collect()
    ref = reference.follow(config, traffic, data, params0, c, logs=warm_logs)
    nums, ok, _ = reference.compare(warm_logs, warm_params, ref, params0,
                                    config, traffic)
    correct, shown = reference.judge(nums, ok, limits)

    kind = devices[0].device_kind
    device = dict(platform=devices[0].platform, kind=kind, count=len(used),
                  memory_peak_bytes=int(peak))
    if trace:
        peaks = json.loads((BENCH / "peaks.json").read_text())
        if kind not in peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        metrics = {}
        ctx = dict(trace=summary, rounds=rounds, chips=chips,
                   flops_per_round=flops.round_flops(config, traffic),
                   peak=peaks[kind], window_compiles=counter.n,
                   config=config, traffic=traffic)
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    else:
        e2e = dict(rounds_per_s=rounds / window_s, setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if workload in m.get("workloads", [workload])}
    result = dict(correct=bool(correct), attempted=rounds,
                  failed=cell_mod.failed_rounds(window_logs),
                  metrics=metrics, device=device)
    if trace:
        result["breakdown"] = dict(device_ops=summary["device_ops"],
                                   idle_gaps=summary["idle_gaps"])
    result["checks"] = shown
    notes = dict(setup_cache=counter.setup, window_s=window_s,
                 window_compiles=counter.n, numbers=nums)
    return result, notes


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    use_compile_cache()
    result, notes = run_cell(args.workload, bench, args.seed, args.seconds,
                             bool(args.trace))
    if result is None:
        print(f"bench/run.py: {notes}", file=sys.stderr)
        return 3
    print(json.dumps({k: v for k, v in notes.items() if k != "numbers"}),
          file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
