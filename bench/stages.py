"""The round's stages in a profiler trace: the program's ``fl.<stage>``
scopes on the device and its ``fl.*`` spans on the host.

The round program names its stages with ``jax.named_scope``
(``fl.sample``, ``fl.client_step``, ``fl.decide``, ``fl.sparsify``,
``fl.aggregate``, ``fl.eval``; ``repro.fl.server``) and the host loop of
``FederatedTrainer`` spans its chunk calls with
``jax.profiler.TraceAnnotation`` (``fl.dispatch``, ``fl.sync``,
``fl.logs``). This module reduces a trace by those names, beside the
layers that ``trace.py`` finds by heuristics, and leaves those as they
are: ``stage_s`` is the exclusive device time per stage, with
``untagged`` for ops under no scope; each idle gap is named by the
``fl.*`` span it falls in; each top op is ``<layer>/<stage>:<op>``.

An op the compiler made has no name stack of its own (a layout copy, a
prefetch, a buffer write, an op its rewriters split off another); it
takes its stage from the compiled program (``hlo_stages``). So there are
two readings: ``named_s`` by each op's own name stack alone, and
``stage_s`` with those ops placed; ``inferred_ops`` lists the largest
ops that only the second places.

    python3 bench/stages.py --workload <cell> --seed <n>
                            [--events <file.json.gz>]

runs one traced window of a cell on the chip (the warm-up and the
``trace_chunks`` chunks of the cell's traffic, as ``run.py --trace 1``
runs them, without the reference; the round program compiled with its
name stacks in the cache key) and prints one JSON line: device time per
round by stage (both readings) and by layer, the idle share, the rounds
per second of the traced window, the idle gaps by span and the top ops.
``--events`` writes the window's tagged device events and host spans in
the form of ``bench/testdata``. Like ``run.py``, it refuses to run
(exit 3) without a TPU.
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import trace as base  # noqa: E402

STAGES = ("sample", "client_step", "decide", "sparsify", "aggregate", "eval")
SPANS = ("fl.dispatch", "fl.sync", "fl.logs")
OPCODE = re.compile(r"(?<![\w.%-])([a-z][\w-]*)\(")
REF = re.compile(r"%([^\s,(){}]+)")
FUSED = re.compile(r"calls=%([^\s,)]+)")
CONTROL = re.compile(r"(?:body|condition)=%([^\s,)]+)"
                     r"|branch_computations=\{([^}]*)\}")
CONTROL_OPS = ("while", "conditional", "call")


# the round's stage of one device op: the innermost ``fl.<stage>`` scope
# of its name stack, or None outside every such scope
stage = base.scope


def _instructions(hlo_text: str) -> list:
    """One dict per instruction of a compiled module's text, in text
    order: ``name, comp, op_name, opcode, args`` (the text between the
    opcode's parentheses), ``operands`` (the instructions named there),
    ``tail`` (what follows them) and ``root``."""
    out, comp = [], None
    for line in hlo_text.splitlines():
        c = base.COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = base.INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        oc = OPCODE.search(rest)
        opcode, args, tail = "", "", rest
        if oc:
            depth, i = 1, oc.end()
            while i < len(rest) and depth:
                depth += {"(": 1, ")": -1}.get(rest[i], 0)
                i += 1
            opcode, args, tail = oc.group(1), rest[oc.end():i - 1], rest[i:]
        op = base.OP_NAME.search(tail)
        out.append(dict(name=name, comp=comp,
                        op_name=op.group(1) if op else "", opcode=opcode,
                        args=args, operands=REF.findall(args), tail=tail,
                        root=line.lstrip().startswith("ROOT")))
    return out


def hlo_stages(hlo_text: str) -> dict:
    """HLO instruction name -> the round's stage, for every instruction
    of a compiled module, or None where nothing decides.

    An instruction under an ``fl.<stage>`` scope has that stage. One
    outside every scope takes the stage of the values it reads: a fusion
    that of its root, a parameter of a fused computation that of the
    fusion's operand, a parameter of a loop body or branch that of the
    loop or conditional, any other instruction that of its first operand
    with one. What still has none (a copy of a loop carry, say) takes
    the stage of its first user with one. A loop or a conditional keeps
    its own: the time it holds the device between the ops it runs is
    control, not a stage's work."""
    insts = _instructions(hlo_text)
    st = {i["name"]: stage(i["op_name"]) for i in insts}
    root, fused_by, control_by, users = {}, {}, {}, {}
    for i in insts:
        if i["root"]:
            root[i["comp"]] = i["name"]
        for o in i["operands"]:
            users.setdefault(o, []).append(i["name"])
        if i["opcode"] == "fusion":
            for c in FUSED.findall(i["tail"]):
                fused_by[c] = i
        for body, branches in CONTROL.findall(i["tail"]):
            for c in [body] if body else branches.split(","):
                control_by[c.strip().lstrip("%")] = i["name"]

    def from_inputs(i):
        if i["opcode"] == "fusion":
            callee = FUSED.search(i["tail"])
            return st.get(root.get(callee.group(1))) if callee else None
        if i["opcode"] == "parameter":
            fusion = fused_by.get(i["comp"])
            if fusion is None:
                return st.get(control_by.get(i["comp"]))
            k, args = int(i["args"]), fusion["operands"]
            return st.get(args[k]) if k < len(args) else None
        return next((st[o] for o in i["operands"] if st.get(o)), None)

    def from_users(i):
        return next((st[u] for u in users.get(i["name"], ()) if st.get(u)),
                    None)

    for fill in (from_inputs, from_users):
        changed = True
        while changed:
            changed = False
            for i in insts:
                if st[i["name"]] is None and i["opcode"] not in CONTROL_OPS:
                    st[i["name"]] = fill(i)
                    changed |= st[i["name"]] is not None
    return st


def tag(events, stages: dict | None = None) -> list:
    """``trace.device_events``' events, each with its ``stage``: that of
    its own name stack, else ``stages`` (``hlo_stages``) of its HLO
    name."""
    stages = stages or {}
    return [dict(e, stage=stage(e["op_name"]) or stages.get(e["name"]))
            for e in events]


def host_spans(path: str) -> list:
    """``name, start, dur`` (nanoseconds) of the program's ``fl.*`` and
    the benchmark's ``bench.*`` host spans in an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    return [dict(name=e.name, start=float(e.start_ns),
                 dur=float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("bench.", "fl."))]


def summarize(events, host, window=None, top=10) -> dict:
    """``trace.summarize`` of the same events and window, with
    ``stage_s`` (exclusive device time per stage, ``untagged`` for ops
    under no scope and events without a ``stage``), ``named_s`` (the
    same by each op's own name stack alone), ``inferred_ops`` (the top
    ops that have a ``stage`` but no scope in their own name stack), the
    top ops as ``<layer>/<stage>:<op>`` where a stage covers the op, and
    each idle gap inside a chunk named by the ``fl.*`` span its midpoint
    falls in (``host: inside run_scanned (fl.sync)``). Seconds
    throughout."""
    if window is None:
        spans = [h for h in host if h["name"] == "bench.window"]
        if spans:
            window = (spans[0]["start"], spans[0]["start"] + spans[0]["dur"])
        elif events:
            window = (min(e["start"] for e in events),
                      max(e["start"] + e["dur"] for e in events))
        else:
            window = (0.0, 0.0)
    out = base.summarize(events, host, window=window, top=top)
    w0, w1 = window
    inside = [e for e in events
              if e["start"] < w1 and e["start"] + e["dur"] > w0]
    nd = out["devices"]
    stage_s, ops, inferred = {}, {}, {}
    for e, d in zip(inside, base.exclusive_times(inside, w0, w1)):
        st, own = e.get("stage"), stage(e["op_name"])
        stage_s[st or "untagged"] = stage_s.get(st or "untagged", 0.0) + d
        if st and not own:
            key = f"{st}:{e['name']}"
            inferred[key] = inferred.get(key, 0.0) + d
        key = (f"{e['layer']}/{st}:{e['name']}" if st
               else f"{e['layer']}:{e['name']}")
        ops[key] = ops.get(key, 0.0) + d
    program = [(h["name"], h["start"], h["start"] + h["dur"]) for h in host
               if h["name"].startswith("fl.")]
    gaps = []
    for dvc in sorted({e["device"] for e in inside}) or ["-"]:
        iv = base.merge((max(e["start"], w0), min(e["start"] + e["dur"], w1))
                        for e in inside if e["device"] == dvc)
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        gaps += [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                 if b > a]

    chunks = [(h["start"], h["start"] + h["dur"]) for h in host
              if h["name"] == "bench.chunk"]

    def gap_name(a, b):
        mid = 0.5 * (a + b)
        name = next((n for n, s, e in program if s <= mid <= e), None)
        if name is not None:
            return f"host: inside run_scanned ({name})"
        if any(s <= mid <= e for s, e in chunks):
            return "host: inside run_scanned (dispatch, sync, logs)"
        return "host: between chunk calls"

    ns = 1e-9

    def top_of(times):
        return [[k, v / nd * ns] for k, v in
                sorted(times.items(), key=lambda kv: -kv[1])[:top]]

    out.update(
        stage_s={k: v / nd * ns for k, v in stage_s.items()},
        named_s=out["scope_s"],
        device_ops=top_of(ops), inferred_ops=top_of(inferred),
        idle_gaps=[[gap_name(a, b), g * ns] for g, a, b in
                   sorted(gaps, reverse=True)[:top]])
    return out


def traced_run(workload: str, bench: dict, seed: int,
               chunks: int | None = None, require_tpu: bool = True):
    """One traced window of ``chunks`` chunks (the traffic's
    ``trace_chunks``, the window ``run.py --trace 1`` traces, unless
    given) after a warm-up chunk. Returns (the reduced line, the tagged
    events, the host spans), or None with a reason when the machine
    cannot run the cell."""
    import jax

    import cell as cell_mod

    entry, config, traffic, _ = cell_mod.spec(workload, bench)
    chunks = chunks or traffic["trace_chunks"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < entry["chips"]):
        return None, f"needs {entry['chips']} TPU chip(s)", None
    cell = cell_mod.Cell(config, traffic, seed)
    c = cell.chunk
    cell.run_chunk(0)
    trace_dir = BENCH / "out" / "stages" / f"{workload}.{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in range(1, chunks + 1):
            with jax.profiler.TraceAnnotation("bench.chunk"):
                cell.run_chunk(k * c)
    jax.profiler.stop_trace()
    path = base.trace_file(str(trace_dir))
    hlo = cell.trainer.lower_scanned(c).compile().as_text()
    events, _ = base.device_events(path)
    if events and not any(e["op_name"] for e in events):
        events, _ = base.device_events(path, base.hlo_op_names(hlo))
    events, host = tag(events, hlo_stages(hlo)), host_spans(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    s = summarize(events, host)
    rounds = chunks * c
    per_round = lambda d: {k: 1e3 * v / rounds for k, v in d.items()}  # noqa
    line = dict(workload=workload, seed=seed, rounds=rounds,
                device=dict(kind=devices[0].device_kind, count=s["devices"],
                            busy_s=s["busy_s"], window_s=s["window_s"]),
                traced_rounds_per_s=rounds / s["window_s"] if s["window_s"]
                else None,
                idle_share=(100.0 * (1.0 - s["busy_s"] / s["window_s"])
                            if s["window_s"] else None),
                stage_ms=per_round(s["stage_s"]),
                named_ms=per_round(s["named_s"]),
                layer_ms=per_round(s["layer_s"]),
                idle_gaps=s["idle_gaps"], device_ops=s["device_ops"],
                inferred_ops=s["inferred_ops"])
    return line, events, host


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--events", default=None)
    args = p.parse_args(argv)
    import jax

    import run
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    run.use_compile_cache()
    # the compilation cache keys a program without its debug info, so a
    # round program that differs from a cached one only in its scopes
    # would load that executable, and with it the op names it was
    # compiled with (none under ``fl.*`` if it was compiled before the
    # scopes): key by the metadata too, so the executable that runs is
    # compiled from this program's name stacks
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    line, events, host = traced_run(args.workload, bench, args.seed)
    if line is None:
        print(f"bench/stages.py: {events}", file=sys.stderr)
        return 3
    if args.events:
        rec = dict(source=f"{line['device']['kind']}: {args.workload}, "
                   f"seed {args.seed}, {line['rounds']} rounds",
                   events=events, host=host)
        with gzip.open(args.events, "wt") as f:
            json.dump(rec, f)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
