"""Reduction of a profiler trace to device events, busy time, idle gaps
and per-layer device time.

``device_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes;
everything after it works on plain event lists, so it can be checked on
a small recorded trace (``bench/testdata``). Each device op is given the
layer of the round it belongs to by the JAX name stack of its HLO
``op_name`` (``classify``), and the scope the program named it under:
the innermost ``fl.<scope>`` of that name stack (``scope``).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
SCOPE = re.compile(r"(?<![\w.])fl\.([a-z_]+)")


def classify(op_name: str, hlo_op: str = "") -> str:
    """The round's layer of one device op, by its name stack."""
    if hlo_op.startswith(COLLECTIVE_PREFIXES):
        return "collective"
    if "eval_fn" in op_name:
        return "eval"
    if "_solve_round" in op_name:
        return "decide"
    if "jvp(" in op_name or "transpose(" in op_name:
        return "client_step"
    return "rest"


def scope(op_name: str):
    """The innermost ``fl.<scope>`` of an op's name stack (``sparsify``
    for ``.../fl.sparsify/cond/...``), or None outside every such
    scope."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=(.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply)=%([^\s,)]+)")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")


def instruction_name(text: str) -> str:
    """``while.112`` from a trace event named by its HLO text
    (``%while.112 = (s32[], ...) while(...)``), or the name itself."""
    m = INSTR.match(text)
    return m.group(1) if m else text


def hlo_op_names(hlo_text: str) -> dict:
    """HLO instruction name -> the JAX name stack (``op_name``) of a
    compiled module's text. A fusion without metadata of its own takes
    the first name stack inside the computation it calls."""
    own, calls, first = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
            if comp is not None:
                first.setdefault(comp, op.group(1))
        callee = CALLS.search(rest)
        if callee:
            calls[name] = callee.group(1)
    out = dict(own)
    for name, callee in calls.items():
        if name not in out and callee in first:
            out[name] = first[callee]
    return out


def trace_file(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def device_events(path: str, op_names: dict | None = None):
    """(device op events, host spans) of an ``.xplane.pb``.

    Device events are dicts ``name, device, start, dur, op_name, layer``
    (nanoseconds); host spans are ``name, start, dur`` of the host's
    trace annotations."""
    from jax.profiler import ProfileData
    op_names = op_names or {}
    dev, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    hlo = instruction_name(str(stats.get("hlo_op", e.name)))
                    op = str(stats.get("tf_op") or stats.get("op_name")
                             or op_names.get(hlo, ""))
                    dev.append(dict(name=hlo, device=plane.name,
                                    start=float(e.start_ns),
                                    dur=float(e.duration_ns), op_name=op,
                                    layer=classify(op, hlo)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(dict(name=e.name, start=float(e.start_ns),
                                         dur=float(e.duration_ns)))
    return dev, host


def exclusive_times(events, w0, w1) -> list:
    """Each event's own device time inside [w0, w1): its duration less
    the time of the events it encloses on the same device (a ``while``
    or ``conditional`` and the ops it runs). They sum to the busy time,
    so nothing is counted twice."""
    clip = lambda s, e: (max(s, w0), min(e, w1))
    end = lambda j: events[j]["start"] + events[j]["dur"]
    children = {}
    by_dev = {}
    for i, e in enumerate(events):
        by_dev.setdefault(e["device"], []).append(i)
    for idx in by_dev.values():
        idx.sort(key=lambda i: (events[i]["start"], -events[i]["dur"]))
        stack = []
        for i in idx:
            while stack and end(stack[-1]) <= events[i]["start"]:
                stack.pop()
            if stack and end(i) <= end(stack[-1]) + 1.0:
                children.setdefault(stack[-1], []).append(i)
            stack.append(i)
    out = []
    for i, e in enumerate(events):
        s, t = clip(e["start"], end(i))
        own = max(t - s, 0.0)
        kids = merge(clip(events[k]["start"], end(k))
                     for k in children.get(i, ()))
        out.append(max(own - sum(max(b - a, 0.0) for a, b in kids), 0.0))
    return out


def merge(intervals):
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(events, host, window=None, top=10):
    """Busy and idle time, per-layer device time (``layer_s``), device
    time per scope (``scope_s``: by each op's own name stack, ``untagged``
    for ops under no ``fl.*`` scope) and the breakdown, over the window
    (the ``bench.window`` host span, else the span of the device events).
    Device times are exclusive and averaged over the devices seen.
    Seconds throughout."""
    if window is None:
        spans = [h for h in host if h["name"] == "bench.window"]
        if spans:
            window = (spans[0]["start"], spans[0]["start"] + spans[0]["dur"])
        elif not events:
            window = (0.0, 0.0)
        else:
            window = (min(e["start"] for e in events),
                      max(e["start"] + e["dur"] for e in events))
    w0, w1 = window
    inside = [e for e in events if e["start"] < w1 and e["start"] + e["dur"] > w0]
    devices = sorted({e["device"] for e in inside}) or ["-"]
    nd = len(devices)
    busy, gaps = 0.0, []
    for dvc in devices:
        iv = merge((max(e["start"], w0), min(e["start"] + e["dur"], w1))
                   for e in inside if e["device"] == dvc)
        busy += sum(e - s for s, e in iv)
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        gaps += [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                 if b > a]
    layer, scopes, ops = {}, {}, {}
    for e, d in zip(inside, exclusive_times(inside, w0, w1)):
        layer[e["layer"]] = layer.get(e["layer"], 0.0) + d
        sc = scope(e["op_name"]) or "untagged"
        scopes[sc] = scopes.get(sc, 0.0) + d
        key = f"{e['layer']}:{e['name']}"
        ops[key] = ops.get(key, 0.0) + d
    chunks = [(h["start"], h["start"] + h["dur"]) for h in host
              if h["name"] == "bench.chunk"]

    def gap_name(a, b):
        mid = 0.5 * (a + b)
        if any(s <= mid <= e for s, e in chunks):
            return "host: inside run_scanned (dispatch, sync, logs)"
        return "host: between chunk calls"

    ns = 1e-9
    return dict(
        window_s=(w1 - w0) * ns, busy_s=busy / nd * ns, devices=nd,
        layer_s={k: v / nd * ns for k, v in layer.items()},
        scope_s={k: v / nd * ns for k, v in scopes.items()},
        device_ops=[[k, v / nd * ns] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[gap_name(a, b), g * ns] for g, a, b in
                   sorted(gaps, reverse=True)[:top]],
        n_events=len(inside))
