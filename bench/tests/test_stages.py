"""CPU tests of the reduction of a trace by the round's stages.

    PYTHONPATH=src python -m pytest bench/tests

They cover ``stage`` on name stacks, ``hlo_stages`` on a hand-written
compiled module, the stage times and idle-gap names of ``stages.
summarize`` on hand cases, and two recorded traces: the round program
before it named its stages (``trace_events.json.gz``, whose per-layer
times must not move) and one chunk's tail per cell of the program with
its ``fl.*`` scopes and spans, recorded on a TPU v5e
(``stages.<cell>.json.gz``).
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import stages  # noqa: E402
import trace as trace_mod  # noqa: E402

TESTDATA = BENCH / "testdata"
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


# the share of busy time that no op's own name stack puts in a stage:
# copies, pads and ops that XLA's rewriters made, with no metadata
# (PERF.md § 5). The recordings read 0.135-0.146.
NAMED_UNTAGGED_SHARE = 0.16


def _load(name):
    with gzip.open(TESTDATA / name, "rt") as f:
        rec = json.load(f)
    return rec["events"], rec["host"]


# ------------------------------------------------------------ stage ----
@pytest.mark.parametrize("op_name,layer,stage", [
    # the name stacks of the round program before it named its stages
    ("jit(scan_body)/while/body/closed_call/jit(_solve_round)/while/body/add",
     "decide", None),
    ("jit(scan_body)/while/body/vmap(transpose(jvp()))/conv_general_dilated",
     "client_step", None),
    ("jit(scan_body)/while/body/cond/branch_1_fun/jit(eval_fn)/dot_general",
     "eval", None),
    ("jit(scan_body)/while/body/dot_general", "rest", None),
    # the same ops under the program's scopes: the layer does not move
    ("jit(scan_body)/while/body/closed_call/fl.decide/jit(_solve_round)/"
     "while/body/add", "decide", "decide"),
    ("jit(scan_body)/while/body/closed_call/fl.client_step/"
     "vmap(transpose(jvp()))/conv_general_dilated", "client_step",
     "client_step"),
    ("jit(scan_body)/while/body/closed_call/fl.eval/cond/branch_1_fun/"
     "jit(eval_fn)/dot_general", "eval", "eval"),
    ("jit(scan_body)/while/body/closed_call/fl.aggregate/dot_general",
     "rest", "aggregate"),
    ("jit(scan_body)/while/body/closed_call/fl.sparsify/cond/branch_1_fun/"
     "while/body/closed_call/reduce_sum", "rest", "sparsify"),
    ("jit(scan_body)/while/body/closed_call/fl.sample/jit(_take)/gather",
     "rest", "sample"),
    ("jit(scan_body)/while/body/closed_call/fl.client_step/vmap()/"
     "reduce_sum;vmap()/sqrt", "rest", "client_step"),
    ("", "rest", None),
])
def test_stage_by_name_stack(op_name, layer, stage):
    assert stages.stage(op_name) == stage
    assert trace_mod.classify(op_name) == layer


def test_every_stage_name_is_free_of_layer_keys():
    for s in stages.STAGES:
        assert trace_mod.classify(f"jit(scan_body)/fl.{s}/add") == "rest"


HLO = """\
%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(f32[4]{0} %param_0.1, f32[4]{0} %param_0.1), metadata={op_name="jit(f)/fl.sparsify/mul"}
}

%body.2 (arg.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.2 = (s32[], f32[4]{0:T(128)}) parameter(0)
  %gte.3 = f32[4]{0:T(128)} get-tuple-element(%arg.2), index=1
  %copy.4 = f32[4]{0:T(128)} copy(%gte.3)
  %fusion.6 = f32[4]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1
  %gte.5 = s32[] get-tuple-element(%arg.2), index=0
  ROOT %tuple.7 = (s32[], f32[4]{0}) tuple(%gte.5, %fusion.6)
}

%cond.3 (arg.3: (s32[], f32[4])) -> pred[] {
  %arg.3 = (s32[], f32[4]{0}) parameter(0)
  %gte.8 = s32[] get-tuple-element(%arg.3), index=0
  %constant.9 = s32[] constant(2)
  ROOT %lt.10 = pred[] compare(%gte.8, %constant.9), direction=LT
}

ENTRY %main.9 (p.0: f32[4]) -> f32[4] {
  %p.0 = f32[4]{0} parameter(0)
  %copy.1 = f32[4]{0} copy(%p.0)
  %add.2 = f32[4]{0} add(%copy.1, %copy.1), metadata={op_name="jit(f)/fl.sample/add"}
  %copy-start.3 = (f32[4]{0}, f32[4]{0:S(1)}, u32[]) copy-start(%add.2)
  %copy-done.4 = f32[4]{0:S(1)} copy-done(%copy-start.3)
  %fusion.5 = f32[4]{0} fusion(%copy-done.4), kind=kLoop, calls=%fused_computation.1
  %constant.11 = s32[] constant(0)
  %tuple.12 = (s32[], f32[4]{0}) tuple(%constant.11, %fusion.5)
  %while.6 = (s32[], f32[4]{0}) while(%tuple.12), condition=%cond.3, body=%body.2, metadata={op_name="jit(f)/while"}
  ROOT %gte.13 = f32[4]{0} get-tuple-element(%while.6), index=1
}
"""


def test_hlo_stages_of_ops_the_compiler_made():
    st = stages.hlo_stages(HLO)
    assert st["add.2"] == "sample"             # its own scope
    assert st["copy.1"] == "sample"            # a copy of an argument: user
    assert st["copy-start.3"] == st["copy-done.4"] == "sample"  # operand
    assert st["fusion.5"] == st["fusion.6"] == "sparsify"       # its root
    assert st["param_0.1"] == "sample"         # the fusion's operand
    assert st["copy.4"] == "sparsify"          # a copy of the loop carry
    assert st["while.6"] is None               # control keeps its own


# -------------------------------------------------------- summarize ----
def _hand():
    ev = [dict(name="a", device="d", start=0.0, dur=10.0, op_name="",
               layer="rest", stage="sparsify"),
          dict(name="b", device="d", start=10.0, dur=10.0,
               op_name="jit(f)/fl.client_step/vmap(jvp())/conv",
               layer="client_step", stage="client_step"),
          dict(name="c", device="d", start=32.0, dur=3.0, op_name="",
               layer="eval", stage=None),
          dict(name="e", device="d", start=45.0, dur=10.0, op_name="",
               layer="rest")]
    host = [dict(name="bench.window", start=0.0, dur=60.0),
            dict(name="bench.chunk", start=0.0, dur=38.0),
            dict(name="fl.dispatch", start=0.0, dur=1.0),
            dict(name="fl.sync", start=1.0, dur=16.0),
            dict(name="fl.logs", start=17.0, dur=12.0),
            dict(name="bench.chunk", start=47.0, dur=13.0)]
    return ev, host


def test_gaps_are_named_by_the_span_they_fall_in():
    ev, host = _hand()
    s = stages.summarize(ev, host)
    gaps = {round(g / 1e-9): name for name, g in s["idle_gaps"]}
    assert gaps == {12: "host: inside run_scanned (fl.logs)",
                    10: "host: between chunk calls",
                    5: "host: inside run_scanned (dispatch, sync, logs)"}
    base = trace_mod.summarize(ev, host)
    assert [g for _, g in s["idle_gaps"]] == [g for _, g in base["idle_gaps"]]


def test_stage_times_sum_to_busy_and_layers_do_not_move():
    ev, host = _hand()
    s = stages.summarize(ev, host)
    assert s["stage_s"] == pytest.approx(
        {"sparsify": 10e-9, "client_step": 10e-9, "untagged": 13e-9})
    assert sum(s["stage_s"].values()) == pytest.approx(s["busy_s"])
    # by the ops' own name stacks alone, and what only the program placed
    assert s["named_s"] == pytest.approx(
        {"client_step": 10e-9, "untagged": 23e-9})
    assert s["inferred_ops"] == [["sparsify:a", pytest.approx(10e-9)]]
    base = trace_mod.summarize(ev, host)
    assert s["layer_s"] == base["layer_s"]
    assert (s["busy_s"], s["window_s"]) == (base["busy_s"], base["window_s"])
    assert [k for k, _ in s["device_ops"]] == [
        "rest/sparsify:a", "client_step/client_step:b", "rest:e", "eval:c"]


def test_recorded_trace_before_the_scopes_reads_as_before():
    """The trace recorded before the program named its stages: every op
    is untagged, and the per-layer times read what they read when it was
    recorded (ms: rest 113.626456, decide 1.159498, client step
    72.911849, eval 4.936307)."""
    ev, host = _load("trace_events.json.gz")
    s = stages.summarize(ev, host)
    assert s["layer_s"] == pytest.approx(
        {"rest": 0.113626456, "decide": 0.001159498,
         "client_step": 0.072911849, "eval": 0.004936307}, rel=1e-9)
    assert s["layer_s"] == trace_mod.summarize(ev, host)["layer_s"]
    assert s["stage_s"] == pytest.approx({"untagged": s["busy_s"]})
    assert s["named_s"] == s["stage_s"] and s["inferred_ops"] == []


@pytest.mark.parametrize("workload", WORKLOADS)
def test_recorded_chunk_has_every_stage(workload):
    """Both readings of a recorded chunk: by the ops' own name stacks
    (``named_s``), and with the ops the compiler made placed by the
    program (``stage_s``), which only adds to a stage."""
    ev, host = _load(f"stages.{workload}.json.gz")
    s = stages.summarize(ev, host)
    busy = s["busy_s"]
    for reading in ("stage_s", "named_s"):
        assert set(stages.STAGES) <= set(s[reading])
        assert sum(s[reading].values()) == pytest.approx(busy, rel=1e-9)
    assert s["stage_s"].get("untagged", 0.0) < 0.02 * busy
    assert s["named_s"]["untagged"] < NAMED_UNTAGGED_SHARE * busy
    for st in stages.STAGES:
        assert s["named_s"][st] <= s["stage_s"][st] * (1 + 1e-12)
    for e in ev:
        assert stages.stage(e["op_name"]) in (None, e["stage"])
    # the top-k's prefix sum is named by its scope, not placed (the ops
    # that XLA's rewriter splits off it name only ``reduce_window_sum``)
    prefix = [e for e in ev if e["op_name"].endswith("/reduce_window_sum")]
    assert all(stages.stage(e["op_name"]) == "sparsify" for e in prefix)
    assert prefix or workload == "cnn-n50.scoremax"   # its top-k is skipped
    assert s["layer_s"] == trace_mod.summarize(ev, host)["layer_s"]
    names = {h["name"] for h in host}
    assert set(stages.SPANS) <= names
    for e in ev:
        assert e["layer"] == trace_mod.classify(e["op_name"], e["name"])


# ---------------------------------------------------------- scope_s ----
# what the recordings read before ``trace.summarize`` gave ``scope_s``
# (seconds): ``stages.summarize``'s ``named_s`` and the per-layer times
PARENT_NAMED = {
    "cnn-n50.fairenergy": {
        "aggregate": 0.0018567680000000002, "client_step": 0.081695215,
        "decide": 0.000794607, "eval": 0.004939095, "sample": 0.01083626,
        "sparsify": 0.06634166500000001, "untagged": 0.028534943},
    "cnn-n50.ecorandom": {
        "aggregate": 0.0018563000000000002, "client_step": 0.08071409,
        "decide": 8.79e-06, "eval": 0.004937913, "sample": 0.010837661,
        "sparsify": 0.06809657100000001, "untagged": 0.028546526000000003},
    "cnn-n50.scoremax": {
        "aggregate": 0.002475291, "client_step": 0.11881206000000001,
        "decide": 1.2076000000000001e-05, "eval": 0.004939023000000001,
        "sample": 0.014466245, "sparsify": 0.028034343000000003,
        "untagged": 0.026259547},
}
PARENT_LAYERS = {
    "cnn-n50.fairenergy": {
        "client_step": 0.08021589200000001, "decide": 0.000794607,
        "eval": 0.004936542, "rest": 0.109051512},
    "cnn-n50.ecorandom": {
        "client_step": 0.080201719, "eval": 0.004935223000000001,
        "rest": 0.109860909},
    "cnn-n50.scoremax": {
        "client_step": 0.116669118, "eval": 0.004935065000000001,
        "rest": 0.07339440200000001},
    "trace_events": {
        "client_step": 0.072911849, "decide": 0.0011594980000000001,
        "eval": 0.004936307, "rest": 0.113626456},
}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_scope_s_is_named_s_stage_by_stage(workload):
    """The device time per scope that ``trace.summarize`` hands the
    metric readers is ``bench/stages.py``'s reading by each op's own name
    stack, and the per-layer times do not move."""
    ev, host = _load(f"stages.{workload}.json.gz")
    s = trace_mod.summarize(ev, host)
    assert s["scope_s"] == stages.summarize(ev, host)["named_s"]
    assert s["scope_s"] == PARENT_NAMED[workload]
    assert s["layer_s"] == PARENT_LAYERS[workload]


def test_layer_s_of_the_trace_before_the_scopes_is_the_parents():
    ev, host = _load("trace_events.json.gz")
    s = trace_mod.summarize(ev, host)
    assert s["layer_s"] == PARENT_LAYERS["trace_events"]
    assert s["scope_s"] == {"untagged": pytest.approx(s["busy_s"])}


def test_traced_run_records_the_program_spans(monkeypatch):
    """A traced window at a size a CPU test holds: no TPU events, but
    the benchmark's and the program's host spans, in order."""
    import cell as cell_mod
    import run
    orig = cell_mod.spec

    def tiny(workload, bench):
        entry, config, traffic, limits = orig(workload, bench)
        config = json.loads(json.dumps(config))
        config["n_clients"] = 6
        config["model"].update(cnn_channels=[8, 16], cnn_dense=64)
        config["data"].update(n_train=1200, n_test=500)
        return entry, config, dict(traffic, fixed_k=3), limits
    monkeypatch.setattr(cell_mod, "spec", tiny)
    run.use_compile_cache()
    line, events, host = stages.traced_run(
        "cnn-n50.scoremax", BENCHMARK, 2 ** 31 + 99, 2, require_tpu=False)
    assert line["rounds"] == 20 and events == []
    assert line["stage_ms"] == line["named_ms"] == {}
    names = [h["name"] for h in sorted(host, key=lambda h: h["start"])]
    assert names == ["bench.window"] + ["bench.chunk", *stages.SPANS] * 2
