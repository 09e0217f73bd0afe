"""CPU tests of the benchmark harness.

    PYTHONPATH=src python -m pytest bench/tests

They cover the trace reduction (on a trace recorded on a TPU v5e, kept
in ``bench/testdata``), the FLOP count, the discovery of configurations,
traffic mixes, limits and metric readers by name, the refusal to run
without a TPU, and the check that decides ``correct``: a sound run at a
small size passes it, and the lower-precision control and every planted
fault of the timed path fail it. The harness's look for a chip is
skipped there; everything else is a whole run.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import cell as cell_mod  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402
import trace as trace_mod  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
TESTDATA = BENCH / "testdata"
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


# ------------------------------------------------------------ trace ----
def _events():
    with gzip.open(TESTDATA / "trace_events.json.gz", "rt") as f:
        rec = json.load(f)
    return rec["events"], rec["host"]


def test_busy_union_and_idle_gaps_hand_case():
    ev = [dict(name="a", device="d", start=0.0, dur=10.0, op_name="", layer="rest"),
          dict(name="b", device="d", start=5.0, dur=10.0, op_name="", layer="rest"),
          dict(name="c", device="d", start=30.0, dur=5.0, op_name="", layer="eval")]
    host = [dict(name="bench.window", start=0.0, dur=40.0),
            dict(name="bench.chunk", start=0.0, dur=25.0)]
    s = trace_mod.summarize(ev, host)
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["window_s"] == pytest.approx(40e-9)
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx([15e-9, 5e-9])
    assert s["idle_gaps"][0][0].startswith("host: inside run_scanned")
    assert s["idle_gaps"][1][0] == "host: between chunk calls"
    assert s["layer_s"] == pytest.approx({"rest": 20e-9, "eval": 5e-9})


def test_recorded_trace_reduction():
    ev, host = _events()
    s = trace_mod.summarize(ev, host)
    assert 0 < s["busy_s"] <= s["window_s"]
    iv = trace_mod.merge((e["start"], e["start"] + e["dur"]) for e in ev)
    assert all(a[1] < b[0] for a, b in zip(iv, iv[1:]))
    assert sum(s["layer_s"].values()) >= s["busy_s"] * (1 - 1e-9)
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    # every layer of the round shows up by its name stack
    assert {"client_step", "decide", "eval", "rest"} <= set(s["layer_s"])


@pytest.mark.parametrize("op_name,layer", [
    ("jit(scan_body)/while/body/closed_call/jit(_solve_round)/while/body/add",
     "decide"),
    ("jit(scan_body)/while/body/vmap(transpose(jvp()))/conv_general_dilated",
     "client_step"),
    ("jit(scan_body)/while/body/cond/branch_1_fun/jit(eval_fn)/dot_general",
     "eval"),
    ("jit(scan_body)/while/body/dot_general", "rest"),
])
def test_classify_by_name_stack(op_name, layer):
    assert trace_mod.classify(op_name) == layer


def test_recorded_trace_names_match_classify():
    ev, _ = _events()
    for e in ev:
        assert e["layer"] == trace_mod.classify(e["op_name"], e["name"])


# ------------------------------------------------------------ flops ----
def test_cnn_flops_by_hand():
    import family
    cnn = family.load("cnn", "flops")
    model = cell_mod.load("configs", "fmnist-cnn.n50")["model"]
    conv0 = 28 * 28 * 32 * (3 * 3 * 1)
    conv1 = 14 * 14 * 64 * (3 * 3 * 32)
    fc1, fc2 = 7 * 7 * 64 * 512, 512 * 10
    fwd = 2 * (conv0 + conv1 + fc1 + fc2)
    assert cnn.forward_flops(model) == fwd == 10_898_432
    # forward + weight grads + input grads of all layers but the first
    assert cnn.train_flops(model) == fwd + fwd + 2 * (conv1 + fc1 + fc2)
    config = cell_mod.load("configs", "fmnist-cnn.n50")
    traffic = cell_mod.load("traffic", "fairenergy")
    assert flops.round_flops(config, traffic) == pytest.approx(
        50 * 2 * 64 * cnn.train_flops(model) + 10_000 * fwd / 10)
    eco = cell_mod.load("traffic", "ecorandom")
    assert flops.round_flops(config, eco) == pytest.approx(
        16 * 2 * 64 * cnn.train_flops(model) + 10_000 * fwd / 10)


def test_param_count_matches_config():
    config = cell_mod.load("configs", "fmnist-cnn.n50")
    import jax
    import numpy as np
    p = jax.eval_shape(lambda: reference.init_params(config["model"], 0))
    n = sum(int(np.prod(v.shape)) for v in reference.leaves(p))
    assert n == config["model"]["n_params"] == 1_630_090


# -------------------------------------------------------- discovery ----
@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves_by_name(workload):
    entry, config, traffic, limits = cell_mod.spec(workload, BENCHMARK)
    assert config["name"] == entry["config"]
    assert traffic["chunk_rounds"] > 0
    assert set(limits) <= {"loss0_gap", "loss_gap", "loss_last_gap",
                           "param_gap",
                           "energy_gap", "bandwidth_gap", "select_mismatch",
                           "acc_gap"}


def test_every_metric_has_a_reader():
    import run
    for m in BENCHMARK["per_layer"]:
        assert callable(run.load_reader(m["name"]))
    names = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert names == {m["name"] for m in BENCHMARK["per_layer"]}


def test_readers_return_nothing_without_a_trace():
    import run
    ctx = dict(trace=None, rounds=0, chips=1, flops_per_round=1.0,
               peak={"bf16_flops_per_s": 1.0}, window_compiles=0)
    for m in BENCHMARK["per_layer"]:
        if m["name"] != "window_compiles":
            assert run.load_reader(m["name"])(ctx) is None


def test_an_added_traffic_file_is_found_without_edits(tmp_path, monkeypatch):
    copy = tmp_path / "bench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("out"))
    mix = dict(cell_mod.load("traffic", "fairenergy"), local_steps=3)
    (copy / "traffic" / "fairenergy-s3.json").write_text(json.dumps(mix))
    (copy / "limits" / "cnn-n50.fairenergy-s3.json").write_text(
        (BENCH / "limits" / "cnn-n50.fairenergy.json").read_text())
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append(dict(name="cnn-n50.fairenergy-s3",
                                   config="fmnist-cnn.n50",
                                   traffic="fairenergy-s3", chips=1, why="t"))
    monkeypatch.setattr(cell_mod, "BENCH", copy)
    _, _, traffic, _ = cell_mod.spec("cnn-n50.fairenergy-s3", bench)
    assert traffic["local_steps"] == 3


def test_peaks_are_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_data_is_made_from_the_seed():
    import data
    config = dict(cell_mod.load("configs", "fmnist-cnn.n50"))
    config["data"] = dict(config["data"], n_train=3000, n_test=500)
    a, b = data.make(config, 2 ** 31 + 11), data.make(config, 2 ** 31 + 11)
    c = data.make(config, 7)
    assert (a["images"] == b["images"]).all()
    assert not (a["images"] == c["images"]).all()
    # the fleet is the configuration's: the same shards for every seed
    assert all((p == q).all() for p, q in zip(a["parts"], c["parts"]))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "cnn-n50.fairenergy", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# ------------------------------------------------------ correctness ----
def _tiny(monkeypatch):
    """Every cell at a size a CPU test holds: 6 clients, narrow CNN."""
    orig = cell_mod.spec

    def spec(workload, bench):
        entry, config, traffic, limits = orig(workload, bench)
        config = json.loads(json.dumps(config))
        config["n_clients"] = 6
        config["model"].update(cnn_channels=[8, 16], cnn_dense=64)
        config["data"].update(n_train=1200, n_test=500)
        if "fixed_k" in traffic:
            traffic = dict(traffic, fixed_k=3)
        return entry, config, traffic, limits
    monkeypatch.setattr(cell_mod, "spec", spec)


@pytest.fixture(scope="module")
def runner():
    import run
    run.use_compile_cache()
    return run


SEED = 2 ** 31 + 1234


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(runner, monkeypatch, workload):
    _tiny(monkeypatch)
    res, notes = runner.run_cell(workload, BENCHMARK, SEED, 0.5, False,
                                 require_tpu=False)
    assert res["correct"], notes["numbers"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 10 and res["failed"] == 0


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "decision_altered", "update_altered"])
def test_planted_fault_is_not_correct(runner, monkeypatch, fault, workload):
    import readings
    _tiny(monkeypatch)
    with readings.planted(fault):
        res, notes = runner.run_cell(workload, BENCHMARK, SEED, 0.5, False,
                                     require_tpu=False)
    assert not res["correct"], (fault, notes["numbers"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bf16_control_is_not_correct(runner, monkeypatch, workload):
    import readings
    _tiny(monkeypatch)
    row = readings.control_reading(workload, BENCHMARK, SEED)
    limits = cell_mod.load("limits", workload)
    correct, _ = reference.judge(row["numbers"], row["finite"], limits)
    assert not correct, row["numbers"]
