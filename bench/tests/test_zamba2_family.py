"""CPU tests of the Zamba2 LoRA family (``bench/families/zamba2``) at a
tiny size: a run through ``run.run_cell`` is ``correct``, the
``half_batch`` fault and the bfloat16 control are not, ``mfu`` takes the
family's FLOPs, ``flops.py`` agrees with XLA's count of the reference's
step, the program's ``fl.mamba`` and ``fl.shared_block`` scopes reach
``scope_s`` and the two readers, and the configuration file states the
catalog's numbers.

    PYTHONPATH=src python -m pytest bench/tests/test_zamba2_family.py
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import cell as cell_mod  # noqa: E402
import family  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "zamba2-lora-n20.fairenergy"
SEED = 2 ** 31 + 2718
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            attention_head_dim=32, attention_hidden_size=128,
            n_mamba_heads=8, mamba_headdim=16, mamba_d_state=16,
            intermediate_size=128, ffn_hidden_size=128, adapter_rank=8,
            vocab_size=256, chunk_size=16, seq_len=32, lora_rank=4,
            lora_alpha=8)


def tiny(config):
    """The configuration at a size a CPU test holds: every width cut,
    the 12-layer pattern, the two groups and the two shared blocks
    kept; 6 clients."""
    config = json.loads(json.dumps(config))
    config["model"].update(TINY)
    config["n_clients"] = 6
    config["data"].update(n_train=120, n_test=4, seq_len=32)
    return config


@pytest.fixture
def small(monkeypatch):
    import run
    orig = cell_mod.spec

    def spec(workload, bench):
        entry, config, traffic, limits = orig(workload, bench)
        return entry, tiny(config), traffic, limits
    monkeypatch.setattr(cell_mod, "spec", spec)
    run.use_compile_cache()
    return run


def test_sound_run_is_correct_and_its_mfu_is_the_familys(small, monkeypatch):
    run = small
    # the CPU this test runs on has no published peak: give it one
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["cpu"] = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e11)
    real = Path.read_text
    monkeypatch.setattr(Path, "read_text", lambda p, *a, **k: json.dumps(
        peaks) if p.name == "peaks.json" else real(p, *a, **k))
    res, notes = run.run_cell(CELL, BENCHMARK, SEED, 0.0, True,
                              require_tpu=False)
    assert res["correct"], notes["numbers"]
    assert res["failed"] == 0
    _, config, traffic, _ = cell_mod.spec(CELL, BENCHMARK)
    fam = family.load("zamba2", "flops")
    per_round = (6 * fam.train_flops(config["model"])
                 + 4 * fam.forward_flops(config["model"], 32) / 4)
    assert flops.round_flops(config, traffic) == per_round
    window = res["device"]["window_s"]
    assert res["metrics"]["mfu"]["value"] == pytest.approx(
        100 * per_round * res["attempted"] / window / 1e12, rel=1e-12)


def test_half_batch_fault_and_bf16_control_are_not_correct(small):
    import readings
    run = small
    with readings.planted("half_batch"):
        res, notes = run.run_cell(CELL, BENCHMARK, SEED, 0.0, False,
                                  require_tpu=False)
    assert not res["correct"], notes["numbers"]
    row = readings.control_reading(CELL, BENCHMARK, SEED)
    correct, _ = reference.judge(row["numbers"], row["finite"],
                                 cell_mod.load("limits", CELL))
    assert not correct, row["numbers"]


def test_flops_match_xla_count_of_the_reference_step():
    """``train_flops`` (no recomputation) plus the one forward the
    reference recomputes per layer, against XLA's count of the jitted
    step of one client. XLA counts the elementwise work too, so the
    widths here are the tiny ones but four times wider, where the matrix
    work is most of a step; the SSD is counted at the quadratic form the
    reference computes (chunk = sequence)."""
    import jax
    import jax.numpy as jnp
    config = tiny(cell_mod.load("configs", "zamba2-7b.lora.n20"))
    config["model"].update(hidden_size=256, attention_head_dim=128,
                           attention_hidden_size=512, mamba_headdim=64,
                           intermediate_size=512, ffn_hidden_size=512,
                           vocab_size=2048, chunk_size=32)
    m = config["model"]
    data = family.load("zamba2", "data").make_data(config, SEED)
    ref = family.load("zamba2", "reference")
    fam = family.load("zamba2", "flops")
    model = ref.Model(m, jnp.float32, jax.lax.Precision.DEFAULT)
    ad = ref.init_params(m, SEED)
    toks = jnp.asarray(data["tokens"][:1])
    step = jax.jit(jax.grad(model.loss)).lower(ad, data["base"], toks)
    xla = step.compile().cost_analysis()["flops"]
    want = fam.train_flops(m) + fam.forward_flops(m, 32)
    assert abs(xla - want) / want < 0.05, (xla, want)


def test_program_scopes_reach_the_readers(small):
    """The compiled round program's ops under ``fl.mamba`` and
    ``fl.shared_block`` come out of ``trace.summarize`` as ``scope_s``
    entries, and the two readers report them per round."""
    import run
    import trace as trace_mod
    _, config, traffic, _ = cell_mod.spec(CELL, BENCHMARK)
    c = cell_mod.Cell(config, traffic, SEED)
    names = trace_mod.hlo_op_names(c.trainer.lower_scanned(
        traffic["chunk_rounds"]).compile().as_text())
    scopes = {trace_mod.scope(n) for n in names.values()}
    assert {"mamba", "shared_block", "client_step", "eval"} <= scopes
    events = [dict(name=k, device="/device:TPU:0", start=float(i),
                   dur=1.0, op_name=v, layer=trace_mod.classify(v, k))
              for i, (k, v) in enumerate(sorted(names.items()))]
    summary = trace_mod.summarize(events, [])
    ctx = dict(trace=summary, rounds=4)
    for name, scope in (("mamba_ms", "mamba"),
                        ("shared_block_ms", "shared_block")):
        got = run.load_reader(name)(ctx)
        assert got == pytest.approx(1e3 * summary["scope_s"][scope] / 4)
        assert got > 0


def test_configuration_states_the_catalog_and_its_counts():
    config = cell_mod.load("configs", "zamba2-7b.lora.n20")
    model = config["model"]
    for k, v in model.items():
        if k in config:
            assert config[k] == v, k
    assert config["num_hidden_layers"] == 12
    assert config["hybrid_layer_ids"] == [6, 11] == [
        i for i, t in enumerate(config["layers_block_type"]) if t == "hybrid"]
    import jax
    ref = family.load("zamba2", "reference")
    shapes = jax.eval_shape(lambda: ref.init_params(model, 0))
    d = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(shapes))
    assert d == config["n_params"]["trained"] == 18_262_016
    base = jax.eval_shape(lambda: family.load("zamba2", "data").make_base(
        model, 0))
    assert sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(
        base)) == config["n_params"]["base"]
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[config["name"]]
    assert set(entry["reduced"]) == set(config["reduced"])
