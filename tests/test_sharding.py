"""Sharding-rule unit tests + a small-mesh dry-run in a subprocess (the
512-device placeholder env must not leak into other tests)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke
from repro.launch import steps as steps_mod
from repro.sharding import batch_axes, param_specs

REPO = os.path.join(os.path.dirname(__file__), "..")


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})


def _spec_of(specs, *path):
    node = specs
    for k in path:
        node = node[k]
    return node


def test_dense_param_rules():
    cfg = get_config("tinyllama-1.1b")
    specs = param_specs(steps_mod.params_shape(cfg), MESH)
    assert _spec_of(specs, "layers", "attn", "wq", "w") == P(None, "data", "model")
    assert _spec_of(specs, "layers", "attn", "wo", "w") == P(None, "model", "data")
    assert _spec_of(specs, "layers", "mlp", "down", "w") == P(None, "model", "data")
    # embedding: vocab on model, d_model replicated (see specs.py comment)
    assert _spec_of(specs, "embed", "table") == P("model", None)
    assert _spec_of(specs, "layers", "ln1", "scale") == P(None)


def test_whisper_nondivisible_fallback():
    cfg = get_config("whisper-tiny")   # 6 heads, vocab 51865 — not /16
    specs = param_specs(steps_mod.params_shape(cfg), MESH)
    # head dim = 6*64=384 divides 16? 384/16=24 -> sharded; vocab 51865 doesn't
    assert _spec_of(specs, "tok_embed", "table")[0] is None
    # d_ff 1536 divides -> mlp fc1 out sharded
    assert _spec_of(specs, "dec_layers", "mlp", "fc1", "w") == P(None, "data", "model")


def test_moe_expert_rules():
    cfg = get_config("qwen2-moe-a2.7b")
    specs = param_specs(steps_mod.params_shape(cfg), MESH)
    moe = _spec_of(specs, "layers", "moe")
    assert moe["w_gate"] == P(None, None, "data", "model")
    assert moe["w_down"] == P(None, None, "model", "data")


def test_batch_axes_divisibility():
    assert batch_axes(MESH, 256) == ("data",)
    assert batch_axes(MESH, 1) is None
    multi = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert batch_axes(multi, 256) == ("pod", "data")
    assert batch_axes(multi, 16) is None or batch_axes(multi, 16) == ("pod",)


@pytest.mark.slow
def test_dryrun_subprocess_smoke():
    """Run the real dryrun CLI for one cheap combo (spawns its own 512-dev
    placeholder backend)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # placeholder host devices, never a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper-tiny",
         "--shape", "decode_32k", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    artifact = "/tmp/dryrun_test/whisper-tiny__decode_32k__single.json"
    with open(artifact) as f:
        res = json.load(f)
    assert res["n_devices"] == 256
    assert res["flops_per_device"] > 0
