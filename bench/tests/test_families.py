"""CPU tests of the model families: a configuration's model is found by
the ``model.family`` key of its file, and the harness names no model.

    PYTHONPATH=src python -m pytest bench/tests

They pin the CNN family to what the harness computed before the family
modules existed (data, weights, FLOPs and two reference rounds, read at
that commit), check that no family's reference half imports the
program, and add a configuration of another model, a toy token-stream
family, as new files in a copy of the harness, which it runs end to end
without an edit.
"""
from __future__ import annotations

import ast
import hashlib
import json
import shutil
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
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return (hashlib.sha256(a.tobytes()).hexdigest()[:16]
            + f":{a.dtype}:{a.shape}")


def leaf_digests(p) -> dict:
    return {k: digest(v) for k, v in zip(_names(p), reference.leaves(p))}


def _names(p, prefix=""):
    if isinstance(p, dict):
        return [n for k in sorted(p) for n in _names(p[k], f"{prefix}{k}/")]
    return [prefix[:-1]]


# ------------------------------------------- the CNN family is the parent's ----
# read with the harness as it was before the family modules, at seed
# 2**31 + 11 (data and weights at the configuration's full size)
PARENT_DATA = {
    "images": "2ce081ba237cad42:float32:(60000, 28, 28, 1)",
    "labels": "ac8365eb91a8dcb4:int32:(60000,)",
    "test_images": "8c90d2f093a31e27:float32:(10000, 28, 28, 1)",
    "test_labels": "227a8c01e81d500e:int32:(10000,)",
    "parts": "8f1ea77e00eec9e4:int64:(60000,)",
    "part_sizes": "6d3cf7fdf4a5e7c7:int64:(50,)",
}
PARENT_PARAMS = {
    "conv0/b": "38723a2e5e8a17aa:float32:(32,)",
    "conv0/w": "1971cc6a4860e098:float32:(3, 3, 1, 32)",
    "conv1/b": "5341e6b2646979a7:float32:(64,)",
    "conv1/w": "ff0fb7b4c9adcaf5:float32:(3, 3, 32, 64)",
    "fc1/b": "e5a00aa9991ac8a5:float32:(512,)",
    "fc1/w": "3503f66567d650b3:float32:(3136, 512)",
    "fc2/b": "2c34ce1df23b838c:float32:(10,)",
    "fc2/w": "4a3145bdba2ccef1:float32:(512, 10)",
}
PARENT_ROUND_FLOPS = {"cnn-n50.fairenergy": 217258188800.0,
                      "cnn-n50.ecorandom": 76933554176.0,
                      "cnn-n50.scoremax": 217258188800.0}
# ``reference.follow`` free-running for 2 rounds of a small copy of the
# configuration (``_small``) at seed 2**31 + 1234: losses, accuracies and
# the final parameters
PARENT_FOLLOW = {
    "cnn-n50.fairenergy": dict(
        loss=[2.0034306049346924, 1.9842872619628906],
        acc=[0.0820000022649765, 0.12600000202655792],
        params={"conv0/b": "cd3517fbf6fff16a:float32:(8,)",
                "conv0/w": "d7aadcb8f9d5ccea:float32:(3, 3, 1, 8)",
                "conv1/b": "a031bf53b36daafd:float32:(16,)",
                "conv1/w": "521fe55039b0404f:float32:(3, 3, 8, 16)",
                "fc1/b": "b2ad72fff25990dd:float32:(64,)",
                "fc1/w": "22c9208d6a6a0f96:float32:(784, 64)",
                "fc2/b": "ebfe627957bc01df:float32:(10,)",
                "fc2/w": "0cf5522f8dfd65ed:float32:(64, 10)"}),
    "cnn-n50.ecorandom": dict(
        loss=[2.0034306049346924, 1.9222943782806396],
        acc=[0.1120000034570694, 0.11400000751018524],
        params={"conv0/b": "3563a1c0a7ae6284:float32:(8,)",
                "conv0/w": "55e82f961745f15b:float32:(3, 3, 1, 8)",
                "conv1/b": "baf48756b6b27048:float32:(16,)",
                "conv1/w": "4bc34cb4d2dffc70:float32:(3, 3, 8, 16)",
                "fc1/b": "9a89c9ac5f7c6d5c:float32:(64,)",
                "fc1/w": "bb833b8cebd74000:float32:(784, 64)",
                "fc2/b": "b32f6791d2aa0bcc:float32:(10,)",
                "fc2/w": "eb0e94b581e8ad45:float32:(64, 10)"}),
    "cnn-n50.scoremax": dict(
        loss=[2.0034306049346924, 2.0337724685668945],
        acc=[0.09000000357627869, 0.08800000697374344],
        params={"conv0/b": "67bd523377e9ba7a:float32:(8,)",
                "conv0/w": "8ce3d1ec58f6b1b3:float32:(3, 3, 1, 8)",
                "conv1/b": "5d8c69a721794b80:float32:(16,)",
                "conv1/w": "58a14c4813018a65:float32:(3, 3, 8, 16)",
                "fc1/b": "a456e0fc89e74d8c:float32:(64,)",
                "fc1/w": "66944bdab8b60b72:float32:(784, 64)",
                "fc2/b": "f18ed5a2dea4a91a:float32:(10,)",
                "fc2/w": "dbd1291a87165c17:float32:(64, 10)"}),
}


def test_cnn_data_and_weights_are_the_parents():
    config = cell_mod.load("configs", "fmnist-cnn.n50")
    data, params0 = cell_mod.inputs(config, 2 ** 31 + 11)
    got = {k: digest(v) for k, v in data.items() if k != "parts"}
    got["parts"] = digest(np.concatenate(data["parts"]))
    got["part_sizes"] = digest([len(p) for p in data["parts"]])
    assert got == PARENT_DATA
    assert leaf_digests(params0) == PARENT_PARAMS


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cnn_round_flops_are_the_parents(workload):
    _, config, traffic, _ = cell_mod.spec(workload, BENCHMARK)
    assert flops.round_flops(config, traffic) == PARENT_ROUND_FLOPS[workload]


def _small(workload):
    _, config, traffic, _ = cell_mod.spec(workload, BENCHMARK)
    config = json.loads(json.dumps(config))
    config["n_clients"] = 6
    config["model"].update(cnn_channels=[8, 16], cnn_dense=64)
    config["data"].update(n_train=1200, n_test=500)
    if "fixed_k" in traffic:
        traffic = dict(traffic, fixed_k=3)
    return config, traffic


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cnn_reference_rounds_are_the_parents(workload):
    config, traffic = _small(workload)
    data, params0 = cell_mod.inputs(config, 2 ** 31 + 1234)
    out = reference.follow(config, traffic, data, params0, 2)
    want = PARENT_FOLLOW[workload]
    assert out["loss"] == want["loss"]
    assert out["acc"] == want["acc"]
    assert leaf_digests(out["params"]) == want["params"]


# ------------------------------------------------- what a family imports ----
def _imports(path: Path) -> set:
    tree = ast.parse(path.read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
    return names


FAMILY_DIRS = sorted(p for p in (BENCH / "families").iterdir() if p.is_dir()
                     and not p.name.startswith(("_", ".")))


@pytest.mark.parametrize("directory", FAMILY_DIRS, ids=lambda p: p.name)
def test_reference_half_imports_nothing_of_the_program(directory):
    """Every module of a family but ``program.py``, and the harness's
    reference with what it loads the family by, import nothing of
    ``repro``."""
    files = [p for p in directory.glob("*.py") if p.name != "program.py"]
    files += [BENCH / "reference.py", BENCH / "family.py", BENCH / "data.py"]
    assert {p.name for p in files} >= {"reference.py", "data.py", "flops.py"}
    for p in files:
        assert not any(n == "repro" or n.startswith("repro.")
                       for n in _imports(p)), p


def test_harness_names_no_model():
    for name in ("run", "cell", "reference", "flops", "readings", "stages",
                 "trace"):
        text = (BENCH / f"{name}.py").read_text()
        for word in ("cnn", "images", "labels", "input_hw"):
            assert word not in text, (name, word)


def test_an_unknown_family_fails_with_its_name_and_directory(tmp_path,
                                                            monkeypatch):
    config = dict(cell_mod.load("configs", "fmnist-cnn.n50"))
    config["model"] = dict(config["model"], family="no_such_family")
    path = tmp_path / "configs" / "x.json"
    path.parent.mkdir()
    path.write_text(json.dumps(config))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"][0]["file"] = str(path)
    with pytest.raises(SystemExit) as e:
        cell_mod.spec(WORKLOADS[0], bench)
    msg = str(e.value)
    assert "no_such_family" in msg
    assert str(BENCH / "families" / "no_such_family") in msg


# --------------------------------------------- a family added as new files ----
TOY_DATA = '''
"""Token streams: each sequence steps through the vocabulary by its
topic's stride, with a tenth of the tokens redrawn."""
import numpy as np

from data import dirichlet_partition


def _streams(topics, rng, vocab, length):
    start = rng.integers(0, vocab, len(topics))
    tok = (start[:, None] + (topics[:, None] + 1) * np.arange(length)) % vocab
    noise = rng.random(tok.shape) < 0.1
    return np.where(noise, rng.integers(0, vocab, tok.shape),
                    tok).astype(np.int32)


def make_data(config, seed):
    m, d, fleet = config["model"], config["data"], config["fleet_seed"]
    topics = np.random.default_rng([fleet, 0]).integers(
        0, d["n_topics"], d["n_train"])
    parts = dirichlet_partition(topics, config["n_clients"],
                                d["dirichlet_beta"],
                                np.random.default_rng([fleet, 1]),
                                d["min_client_size"])
    tokens = _streams(topics, np.random.default_rng([seed, 0]), m["vocab"],
                      m["seq_len"] + 1)
    test_rng = np.random.default_rng([fleet, 2])
    test = _streams(test_rng.integers(0, d["n_topics"], d["n_test"]),
                    test_rng, m["vocab"], m["seq_len"] + 1)
    return dict(tokens=tokens, test_tokens=test, parts=parts)
'''

TOY_REFERENCE = '''
"""Embedding, one dense layer and an unembedding; next-token loss."""
import jax
import jax.numpy as jnp


def init_params(model, seed):
    v, e, h = model["vocab"], model["d_embed"], model["d_hidden"]

    @jax.jit
    def make(key):
        k = jax.random.split(key, 3)
        return {"embed": {"tokens": {"w": jax.random.normal(k[0], (v, e))}},
                "body": {"dense": {"w": jax.random.normal(k[1], (e, h))
                                   / jnp.sqrt(e), "b": jnp.zeros((h,))}},
                "head": {"unembed": {"w": jax.random.normal(k[2], (h, v))
                                     / jnp.sqrt(h), "b": jnp.zeros((v,))}}}
    return make(jax.random.PRNGKey(seed))


def logits(p, tokens, dtype, precision):
    x = p["embed"]["tokens"]["w"].astype(dtype)[tokens[:, :-1]]
    d, u = p["body"]["dense"], p["head"]["unembed"]
    h = jax.nn.relu(jnp.dot(x, d["w"].astype(dtype), precision=precision)
                    + d["b"].astype(dtype))
    return (jnp.dot(h, u["w"].astype(dtype), precision=precision)
            + u["b"].astype(dtype)).astype(jnp.float32)


def loss(p, tokens, dtype, precision):
    logp = jax.nn.log_softmax(logits(p, tokens, dtype, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def minibatch(data, rows):
    return dict(tokens=data["tokens"][rows])


def client_step(lr, dtype, precision):
    def one(p0, batch):
        p = p0
        for s in range(batch["tokens"].shape[0]):
            ls, g = jax.value_and_grad(loss)(p, batch["tokens"][s], dtype,
                                             precision)
            p = jax.tree_util.tree_map(
                lambda a, b: a - jnp.asarray(lr, dtype) * b.astype(dtype),
                p, g)
        d = jax.tree_util.tree_map(lambda a, b: a - b, p, p0)
        return jnp.concatenate([v.astype(jnp.float32).reshape(-1)
                                for v in jax.tree_util.tree_leaves(d)]), ls
    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def accuracy(data, dtype, precision, block=40):
    @jax.jit
    def acc(p, tokens):
        def one(t):
            pred = jnp.argmax(logits(p, t, dtype, precision), -1)
            return jnp.sum((pred == t[:, 1:]).astype(jnp.int32))
        hits = jax.lax.map(one, tokens.reshape(
            (-1, block) + tokens.shape[1:]))
        return jnp.sum(hits) / (tokens.shape[0] * (tokens.shape[1] - 1))
    test = jnp.asarray(data["test_tokens"])
    return lambda p: acc(p, test)
'''

TOY_FLOPS = '''
def train_flops(model):
    """Per sequence: forward and weight gradients of both matrices, and
    the input gradient of the unembedding."""
    e, h, v, t = (model["d_embed"], model["d_hidden"], model["vocab"],
                  model["seq_len"])
    return 2 * t * (2 * (e * h + h * v) + h * v)


def eval_flops(config):
    m = config["model"]
    return (config["data"]["n_test"] * 2 * m["seq_len"]
            * (m["d_embed"] * m["d_hidden"] + m["d_hidden"] * m["vocab"]))
'''

TOY_PROGRAM = '''
import jax
import jax.numpy as jnp


def _logits(p, tokens):
    x = p["embed"]["tokens"]["w"][tokens[:, :-1]]
    h = jax.nn.relu(x @ p["body"]["dense"]["w"] + p["body"]["dense"]["b"])
    return h @ p["head"]["unembed"]["w"] + p["head"]["unembed"]["b"]


def trainer_inputs(config, traffic, data, params0):
    def model_loss(p, batch):
        t = batch["tokens"]
        logp = jax.nn.log_softmax(_logits(p, t), -1)
        loss = -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], -1))
        return loss, {"xent": loss}

    test = jnp.asarray(data["test_tokens"])

    @jax.jit
    def eval_fn(p):
        return jnp.mean((jnp.argmax(_logits(p, test), -1) == test[:, 1:])
                        .astype(jnp.float32))

    clients = [dict(tokens=data["tokens"][q]) for q in data["parts"]]
    return dict(model_loss=model_loss, model_params=params0,
                client_datasets=clients, eval_fn=eval_fn)
'''


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A copy of the harness with a toy token-stream family, its
    configuration, traffic, limits and a ``BENCHMARK.json`` of one cell
    added as new files, and the harness pointed at the copy."""
    import run
    copy = tmp_path / "bench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "testdata"))
    fam = copy / "families" / "toy_lm"
    fam.mkdir()
    for name, text in (("data", TOY_DATA), ("reference", TOY_REFERENCE),
                       ("flops", TOY_FLOPS), ("program", TOY_PROGRAM)):
        (fam / f"{name}.py").write_text(text)
    cnn = cell_mod.load("configs", "fmnist-cnn.n50")
    config = dict(
        name="toy-lm.n6", source="a toy next-token model",
        model=dict(family="toy_lm", vocab=32, d_embed=16, d_hidden=32,
                   seq_len=8, dtype="float32"),
        precision=dict(params="float32", matmul="default"), n_clients=6,
        data=dict(n_train=600, n_test=120, n_topics=4, dirichlet_beta=0.3,
                  min_client_size=2),
        channel=cnn["channel"], fleet_seed=0)
    (copy / "configs" / "toy-lm.n6.json").write_text(json.dumps(config))
    traffic = dict(cell_mod.load("traffic", "fairenergy"), local_batch=16,
                   trace_chunks=1)
    (copy / "traffic" / "toy-fairenergy.json").write_text(json.dumps(traffic))
    (copy / "limits" / "toy-lm.fairenergy.json").write_text(json.dumps(
        dict(loss0_gap=3e-4, loss_gap=1e-3, param_gap=0.02, acc_gap=0.02)))
    bench = dict(BENCHMARK, configs=[dict(
        name="toy-lm.n6", source="a toy next-token model",
        file="bench/configs/toy-lm.n6.json", reduced=[], why="t")],
        workloads=[dict(name="toy-lm.fairenergy", config="toy-lm.n6",
                        traffic="toy-fairenergy", chips=1, why="t")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the CPU this test runs on has no published peak: give it one
    peaks = json.loads((copy / "peaks.json").read_text())
    peaks["cpu"] = dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e11)
    (copy / "peaks.json").write_text(json.dumps(peaks))
    monkeypatch.setattr(cell_mod, "BENCH", copy)
    monkeypatch.setattr(cell_mod, "ROOT", tmp_path)
    monkeypatch.setattr(family, "FAMILIES", copy / "families")
    monkeypatch.setattr(run, "BENCH", copy)
    run.use_compile_cache()
    return run, bench, config, traffic


TOY_SEED = 2 ** 31 + 4321


def test_an_added_model_family_is_found_without_edits(toy):
    run, bench, config, traffic = toy
    res, notes = run.run_cell("toy-lm.fairenergy", bench, TOY_SEED, 0.0,
                              True, require_tpu=False)
    assert res["correct"], notes["numbers"]
    assert res["attempted"] == traffic["chunk_rounds"]
    assert res["failed"] == 0
    # mfu by the family's FLOPs: 6 clients' 2 steps of 16 sequences of
    # 8 tokens, and the eval of 120 sequences spread over the chunk
    e, h, v, t = 16, 32, 32, 8
    per_round = (6 * 2 * 16 * 2 * t * (2 * (e * h + h * v) + h * v)
                 + 120 * 2 * t * (e * h + h * v) / 10)
    assert flops.round_flops(config, traffic) == per_round
    window = res["device"]["window_s"]
    assert res["metrics"]["mfu"]["value"] == pytest.approx(
        100 * per_round * res["attempted"] / window / 1e12, rel=1e-12)


def test_an_added_model_family_catches_its_fault_and_control(toy):
    import readings
    run, bench, _, _ = toy
    with readings.planted("half_batch"):
        res, notes = run.run_cell("toy-lm.fairenergy", bench, TOY_SEED, 0.0,
                                  False, require_tpu=False)
    assert not res["correct"], notes["numbers"]
    row = readings.control_reading("toy-lm.fairenergy", bench, TOY_SEED)
    limits = cell_mod.load("limits", "toy-lm.fairenergy")
    correct, _ = reference.judge(row["numbers"], row["finite"], limits)
    assert not correct, row["numbers"]
