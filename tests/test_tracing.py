"""The round program names its stages, and the host loop spans its
chunk calls, on the profiler's clock.

Device side: every stage of the scanned round sits under a
``jax.named_scope`` (``fl.sample``, ``fl.client_step``, ``fl.decide``,
``fl.sparsify``, ``fl.aggregate``, ``fl.eval``), which the compiled
program keeps in its HLO ``op_name`` metadata, so a device trace can
attribute each op to a stage. Host side: ``fl.dispatch``, ``fl.sync``
and ``fl.logs`` span each chunk call's dispatch, its wait for the
device and copy to the host, and its ``RoundLog`` construction.
"""
import glob
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
from repro.configs.base import ModelConfig
from repro.fl import FederatedTrainer
from repro.models import cnn

STAGES = ("sample", "client_step", "decide", "sparsify", "aggregate", "eval")
SCOPE = re.compile(r"fl\.[a-z_]+/?")
N_CLIENTS = 6
MODEL = ModelConfig(name="cnn-tiny", family="cnn", n_layers=2, d_model=0,
                    cnn_channels=(4, 8), cnn_dense=16, input_hw=(28, 28, 1),
                    n_classes=10, dtype="float32")


def make_trainer(controller):
    rng = np.random.default_rng(3)
    datasets = [dict(images=rng.normal(size=(24 + 4 * i, 28, 28, 1))
                     .astype(np.float32),
                     labels=rng.integers(0, 10, size=24 + 4 * i))
                for i in range(N_CLIENTS)]
    tx = jnp.asarray(rng.normal(size=(32, 28, 28, 1)).astype(np.float32))
    ty = jnp.asarray(rng.integers(0, 10, size=32))

    def eval_fn(p):
        logits = cnn.cnn_forward(p, tx, MODEL)
        return jnp.mean((jnp.argmax(logits, -1) == ty).astype(jnp.float32))

    kw = {} if controller == "fairenergy" else {"fixed_k": 3}
    return FederatedTrainer(
        model_loss=lambda p, b: cnn.cnn_loss(p, b, MODEL),
        model_params=cnn.init_cnn(jax.random.PRNGKey(0), MODEL),
        client_datasets=datasets, eval_fn=eval_fn,
        fl_cfg=FLConfig(local_steps=2, local_batch=8, lr=0.05),
        fe_cfg=FairEnergyConfig(), ch_cfg=ChannelConfig(n_clients=N_CLIENTS),
        controller=controller, seed=0, **kw)


def _classify():
    """The benchmark's layer of a device op by its name stack
    (``bench/trace.py:classify``), loaded by path: the scopes must leave
    every op in the layer the benchmark gave it before they existed."""
    path = Path(__file__).resolve().parents[1] / "bench" / "trace.py"
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.classify


@pytest.fixture
def metadata_keyed_cache():
    """A persistent compilation cache keys a program without its debug
    info, so with one on, a compile could load an executable made from
    the same program before it had scopes, with that program's op names:
    key by the metadata too while the test compiles."""
    key = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, key)
    jax.config.update(key, True)
    yield
    jax.config.update(key, prev)


@pytest.mark.parametrize("controller", ["fairenergy", "ecorandom",
                                        "scoremax"])
def test_compiled_round_names_every_stage(controller, metadata_keyed_cache):
    hlo = make_trainer(controller).lower_scanned(2).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = {s for op in op_names for s in re.findall(r"fl\.[a-z_]+", op)}
    assert scopes == {f"fl.{s}" for s in STAGES}
    classify = _classify()
    moved = [op for op in op_names
             if classify(op) != classify(SCOPE.sub("", op))]
    assert not moved
    # the client step's autodiff and the eval sit under their stages
    assert any("fl.client_step/" in op and "jvp(" in op for op in op_names)
    assert any("fl.eval/" in op for op in op_names)


def test_topk_prefix_sum_carries_the_callers_scope():
    """The top-k's tie fill counts with a prefix sum. Spelled as the
    reduce-window that ``jnp.cumsum`` lowers to, it is lowered in place,
    so the caller's scope names it in the program (``cumsum`` lowers it
    as an outlined function whose ops carry no name stack); the counts
    are the same, and so are the values kept, against the kernel body's
    index fill."""
    from repro.kernels.topk_sparsify.ref import (_prefix_count, topk_keep,
                                                 topk_threshold_mask)
    rng = np.random.default_rng(1)
    x = jnp.asarray(np.round(rng.normal(size=(6, 256)), 1)
                    .astype(np.float32))                     # many ties
    k = jnp.asarray(rng.integers(1, 257, size=(6, 1)), jnp.int32)
    flags = jnp.abs(x) == 0.5
    np.testing.assert_array_equal(
        _prefix_count(flags), jnp.cumsum(flags.astype(jnp.int32), axis=-1))
    kept = jax.jit(lambda x, k: x * topk_threshold_mask(x, k))(x, k)
    np.testing.assert_array_equal(
        np.asarray(kept).view(np.int32),
        np.asarray(jax.jit(lambda x, k: topk_keep([x], k)[0])(x, k))
        .view(np.int32))

    def sparsify(x, k):
        with jax.named_scope("fl.sparsify"):
            return topk_threshold_mask(x, k)
    module = (jax.jit(sparsify).lower(x, k).compiler_ir("hlo")
              .as_hlo_module().to_string())
    windows = [re.search(r'op_name="([^"]*)"', line).group(1)
               for line in module.splitlines() if "reduce-window(" in line]
    assert windows and all("fl.sparsify/" in op for op in windows)


def _host_spans(directory):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = [(e.start_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("fl.")]
    return [name for _, name in sorted(spans)]


@pytest.mark.parametrize("entry", ["run_scanned", "run_round"])
def test_host_spans_per_chunk_in_order(entry, tmp_path):
    trainer = make_trainer("ecorandom")
    with jax.profiler.trace(str(tmp_path)):
        if entry == "run_scanned":
            trainer.run_scanned(4, chunk=2, verbose=False)
        else:
            trainer.run_round(0)
            trainer.run_round(1)
    assert len(trainer.history) == (4 if entry == "run_scanned" else 2)
    assert _host_spans(tmp_path) == ["fl.dispatch", "fl.sync", "fl.logs"] * 2
