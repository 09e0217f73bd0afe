"""A frozen model base (``FederatedTrainer(frozen_params=...)``): the base
is an argument of the round program, shared by every client and never
trained; only the trained tree (here LoRA-style adapters) is
differentiated, flattened, sparsified, aggregated and checkpointed.

Pinned: three rounds of the fused engine equal the per-round path (``run``) and
a per-client Python-loop FedAvg written here, on one device and on a
``clients`` mesh; the lowered round program takes the base as a
parameter and holds no constant over 1 MB; and a model without a frozen
base lowers to the same program text as before the option existed.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
from repro.configs.base import ModelConfig
from repro.fl import FederatedTrainer
from repro.fl.client import WithFrozen
from repro.models import cnn
from repro.sharding import make_clients_mesh

D_IN, D_BASE, N_CLASSES, RANK = 24, 600, 5, 4
N_CLIENTS, STEPS, BATCH, LR = 4, 2, 8, 0.1


def _model_loss(p, batch):
    """tanh(x W0) (W1 + A B): W0, W1 frozen, A and B trained."""
    base, ad = p.frozen, p.trained
    hid = jnp.tanh(batch["x"] @ base["w0"][:D_IN, :])
    logits = hid @ (base["w1"] + ad["a"] @ ad["b"])
    ll = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(ll, batch["y"][:, None], 1)), {}


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    base = {"w0": jnp.asarray(rng.normal(size=(D_BASE, D_BASE)) * 0.2,
                              jnp.float32),            # 1.44 MB
            "w1": jnp.asarray(rng.normal(size=(D_BASE, N_CLASSES)) * 0.1,
                              jnp.float32)}
    adapters = {"a": jnp.asarray(rng.normal(size=(D_BASE, RANK)) * 0.1,
                                 jnp.float32),
                "b": jnp.asarray(rng.normal(size=(RANK, N_CLASSES)) * 0.1,
                                 jnp.float32)}
    data = [{"x": rng.normal(size=(20 + 3 * i, D_IN)).astype(np.float32),
             "y": rng.integers(0, N_CLASSES, 20 + 3 * i).astype(np.int32)}
            for i in range(N_CLIENTS)]
    tx = jnp.asarray(rng.normal(size=(32, D_IN)).astype(np.float32))
    ty = jnp.asarray(rng.integers(0, N_CLASSES, 32))

    @jax.jit
    def eval_fn(p):
        hid = jnp.tanh(tx @ p.frozen["w0"][:D_IN, :])
        lg = hid @ (p.frozen["w1"] + p.trained["a"] @ p.trained["b"])
        return jnp.mean((jnp.argmax(lg, -1) == ty).astype(jnp.float32))

    return base, adapters, data, eval_fn


def _trainer(controller="randomfull", mesh=None):
    base, adapters, data, eval_fn = _setup()
    return FederatedTrainer(
        model_loss=_model_loss, model_params=adapters, client_datasets=data,
        eval_fn=eval_fn, frozen_params=base,
        fl_cfg=FLConfig(local_steps=STEPS, local_batch=BATCH, lr=LR),
        fe_cfg=FairEnergyConfig(), ch_cfg=ChannelConfig(n_clients=N_CLIENTS),
        controller=controller, fixed_k=N_CLIENTS, mesh=mesh)


def _fedavg(rounds):
    """Per-client Python loop: SGD on the adapters from the round's
    global adapters over the trainer's own minibatches, then the
    |D_i|-weighted mean of the deltas (every client selected, gamma 1)."""
    tr = _trainer()
    base, p = tr.frozen_params, jax.tree_util.tree_map(jnp.array, tr.params)
    sizes = np.array([len(d["y"]) for d in _setup()[2]], np.float64)
    w = sizes / sizes.sum()
    grad = jax.jit(jax.grad(lambda q, b: _model_loss(WithFrozen(q, base),
                                                     b)[0]))
    for r in range(rounds):
        batches = tr._round_batches(r)
        total = jax.tree_util.tree_map(jnp.zeros_like, p)
        for i in range(N_CLIENTS):
            q = p
            for s in range(STEPS):
                b = jax.tree_util.tree_map(lambda v: v[i, s], batches)
                q = jax.tree_util.tree_map(lambda a, g: a - LR * g, q,
                                           grad(q, b))
            total = jax.tree_util.tree_map(
                lambda t, a, b: t + np.float32(w[i]) * (a - b), total, q, p)
        p = jax.tree_util.tree_map(lambda a, t: a + t, p, total)
    return p


@pytest.mark.parametrize("mesh", [None, "clients"])
def test_fused_rounds_equal_per_round_path_and_a_fedavg_loop(mesh):
    want = _fedavg(3)
    fused = _trainer(mesh=make_clients_mesh(1) if mesh else None)
    fused.run_scanned(3, chunk=3, verbose=False)
    stepped = _trainer()
    stepped.run(3, verbose=False)
    for got in (fused.params, stepped.params):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)
    # the base is the caller's, untouched and never donated
    base = _setup()[0]
    assert np.array_equal(np.asarray(fused.frozen_params["w0"]),
                          np.asarray(base["w0"]))
    assert set(fused.params) == {"a", "b"}
    assert fused.n_params == D_BASE * RANK + RANK * N_CLASSES


def _constants(text):
    """Bytes of every constant of a lowered module."""
    sizes = {"f32": 4, "i32": 4, "ui32": 4, "f64": 8, "bf16": 2, "i1": 1,
             "i64": 8, "ui8": 1, "i8": 1}
    out = []
    for m in re.finditer(r"stablehlo\.constant dense<[^>]*> : "
                         r"tensor<((?:\d+x)*)(\w+)>", text):
        n = int(np.prod([int(d) for d in m.group(1).split("x") if d]))
        out.append(n * sizes.get(m.group(2), 4))
    return out


@pytest.mark.parametrize("controller", ["fairenergy", "randomfull"])
def test_round_program_takes_the_base_as_a_parameter(controller):
    tr = _trainer(controller)
    text = tr.lower_scanned(2).as_text()
    main = text[text.index("func.func public @main("):]
    signature = main[:main.index(") -> (")]
    assert f"tensor<{D_BASE}x{D_BASE}xf32>" in signature
    assert max(_constants(text)) < 1 << 20


def test_sweep_refuses_a_frozen_base_and_checkpoints_hold_the_adapters(
        tmp_path):
    tr = _trainer()
    with pytest.raises(NotImplementedError, match="frozen_params"):
        tr.run_sweep([0, 1], 2)
    tr.run_scanned(1, verbose=False)
    path = tr.save_checkpoint(str(tmp_path), 1)
    from repro.checkpoint import ckpt
    tree = ckpt.restore_checkpoint(path, tr._carry_tree())
    assert set(tree["params"]) == {"a", "b"}
    assert "frozen" not in tree


# a model without a frozen base lowers to the program it lowered to before
# the option existed: sha256 prefixes of ``lower_scanned(3).as_text()``
# (no debug locations in the text) of this small CNN fleet, read at the
# commit before ``frozen_params``
CNN_TEXT = {"fairenergy": (380264, "2db6f183fcc6c0f6"),
            "ecorandom": (327109, "f9829690232683f6")}


@pytest.mark.parametrize("controller", sorted(CNN_TEXT))
def test_cnn_round_program_text_is_unchanged(controller):
    mcfg = ModelConfig(name="c", family="cnn", n_layers=2, d_model=0,
                       cnn_channels=(4, 8), cnn_dense=16,
                       input_hw=(28, 28, 1), n_classes=10, dtype="float32")
    rng = np.random.default_rng(0)
    params = cnn.init_cnn(jax.random.PRNGKey(0), mcfg)
    clients = [dict(images=rng.normal(size=(20 + i, 28, 28, 1)).astype(
        np.float32), labels=rng.integers(0, 10, 20 + i).astype(np.int32))
        for i in range(4)]
    tx = jnp.asarray(rng.normal(size=(32, 28, 28, 1)).astype(np.float32))
    ty = jnp.asarray(rng.integers(0, 10, 32))

    @jax.jit
    def eval_fn(p):
        return jnp.mean((jnp.argmax(cnn.cnn_forward(p, tx, mcfg), -1) == ty)
                        .astype(jnp.float32))

    tr = FederatedTrainer(
        model_loss=lambda p, b: cnn.cnn_loss(p, b, mcfg), model_params=params,
        client_datasets=clients, eval_fn=eval_fn,
        fl_cfg=FLConfig(local_steps=2, local_batch=8, lr=0.05),
        fe_cfg=FairEnergyConfig(), ch_cfg=ChannelConfig(n_clients=4),
        controller=controller, fixed_k=2)
    text = tr.lower_scanned(3).as_text()
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()[:16]) == \
        CNN_TEXT[controller]
