"""End-to-end behaviour tests for the paper's system (FL + FairEnergy)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
from repro.configs.fmnist_cnn import SMOKE as CNN_SMOKE
from repro.data import ClientDataset, dirichlet_partition, make_fmnist_like
from repro.fl import FederatedTrainer
from repro.models import cnn


@pytest.fixture(scope="module")
def fl_setup():
    cfg = CNN_SMOKE
    imgs, labels = make_fmnist_like(4000, seed=0)
    ti, tl = make_fmnist_like(800, seed=99)
    N = 10
    parts = dirichlet_partition(labels, N, 0.3, seed=0)
    fl_cfg = FLConfig(local_batch=32, local_steps=2, lr=0.05)
    datasets = [ClientDataset(imgs[p], labels[p], fl_cfg.local_batch, seed=i)
                for i, p in enumerate(parts)]
    params = cnn.init_cnn(jax.random.PRNGKey(0), cfg)
    loss_fn = lambda p, b: cnn.cnn_loss(p, b, cfg)

    @jax.jit
    def eval_fn(p):
        lg = cnn.cnn_forward(p, jnp.asarray(ti), cfg)
        return jnp.mean((jnp.argmax(lg, -1) == jnp.asarray(tl)).astype(jnp.float32))

    def make(controller, **kw):
        return FederatedTrainer(model_loss=loss_fn, model_params=params,
                                client_datasets=datasets, eval_fn=eval_fn,
                                fl_cfg=fl_cfg, fe_cfg=FairEnergyConfig(),
                                ch_cfg=ChannelConfig(n_clients=N),
                                controller=controller, seed=0, **kw)
    return make


def test_fairenergy_learns(fl_setup):
    tr = fl_setup("fairenergy")
    tr.run(25, verbose=False)
    acc = tr.accuracy_curve()
    assert acc[-1] > 0.6, acc[-5:]
    assert acc[-1] > acc[0]


def test_fairenergy_energy_accounting(fl_setup):
    tr = fl_setup("fairenergy")
    tr.run(10, verbose=False)
    for lg in tr.history:
        assert (lg.energy >= 0).all()
        # only selected clients consume energy
        assert (lg.energy[~lg.selected] == 0).all()
        assert lg.bandwidth[lg.selected].sum() <= 10e6 * (1 + 1e-6)


def test_fairenergy_fair_participation(fl_setup):
    """Fairness (paper Table I): FairEnergy must not starve any client —
    its participation FLOOR dominates ScoreMax's, and every client gets
    selected at least pi_min-ish often over enough rounds."""
    rounds = 40
    tr_fe = fl_setup("fairenergy")
    tr_fe.run(rounds, verbose=False)
    k = max(1, int(np.mean([lg.n_selected for lg in tr_fe.history])))
    tr_sm = fl_setup("scoremax", fixed_k=k)
    tr_sm.run(rounds, verbose=False)
    min_fe = tr_fe.participation_counts().min()
    min_sm = tr_sm.participation_counts().min()
    assert min_fe >= min_sm, (min_fe, min_sm)
    assert min_fe >= 1, "a client was never selected under FairEnergy"
    # normalized spread (std/mean) should not be wildly worse than ScoreMax
    def nspread(tr):
        c = tr.participation_counts()
        return c.std() / max(c.mean(), 1e-9)
    assert nspread(tr_fe) <= nspread(tr_sm) * 1.5 + 0.25


def test_scoremax_uses_full_precision(fl_setup):
    tr = fl_setup("scoremax", fixed_k=3)
    tr.run(3, verbose=False)
    for lg in tr.history:
        assert (lg.gamma[lg.selected] == 1.0).all()


def test_ecorandom_cheapest_per_round(fl_setup):
    tr_eco = fl_setup("ecorandom", fixed_k=3, eco_gamma=0.1, eco_bandwidth=2e5)
    tr_eco.run(5, verbose=False)
    tr_sm = fl_setup("scoremax", fixed_k=3)
    tr_sm.run(5, verbose=False)
    assert np.mean(tr_eco.energy_per_round()) < np.mean(tr_sm.energy_per_round())


def test_trainer_uses_pallas_compression(fl_setup):
    tr = fl_setup("fairenergy", use_pallas_compression=True)
    tr.run(2, verbose=False)
    assert tr.history[-1].accuracy >= 0.0  # runs end-to-end


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint
    params = cnn.init_cnn(jax.random.PRNGKey(0), CNN_SMOKE)
    path = save_checkpoint(str(tmp_path), 7, params, {"note": "test"})
    assert latest_checkpoint(str(tmp_path)) == path
    back = restore_checkpoint(path, params)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sparse_crosspod_aggregation(monkeypatch):
    """Sparse (values+indices) cross-pod exchange == dense-masked psum."""
    import subprocess, sys, os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # the 8 forced host devices, never a chip
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.fl.collectives import make_fl_allreduce, make_sparse_fl_allreduce
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
vec = jax.device_put(jnp.asarray(np.random.default_rng(0).normal(size=1<<16).astype(np.float32)),
                     NamedSharding(mesh, P(("data", "model"))))
a = make_fl_allreduce(mesh, 0.25)(vec)
b = make_sparse_fl_allreduce(mesh, 0.25)(vec)
assert float(jnp.abs(a - b).max()) < 1e-6, float(jnp.abs(a - b).max())
c = make_sparse_fl_allreduce(mesh, 0.25, quantize=True)(vec)
rel = float(jnp.abs(c - a).max() / jnp.abs(a).max())
assert rel < 0.02, rel
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0 and "OK" in out.stdout, out.stdout + out.stderr
