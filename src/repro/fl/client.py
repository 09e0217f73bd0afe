"""FL client: local training producing an update pytree.

With ``local_steps=1`` the update equals the (negative-scaled) gradient —
the paper's setting ("computes a local model update u_i, i.e. the gradient
of its local loss"); larger values give standard FedAvg deltas.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.optim import make_optimizer


def make_local_step(loss_fn: Callable, lr: float, opt_name: str = "sgd",
                    **opt_kw):
    """Returns jitted fn(params, batch, opt_state=None) -> (new_params,
    opt_state, metrics). Pass the returned ``opt_state`` back into the
    next call — re-initializing it every step silently degrades stateful
    optimizers (momentum-SGD, AdamW) to their stateless updates. ``None``
    (the default) initializes a fresh state."""
    opt_init, opt_update = make_optimizer(opt_name, **opt_kw)

    @jax.jit
    def step(params, batch, opt_state):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        new_params, opt_state = opt_update(grads, opt_state, params, lr)
        return new_params, opt_state, dict(metrics, loss=loss)

    def call(params, batch, opt_state=None):
        if opt_state is None:
            opt_state = opt_init(params)
        return step(params, batch, opt_state)

    return call


def local_update(params, dataset, local_step, n_steps: int):
    """Run ``n_steps`` minibatch steps (optimizer state threaded through
    the loop); return (delta pytree, metrics)."""
    p = params
    state, metrics = None, None
    for _ in range(n_steps):
        batch = dataset.next_batch()
        p, state, metrics = local_step(p, batch, state)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
    return delta, metrics


class WithFrozen(NamedTuple):
    """The one params argument a model's loss and eval are handed when
    the trainer holds a frozen base: the trained tree (what is
    differentiated, flattened, sparsified and aggregated) and the base,
    which only the model reads."""
    trained: Any
    frozen: Any


def make_batched_client_step(loss_fn: Callable, lr: float, opt_name: str = "sgd",
                             jit: bool = True, frozen: bool = False, **opt_kw):
    """Vectorized replacement for the per-client Python loop.

    Returns a jitted ``fn(params, batches) -> (updates [N,D], u_norms [N],
    losses [N])`` where ``batches`` is a pytree whose leaves carry leading
    dims ``[n_clients, local_steps, ...]``. All clients run together under
    ``vmap`` from the same global params; the (small, static) local-step
    count is unrolled rather than ``lax.scan``-ed — XLA:CPU while-loops
    serialize the conv grads badly (measured 6x slower than unrolled on
    the FMNIST CNN) and local_steps is 1-4 in every config. Updates come
    back flattened (fp32) and stacked, ready for the fused
    sparsify/aggregate in the round engine. ``losses`` is each client's
    last-step training loss (matches the metrics of the loop path).

    ``jit=False`` returns the bare vmapped function for composition into a
    larger traced program (e.g. the multi-round ``lax.scan`` engine).

    ``frozen=True``: the function takes a third argument, a frozen base
    shared by every client (``vmap`` broadcasts it; no client gets a
    copy), and ``loss_fn`` is handed ``WithFrozen(params, base)``; only
    ``params`` is differentiated.
    """
    from repro.fl.updates import flatten_update

    opt_init, opt_update = make_optimizer(opt_name, **opt_kw)

    def one_client(params, client_batches, base=None):
        objective = (loss_fn if not frozen else
                     lambda p, b: loss_fn(WithFrozen(p, base), b))
        n_steps = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
        # optimizer state initialized once and threaded through the local
        # steps — momentum/Adam moments accumulate across the whole local
        # epoch instead of resetting every minibatch
        p, state, loss = params, opt_init(params), jnp.float32(0)
        for s in range(n_steps):
            batch = jax.tree_util.tree_map(lambda v: v[s], client_batches)
            (loss, _), grads = jax.value_and_grad(objective, has_aux=True)(p, batch)
            p, state = opt_update(grads, state, p, lr)
        delta = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
        vec = flatten_update(delta)
        return vec, jnp.sqrt(jnp.sum(vec * vec)), loss

    batched = jax.vmap(one_client,
                       in_axes=(None, 0, None) if frozen else (None, 0))
    return jax.jit(batched) if jit else batched
