"""Multi-pod FL collectives: FairEnergy-compressed cross-silo aggregation.

This is the paper's mechanism expressed at datacenter scale (DESIGN.md §3):
each pod ("pod" mesh axis) is an FL silo; the inter-silo update exchange is
the communication FairEnergy compresses. ``compressed_psum_update`` runs
under ``shard_map``: each silo

  1. computes its local update's contribution score ‖u‖·gamma
     (score_norm kernel semantics: blockwise sum-of-squares + scalar psum
     over the intra-silo axes),
  2. top-k sparsifies the update to its assigned gamma (block_topk — the
     Pallas topk_sparsify kernel on TPU),
  3. all-reduces the SPARSE update across the pod axis.

The wire bytes across the pod axis drop from S to gamma*S + mask, exactly
the paper's payload model — visible in the dry-run's collective table.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.fl.compression import block_topk


def silo_update_norm(update_vec: jnp.ndarray, *, axis_names=()) -> jnp.ndarray:
    """L2 norm of a (possibly sharded) update inside shard_map: blockwise
    partial sums + psum over the intra-silo axes."""
    sq = jnp.sum(jnp.square(update_vec.astype(jnp.float32)))
    for ax in axis_names:
        sq = jax.lax.psum(sq, ax)
    return jnp.sqrt(sq)


def compressed_psum_update(update_vec: jnp.ndarray, gamma: float, *,
                           pod_axis: str = "pod",
                           block: int = 4096) -> jnp.ndarray:
    """Inside shard_map: sparsify the local-silo update to ``gamma`` then
    mean-reduce across silos. Returns the aggregated (dense) update."""
    sparse, _ = block_topk(update_vec, gamma, block=block)
    agg = jax.lax.pmean(sparse, pod_axis)
    return agg


def make_sparse_fl_allreduce(mesh, gamma: float, *, vec_spec: Optional[P] = None,
                             block: int = 4096, quantize: bool = False):
    """Cross-pod aggregation that actually moves gamma*S on the wire.

    A dense all-reduce of a masked vector still transfers S bytes; instead
    each silo extracts its per-block top-k as COMPACT (values, indices)
    arrays [nb, k], all-gathers those across the pod axis, and scatter-adds
    into a dense buffer locally. Wire bytes per coordinate kept: 4+2 (f32 +
    int16 idx) or 1+2 with ``quantize=True`` (int8 values) vs 4 dense — the
    paper's gamma*S + I payload expressed as an ICI collective
    (EXPERIMENTS.md §Perf-3 carries the ring-algorithm accounting too).
    """
    import math

    vec_spec = vec_spec if vec_spec is not None else P(("data", "model"))
    n_pods = mesh.shape.get("pod", 1)

    def body(vec):
        n = vec.shape[0]
        assert n % block == 0, (n, block)
        nb = n // block
        k = max(1, min(block, math.ceil(gamma * block)))
        rows = vec.reshape(nb, block)
        vals, idx = jax.lax.top_k(jnp.abs(rows), k)              # [nb, k]
        vals = jnp.take_along_axis(rows, idx, axis=1)            # signed values
        if quantize:
            scale = jnp.maximum(jnp.max(jnp.abs(vals)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(vals / scale), -127, 127).astype(jnp.int8)
            all_q = jax.lax.all_gather(q, "pod")                 # [pods, nb, k] int8
            all_scale = jax.lax.all_gather(scale, "pod")
            all_vals = all_q.astype(jnp.float32) * all_scale.reshape(-1, 1, 1)
        else:
            all_vals = jax.lax.all_gather(vals, "pod")           # [pods, nb, k] f32
        # block 4096 => indices fit int16 (half the index wire bytes)
        all_idx = jax.lax.all_gather(idx.astype(jnp.int16), "pod").astype(jnp.int32)
        dense = jnp.zeros((nb, block), jnp.float32)
        for pth in range(n_pods):
            dense = dense.at[jnp.arange(nb)[:, None], all_idx[pth]].add(all_vals[pth])
        return (dense / n_pods).reshape(n).astype(vec.dtype)

    # check_vma=False: the output IS pod-replicated (built from all-gathered
    # data) but the static analysis cannot infer it through the scatter-adds
    fn = jax.shard_map(body, mesh=mesh, in_specs=(vec_spec,),
                       out_specs=vec_spec, check_vma=False)
    return jax.jit(fn)


def make_fl_allreduce(mesh, gamma: float, *, vec_spec: Optional[P] = None,
                      block: int = 4096):
    """Returns a jitted fn(update_vec) -> aggregated update, with the
    compression + cross-pod reduce expressed via shard_map on ``mesh``.
    The vector is sharded over the intra-silo axes; each silo compresses
    its shard locally (block-local top-k commutes with sharding when the
    shard size is a multiple of the block)."""
    vec_spec = vec_spec if vec_spec is not None else P(("data", "model"))

    def body(vec):
        sparse, _ = block_topk(vec, gamma, block=block)
        return jax.lax.pmean(sparse, "pod")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(vec_spec,),
                       out_specs=vec_spec)
    return jax.jit(fn)
