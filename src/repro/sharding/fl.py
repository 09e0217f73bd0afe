"""Client-axis sharding for the fused FL engine.

The fused ``lax.scan`` engine (``repro.fl.server.make_scan_engine``) holds
every client's ``[N, L, ...]`` data stack and ``[N, D]`` update buffer on
one device, which caps the reproducible scenarios at N ~ 50. This module
supplies the mesh + PartitionSpec vocabulary to spread that client axis
over a 1-D ``clients`` mesh:

* the big per-client tensors — data stacks ``[N, L, ...]``, flat update /
  sparsify buffers ``[N, D]``, minibatch gathers — are sharded on their
  leading client axis;
* the tiny per-client observables the controllers consume (``u_norms``,
  ``h``, ``P``, all ``[N]``) are all-gathered/replicated, so selection /
  repair logic that needs a *global* argsort or cumsum runs unchanged and
  stays bit-compatible with the single-device path;
* model params, controller state, and per-round logs are replicated.

``N`` must divide the mesh — ``stack_client_datasets(...,
pad_to_multiple=mesh_size)`` appends zero-weight ghost clients to round
up (``repro.data.pipeline``).

Hierarchical (two-tier) aggregation generalizes the mesh to 2-D
``(clusters, clients)`` (``make_hierarchy_mesh``): the client axis of
every stack is split over *both* mesh axes — PartitionSpec
``P(("clusters", "clients"))`` — and the engine reduces in two stages,
``psum`` over ``clients`` (cluster-head partial aggregate) then ``psum``
over ``clusters`` (server reduction). Every helper here accepts the
client-axis argument as either the legacy string or the 2-D tuple of
axis names; with the string the emitted specs are byte-identical to the
historical 1-D ones.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

CLIENTS_AXIS = "clients"
CLUSTERS_AXIS = "clusters"

# a client axis is named by one mesh axis (legacy 1-D) or several (2-D
# hierarchy: the leading array axis is split over all of them in order)
AxisSpec = Union[str, Sequence[str]]


def _axis_entry(axis: AxisSpec):
    """Normalize to a PartitionSpec entry: str stays a str (legacy specs
    stay byte-identical), a sequence becomes the tuple entry that shards
    one array dimension across several mesh axes."""
    if isinstance(axis, str):
        return axis
    axes = tuple(axis)
    return axes[0] if len(axes) == 1 else axes


def axis_names(axis: AxisSpec) -> tuple:
    """The mesh-axis names a client axis maps onto, as a tuple."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def make_clients_mesh(n_devices: Optional[int] = None,
                      axis: str = CLIENTS_AXIS) -> Mesh:
    """1-D mesh over ``n_devices`` (default: all visible devices) with a
    single ``clients`` axis. On CPU, force multiple host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` before importing
    jax. The axis is ``Auto``: the engine places the client axis itself
    (``shard_map`` and ``device_put``), and eager code outside it must see
    ordinary arrays, not explicitly sharded ones."""
    n = n_devices if n_devices is not None else len(jax.devices())
    if n < 1:
        raise ValueError(f"need at least one device, got {n}")
    if n > len(jax.devices()):
        raise ValueError(f"requested {n} devices but only "
                         f"{len(jax.devices())} are visible")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def make_hierarchy_mesh(n_clusters: Optional[int] = None,
                        n_devices: Optional[int] = None,
                        clusters_axis: str = CLUSTERS_AXIS,
                        clients_axis: str = CLIENTS_AXIS) -> Mesh:
    """Two-tier ``(clusters, clients)`` mesh for cluster-head partial
    aggregation. ``n_clusters in (None, 1)`` returns the legacy 1-D
    clients mesh (the compiled program stays the historical one); else
    the devices are factored ``n_clusters x (n_devices / n_clusters)``
    and n_clusters must divide the device count."""
    if n_clusters is None or n_clusters == 1:
        return make_clients_mesh(n_devices, clients_axis)
    n = n_devices if n_devices is not None else len(jax.devices())
    if n > len(jax.devices()):
        raise ValueError(f"requested {n} devices but only "
                         f"{len(jax.devices())} are visible")
    if n_clusters < 1 or n % n_clusters != 0:
        raise ValueError(f"{n_clusters} clusters do not divide "
                         f"{n} devices")
    return jax.make_mesh((n_clusters, n // n_clusters),
                         (clusters_axis, clients_axis),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_client_axes(mesh: Mesh, axis: AxisSpec = CLIENTS_AXIS) -> tuple:
    """The client-axis names present on ``mesh``: ``("clusters",
    "clients")`` on a hierarchy mesh, ``("clients",)`` on the legacy 1-D
    one. The order matters — it is the device-major order client lanes
    are laid out in, and the order the two psum stages reduce over."""
    names = axis_names(axis)
    if len(names) == 1 and CLUSTERS_AXIS in mesh.shape \
            and names[0] != CLUSTERS_AXIS:
        names = (CLUSTERS_AXIS,) + names
    for a in names:
        if a not in mesh.shape:
            raise ValueError(f"mesh has no {a!r} axis; axes: "
                             f"{tuple(mesh.shape)}")
    return names


def clients_axis_size(mesh: Mesh, axis: str = CLIENTS_AXIS) -> int:
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no {axis!r} axis; axes: "
                         f"{tuple(mesh.shape)}")
    return mesh.shape[axis]


def client_shard_count(mesh: Mesh, axis: AxisSpec = CLIENTS_AXIS) -> int:
    """Number of shards the client axis splits into — the product over
    all its mesh axes (= ``clients_axis_size`` on the legacy 1-D mesh)."""
    count = 1
    for a in mesh_client_axes(mesh, axis):
        count *= mesh.shape[a]
    return count


def client_stack_spec(ndim: int, axis: AxisSpec = CLIENTS_AXIS) -> P:
    """Spec for a ``[N, ...]`` per-client stack: leading axis sharded,
    everything else replicated. Covers the ``[N, L, ...]`` data stacks,
    ``[N, D]`` update/sparsify buffers, and ``[N]`` observables alike.
    With a tuple axis the leading dimension is split over both mesh axes
    (cluster-major, matching ``mesh_client_axes`` order)."""
    return P(_axis_entry(axis), *([None] * (ndim - 1)))


def client_data_specs(data, axis: AxisSpec = CLIENTS_AXIS):
    """PartitionSpec pytree for a ``DeviceClientData``: every array (and
    ``lengths``) sharded on its leading client axis."""
    return type(data)(
        arrays={k: client_stack_spec(v.ndim, axis)
                for k, v in data.arrays.items()},
        lengths=client_stack_spec(1, axis))


def replicated_specs(tree) -> object:
    """All-replicated spec pytree (params, controller state, scalars)."""
    return jax.tree_util.tree_map(lambda _: P(), tree)


def async_state_specs(astate, axis: AxisSpec = CLIENTS_AXIS):
    """Spec pytree for the async-round scan carry
    (``repro.core.rounds.AsyncState``): the ``[N, D]`` stale-update
    buffer and its ``[N]`` age / remaining-time vectors all live
    shard-local on the client axis — like the update/sparsify buffers,
    the full stale matrix never materializes on one device. Accepts the
    empty carry ``()`` (staleness off) and returns ``()``."""
    if astate == ():
        return ()
    return type(astate)(*(client_stack_spec(leaf.ndim, axis)
                          for leaf in astate))


def defense_state_specs(fstate) -> object:
    """Spec pytree for the defended-aggregation scan carry
    (``repro.core.faults.DefenseState``): the streaming norm-quantile
    tracker is a scalar every shard computes identically from the
    all-gathered norms, so it is replicated. Accepts the empty carry
    ``()`` (defense off / no clip tracker) and returns ``()``."""
    return replicated_specs(fstate)


def link_state_specs(lstate) -> object:
    """Spec pytree for the link-reliability scan carry
    (``repro.core.link.LinkState``): the [N] Gilbert-Elliott burst mask
    is drawn over the full client vector with a replicated key, so every
    shard carries the identical chain. Accepts the empty carry ``()``
    (link off) and returns ``()``."""
    return replicated_specs(lstate)


def shard_client_data(data, mesh: Mesh, axis: AxisSpec = CLIENTS_AXIS):
    """device_put the client stacks onto the mesh (client axis split
    across devices). The client count must already be mesh-divisible —
    build the stacks with ``stack_client_datasets(...,
    pad_to_multiple=client_shard_count(mesh))``."""
    n = int(data.lengths.shape[0])
    size = client_shard_count(mesh, axis)
    if n % size != 0:
        raise ValueError(
            f"client count {n} does not divide the {axis_names(axis)} mesh "
            f"axes ({size}); stack with pad_to_multiple={size} to add ghost "
            f"clients")
    specs = client_data_specs(data, axis)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), data, specs,
        is_leaf=lambda x: isinstance(x, P))
