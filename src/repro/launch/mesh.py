"""Production mesh definitions (TPU v5e) + the federated client axis.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 chips as (pod=2, data=16, model=16) — the ``pod``
axis is the federated-client boundary in CE-LoRA's mapping (DESIGN.md §3):
only the r×r C matrices ever cross it.

For simulated federated runs (many clients sharing one host or pod), the
``clients`` axis built by :func:`make_client_mesh` lays the LEADING client
axis of the batched runtime state (see :mod:`repro.core.client_batch`) over
the local devices; :func:`client_axis_sharding` produces the matching
NamedSharding pytree.  ``run_federated(..., client_parallelism="shard")``
is the consumer.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """Mesh whose axes GSPMD partitions on its own: the model's sharding
    constraints stay hints, as they are written, under ``jax.set_mesh``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh for CPU smoke runs (same axis names, trivial extents)."""
    return _auto_mesh((1, 1), ("data", "model"))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# federated client axis (the vectorized multi-client runtime)
# ---------------------------------------------------------------------------

def make_client_mesh(n_clients: int | None = None, devices=None) -> Mesh:
    """1-D ``("clients",)`` mesh over every local device.

    The stacked client axis must split evenly over the mesh (GSPMD requires
    divisibility), so a client count that the device count does not divide
    is refused: the mesh never quietly leaves devices idle.
    """
    devices = jax.local_devices() if devices is None else list(devices)
    if n_clients is not None and n_clients % len(devices):
        raise ValueError(f"{n_clients} clients do not split evenly over "
                         f"{len(devices)} devices; use a multiple of "
                         f"{len(devices)}")
    return Mesh(np.asarray(devices), ("clients",))


def client_axis_sharding(mesh: Mesh, tree) -> object:
    """NamedSharding pytree: leading (client) axis of every leaf on
    ``clients``, everything else replicated within a client's shard."""
    def one(leaf):
        return NamedSharding(
            mesh, P("clients", *(None,) * (leaf.ndim - 1)))
    return jax.tree.map(one, tree)


def shard_clients(mesh: Mesh, tree):
    """Lay a stacked client pytree over the ``clients`` mesh axis."""
    return jax.device_put(tree, client_axis_sharding(mesh, tree))
