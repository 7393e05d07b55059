"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. The dry-run process (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so 512 placeholder CPU devices exist; real TPU runtimes get the same
topology from the platform.

Axes:
  pod   — data-parallel across pods (DCN); scales to N pods unchanged.
  data  — data-parallel within a pod (ICI).
  model — tensor/expert parallel within a pod (ICI).
A future ``pipeline`` axis slots between pod and data (see DESIGN.md §5);
none of the assigned shapes requires PP on a 256-chip v5e pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through PartitionSpecs, ``with_sharding_constraint`` and ``shard_map``,
    which the default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if n % model_axis != 0:
        raise ValueError(
            f"make_local_mesh: {n} visible devices not divisible by "
            f"model_axis={model_axis} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N on CPU)")
    return auto_mesh((n // model_axis, model_axis), ("data", "model"))


def make_serve_mesh(n_shards: int = 0):
    """1-D ("model",) mesh for tensor-parallel serving (ServeEngine mesh=).

    Serving shards ONLY the head axis (weights column-wise, KV page pools
    on the KVp dim), so the serve mesh is one axis; data-parallel replica
    routing is a scheduler-level concern layered above, not a mesh axis
    (ROADMAP follow-up). ``n_shards=0`` takes every visible device —
    on CPU CI that is what ``--xla_force_host_platform_device_count``
    forced.
    """
    devs = jax.devices()
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(
            f"make_serve_mesh: asked for {n} shards but only {len(devs)} "
            f"devices are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} on CPU)")
    return auto_mesh((n,), ("model",), devices=devs[:n])


def mesh_batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_num_chips(mesh) -> int:
    return int(mesh.devices.size)
