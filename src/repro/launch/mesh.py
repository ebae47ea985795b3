"""Production meshes.  Functions, not module constants — importing this module
never touches jax device state (device count is locked at first use)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the visible devices with Auto (GSPMD) axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 (256 chips) per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over host devices for CPU integration tests."""
    return make_mesh((data, model), ("data", "model"))


LEAF_AXIS = "leaf"


def make_agg_mesh(num_leaves: int, devices=None):
    """1-D mesh over the aggregation tier's leaf axis.

    Each device on the axis is one LEAF aggregator of the hierarchical
    tier (core/fl/hierarchy.py): it owns a contiguous shard of session
    slots and produces a partial modular sum; the root combine is a psum
    over this axis.  ``devices`` pins an explicit device list (e.g. one
    TPU slice per leaf); default takes the first ``num_leaves`` of
    ``jax.devices()``.
    """
    if devices is None:
        avail = jax.devices()
        if num_leaves > len(avail):
            raise ValueError(
                f"aggregation tier wants {num_leaves} leaves but only "
                f"{len(avail)} devices are visible (force host devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        return make_mesh((num_leaves,), (LEAF_AXIS,))
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(num_leaves),
                             (LEAF_AXIS,))


def make_leaf_mesh(num_leaves: int, devices=None):
    """Mesh for ``num_leaves`` LOGICAL leaf aggregators, multiplexing when
    the machine has fewer devices than leaves.

    The two-level aggregation tier (core/fl/hierarchy.py) decouples the
    leaf count from the device count: each device on the leaf axis hosts
    ``num_leaves / axis_size`` logical leaves (their buffer rows shard
    contiguously over the axis, so a P("leaf") spec on a leading
    ``num_leaves`` dimension folds consecutive leaves onto one device).
    Picks the largest divisor of ``num_leaves`` that fits the visible
    device count; with enough devices this is one leaf per device.  A leaf
    count that divides badly (e.g. a prime count on a smaller machine)
    still runs, but on fewer devices than available — warned, since the
    silent throughput cliff is otherwise hard to diagnose.
    """
    avail = list(jax.devices()) if devices is None else list(devices)
    n = min(num_leaves, len(avail))
    while num_leaves % n:
        n -= 1
    if n < min(num_leaves, len(avail)):
        import warnings
        warnings.warn(
            f"{num_leaves} logical leaves only divide onto {n} of the "
            f"{len(avail)} available devices (largest divisor); pick a "
            f"leaf count that is a multiple of the device count to use "
            f"the whole mesh", stacklevel=2)
    return make_agg_mesh(n, None if devices is None else avail[:n])


def leaves_per_device(num_leaves: int, mesh) -> int:
    """How many logical leaves each device on the leaf axis hosts."""
    n = axis_size(mesh, LEAF_AXIS)
    if num_leaves % n:
        raise ValueError(
            f"{num_leaves} logical leaves do not divide evenly over the "
            f"{n}-device leaf mesh axis (use make_leaf_mesh)")
    return num_leaves // n


def axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def batch_axes(mesh):
    """The axes a client/batch dimension shards over."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
