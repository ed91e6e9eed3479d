"""Device mesh utilities.

The mesh is the TPU-native analog of the reference's device lists
(`ctx=[mx.gpu(i) ...]`) + comm topology (comm.h P2P rings): one
`jax.sharding.Mesh` whose axes name the parallelism dimensions
(data/model/seq/expert), with XLA inserting ICI/DCN collectives.
"""
from __future__ import annotations

from ..base import MXNetError

# Overridable device pool for mesh construction. The test harness (and any
# embedder that wants meshes on something other than jax.devices(), e.g. the
# virtual CPU devices from xla_force_host_platform_device_count) sets this
# via set_default_devices(); production code paths keep the real device set
# and fail loudly when a mesh doesn't fit.
_default_devices = None


def set_default_devices(devices):
    """Set the device pool used when create_mesh/default_mesh get no
    explicit devices. Pass None to restore jax.devices()."""
    global _default_devices
    _default_devices = list(devices) if devices is not None else None


def mark_varying(x, axis_name):
    """Mark a pytree of arrays device-varying along ``axis_name`` inside a
    shard_map body (loop-carry typing discipline for ppermute/all_to_all
    results)."""
    from jax import lax

    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return lax.pcast(x, axes, to="varying")


def axis_size(axis_name):
    """Static size of a mapped mesh axis inside a shard_map/pmap body
    (a concrete int, so loop bounds built from it stay static)."""
    from jax import lax

    return lax.axis_size(axis_name)


def local_devices(platform=None):
    import jax

    if platform:
        try:
            return jax.devices(platform)
        except RuntimeError:
            return []
    return jax.devices()


def _resolve_devices(devices):
    import jax

    if devices is not None:
        return list(devices)
    if _default_devices is not None:
        return list(_default_devices)
    return jax.devices()


def create_mesh(shape, axis_names, devices=None):
    """Create a Mesh of the given logical shape, e.g.
    create_mesh((2, 4), ('data', 'model'))."""
    import numpy as np
    from jax.sharding import Mesh

    devices = _resolve_devices(devices)
    n = 1
    for s in shape:
        n *= s
    if len(devices) < n:
        raise MXNetError(
            "mesh shape %s needs %d devices, only %d available" % (shape, n, len(devices))
        )
    dev_array = np.array(devices[:n]).reshape(shape)
    return Mesh(dev_array, axis_names)


def default_mesh(axis_name="data", devices=None):
    """1-D all-devices mesh — pure data parallelism."""
    devices = _resolve_devices(devices)
    return create_mesh((len(devices),), (axis_name,), devices)
