"""Device context with a first-class TPU device.

Re-design of the reference Context (ref: python/mxnet/context.py:1-126,
include/mxnet/base.h:85-118). `mx.tpu(i)` slots in alongside `cpu()` per
SURVEY.md §7 step 1. `gpu(i)` is kept so reference-era scripts run
unmodified: it resolves to the i-th accelerator device (TPU here). A
`tpu`/`gpu` context with no accelerator attached raises: code that wants
"the chip if there is one" asks `num_devices("tpu")` and says so.

Device resolution maps a Context onto a concrete `jax.Device`. Multiple
`cpu(i)` contexts map onto the virtual CPU devices created by
``--xla_force_host_platform_device_count`` — this is the reference's
"plural device ids in one process simulate multi-worker" testing trick
(ref: tests/python/unittest/test_kvstore.py, SURVEY §4.3).
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_devices"]


class Context:
    """Device context (ref: python/mxnet/context.py:7).

    Works as a with-scope: ``with mx.tpu(0): ...`` sets the default
    context for array creation inside the block.
    """

    # ref: include/mxnet/base.h:88-92 (kCPU=1, kGPU=2, kCPUPinned=3); kTPU is new.
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                device_type = self.devstr2type[device_type]
            self.device_typeid = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- JAX device resolution -------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device this context denotes. Device ids index
        *this process's* devices: under multi-process jax.distributed,
        jax.devices() is the global list and other processes' devices are
        not addressable — a Context always means local hardware (the
        reference's device ids are per-node too)."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _local_cpu_devices()
        else:  # tpu / gpu -> the accelerator backend, never the host
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    "%s: no accelerator attached (jax.local_devices() = %s)"
                    % (self, jax.local_devices()))
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s: device_id %d out of range (%d %s device(s) visible)"
                % (self, self.device_id, len(devs), self.device_type)
            )
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default, "stack"):
            Context._default.stack = []
        Context._default.stack.append(self)
        return self

    def __exit__(self, ptype, value, trace):
        Context._default.stack.pop()


def default_jax_device():
    """The jax.Device that uncommitted computation lands on: the pinned
    ``jax_default_device`` (a Device or a platform name; tests pin the
    CPU while the TPU plugin is loaded), else the default backend's
    first device."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None or isinstance(dev, str):
        dev = jax.devices(dev)[0]
    return dev


def _accelerator_devices():
    """Local accelerator devices: under multi-process jax.distributed,
    jax.devices() is global and other processes' chips are not
    addressable — Context device ids index this process's hardware."""
    import jax

    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _local_cpu_devices():
    """This process's cpu devices. jax.local_devices() only enumerates
    the default backend (tpu on accelerator hosts), so ask the cpu
    backend explicitly."""
    import jax

    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        return jax.devices("cpu")


def cpu(device_id=0):
    """CPU context (ref: python/mxnet/context.py:90)."""
    return Context(1, device_id)


def gpu(device_id=0):
    """Accelerator context, kept for script compatibility; on this stack it
    is the TPU (ref: python/mxnet/context.py:108)."""
    return Context(2, device_id)


def cpu_pinned(device_id=0):
    """Pinned-host context (ref: include/mxnet/base.h:90). On TPU hosts this
    is plain host memory; kept so reference scripts parse."""
    return Context(3, device_id)


def tpu(device_id=0):
    """TPU context — the new first-class device (BASELINE.json north-star)."""
    return Context(4, device_id)


def current_context():
    """Default context (ref: python/mxnet/context.py:126). The bottom of
    the stack is cpu(0) unless overridden by
    ``test_utils.set_default_context`` (ref Context.default_ctx)."""
    stack = getattr(Context._default, "stack", None)
    if stack:
        return stack[-1]
    return getattr(Context, "_default_bottom", None) or Context(1, 0)


def num_devices(device_type="tpu"):
    """Count visible devices of a type; not in the 2016 reference but needed
    for device-count-parametrised tests and launchers."""
    import jax

    if device_type in ("cpu", "cpu_pinned"):
        return len(_local_cpu_devices())
    return len(_accelerator_devices())
