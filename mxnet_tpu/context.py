"""Device context model.

TPU-native equivalent of MXNet's ``Context`` (ref: include/mxnet/base.h:87,
python/mxnet/context.py). In MXNet a Context names a device (cpu/gpu) and every
NDArray lives on one; kernels are launched by the ThreadedEngine onto that
device's stream. Here a Context maps onto a ``jax.Device``; ordering/async
semantics are delegated to XLA's per-device program order.

``mx.gpu()`` is kept as an alias for the accelerator so reference user code
ports unchanged; ``mx.tpu()`` is the first-class accelerator context.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

_tls = threading.local()


def _accel_devices():
    """This process's TPU devices: [] when the default backend is the CPU.
    A backend that fails to initialise raises; it is never read as "no
    accelerator"."""
    return [d for d in jax.local_devices() if d.platform == "tpu"]


class Context:
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if isinstance(device_type, int):
            device_type = self.devtype2str[device_type]
        if device_type not in self.devstr2type:
            raise ValueError("unknown device type %r" % (device_type,))
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def jax_device(self):
        """Resolve to a concrete jax.Device. Device ids are PER-PROCESS like
        MXNet's (ref: python/mxnet/context.py — gpu(0) is this worker's first
        GPU): under multi-controller jax, jax.devices() lists every host's
        devices, so indexing it would hand other ranks a remote device.

        ``gpu(i)``/``tpu(i)`` both mean TPU ``i`` of this process and raise
        when it does not exist — never another chip, never the CPU. ``cpu(i)``
        names host memory whatever ``i`` is (upstream's cpu ids are labels),
        so ids past the last CPU device share it."""
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = jax.local_devices(backend="cpu")
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accel_devices()
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%r does not exist: this process has %d TPU device(s)"
                % (self, len(devs)))
        return devs[self.device_id]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __enter__(self):
        if not hasattr(_tls, "stack"):
            _tls.stack = []
        _tls.stack.append(self)
        return self

    def __exit__(self, *a):
        _tls.stack.pop()

    @classmethod
    def default_ctx(cls):
        stack = getattr(_tls, "stack", None)
        if stack:
            return stack[-1]
        return _resolve_default()


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    """TPU devices of this process; 0 on a CPU-only backend, as upstream
    reports 0 GPUs."""
    return len(_accel_devices())


def num_tpus():
    return num_gpus()


def gpu_memory_info(device_id=0):
    """(free, total) bytes on the accelerator (ref: context.py:
    gpu_memory_info). On TPU this reads the device's HBM allocator stats;
    raises when no accelerator exists, like upstream on a CPU-only host."""
    devs = _accel_devices()
    if not 0 <= device_id < len(devs):
        raise RuntimeError("no accelerator device %d" % device_id)
    stats = devs[device_id].memory_stats() or {}
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    if not total:  # upstream raises on hosts without accelerator memory
        raise RuntimeError(
            "device %r reports no memory stats (no accelerator HBM)"
            % (devs[device_id],))
    return (total - used, total)


def current_context():
    return Context.default_ctx()


def context_from_device(dev) -> Context:
    if dev.platform == "cpu":
        return cpu(dev.id)
    return tpu(dev.id)


# Default context: the accelerator if present, else cpu — unlike MXNet (cpu
# default) because on this stack there is always exactly one sensible device.
#
# Resolution is LAZY (first use, not import): upstream MXNet likewise imports
# cleanly with zero GPUs (python/mxnet/context.py resolves devices on demand).
# A backend that fails to initialise raises from the first use.
_default = None


def _resolve_default():
    global _default
    if _default is None:
        _default = Context(
            "cpu" if jax.default_backend() == "cpu" else "tpu", 0)
    return _default
