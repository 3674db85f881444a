"""Device context.

Reference: ``Context`` in include/mxnet/base.h:93-122 (cpu/gpu/cpu_pinned
device types + device id).  TPU-native redesign: a Context names a JAX/PJRT
device.  ``mx.tpu()`` is first-class; ``mx.gpu()`` aliases onto the local
accelerator so reference-era scripts keep running on TPU machines.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_context",
           "num_tpus", "num_gpus"]


def _jax():
    import jax

    return jax


def _devices_for(device_type):
    jax = _jax()
    # process-LOCAL devices only: under jax.distributed (tools/launch.py /
    # multi-host pods) the global list contains other ranks' devices,
    # which are non-addressable — ctx device ids index this rank's chips,
    # exactly like the reference's per-worker gpu(i) numbering
    if device_type == "cpu":
        try:
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            # No explicit cpu backend registered: fall back to default devices
            # if they are cpu, else empty.
            devs = jax.local_devices()
            return [d for d in devs if d.platform == "cpu"]
    # Any accelerator platform counts as "tpu"/"gpu" here.
    devs = jax.local_devices()
    accel = [d for d in devs if d.platform != "cpu"]
    return accel


class Context:
    """A device context: (device_type, device_id) naming one PJRT device."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        self.device_type = device_type
        self.device_id = device_id
        self._device = None

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    @property
    def jax_device(self):
        """Resolve to the concrete PJRT device (lazy; cached)."""
        if self._device is None:
            kind = "cpu" if self.device_type.startswith("cpu") else "accel"
            devs = _devices_for("cpu" if kind == "cpu" else "tpu")
            if not devs:
                raise MXNetError(
                    "no %s device available (jax sees: %s)"
                    % (self.device_type, [d.platform for d in _jax().devices()])
                )
            if self.device_id >= len(devs):
                raise MXNetError(
                    "device id %d out of range: only %d %s device(s)"
                    % (self.device_id, len(devs), self.device_type)
                )
            self._device = devs[self.device_id]
        return self._device

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

    def __str__(self):
        return repr(self)

    def __enter__(self):
        if not hasattr(self._default_ctx, "stack"):
            self._default_ctx.stack = []
        self._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        self._default_ctx.stack.pop()

    def empty_cache(self):
        """Reference: Storage pool release (MXStorageEmptyCache).  PJRT owns
        pooling; provided for API compat."""


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compat alias: on TPU machines this resolves to the accelerator."""
    return Context("gpu", device_id)


def num_tpus():
    return len(_devices_for("tpu"))


def num_gpus():
    return num_tpus()


def current_context():
    """The innermost ``with ctx:`` scope, else the device JAX itself
    defaults to: ``tpu(0)`` on a machine with a chip, ``cpu(0)`` without.
    (A ``cpu(0)`` default there would COMMIT every ``nd.array`` to the
    host, and one committed host array makes XLA run the whole
    computation it joins on the host, with no error.)"""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu" if _jax().default_backend() == "cpu" else "tpu", 0)
