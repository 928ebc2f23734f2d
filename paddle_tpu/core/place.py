"""Device places (reference: paddle/fluid/platform/place.h).

The reference models devices as a CPUPlace/CUDAPlace/CUDAPinnedPlace variant;
here TPUPlace is the first-class device (the survey's north star: "this is
where TPUPlace slots in", SURVEY §2.3).  A Place resolves to a JAX device;
TPUPlace resolves to a TPU or raises — a host without one runs on the CPU
only by saying CPUPlace.  CUDAPlace is accepted for API compatibility and
is TPUPlace under another name, so reference scripts run unmodified on a
TPU host.
"""

from __future__ import annotations

import jax

__all__ = ["Place", "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
           "is_compiled_with_cuda", "device_is_tpu"]


def device_is_tpu(device) -> bool:
    """True when a resolved jax device is a TPU.  Executors key the
    trace-time defaults scope (flags.tpu_trace_scope: auto conv layout,
    auto AMP tier) off the ACTUAL device platform, not the Place class."""
    return getattr(device, "platform", "") == "tpu"


class Place:
    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        # process-LOCAL devices: under jax.distributed the global list leads
        # with other processes' (non-addressable) devices, and a Place must
        # resolve to one this host can feed (test_multihost.py)
        devices = [
            d for d in self._platform_devices()
            if d.process_index == jax.process_index()
        ] or self._platform_devices()
        return devices[self.device_id % len(devices)]

    def _platform_devices(self):
        return jax.devices()


class CPUPlace(Place):
    def _platform_devices(self):
        return jax.devices("cpu")


class TPUPlace(Place):
    """A TPU device.  jax_device() raises jax's own RuntimeError on a
    backend with no TPU: there is no fallback to another platform."""

    def _platform_devices(self):
        return jax.devices("tpu")


class CUDAPlace(TPUPlace):
    """Compatibility alias for reference scripts that say CUDAPlace: it is
    a TPUPlace, and like it raises where the backend has no TPU."""


class CUDAPinnedPlace(CPUPlace):
    pass


def is_compiled_with_cuda() -> bool:
    return False
