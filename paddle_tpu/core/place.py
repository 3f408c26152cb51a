"""Device placement.

Parity target: Paddle's ``Place`` hierarchy (``phi::Place``, ``paddle.CPUPlace()``,
``paddle.CUDAPlace(id)``, custom places; reference: ``paddle/phi/common/place.h``) and
``paddle.device.set_device``/``get_device``. Here the accelerator is TPU via PJRT;
``TPUPlace(i)`` maps to ``jax.devices()[i]`` of the TPU platform, ``CPUPlace`` to the
host platform. ``CUDAPlace`` is accepted as an alias of ``TPUPlace`` so reference
scripts run unmodified (a deliberate compatibility shim, logged once).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

import jax

__all__ = ["Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "set_device",
           "get_device", "device_count", "is_compiled_with_cuda",
           "is_compiled_with_xpu", "is_compiled_with_tpu", "get_jax_device",
           "cpu_requested"]


def cpu_requested() -> bool:
    """Whether the caller's environment says ``JAX_PLATFORMS=cpu`` in so
    many words — the one condition under which an entry point may run its
    CPU simulation (bench's toy preset, the launcher's several processes
    per node, the dry run's virtual mesh). Resolving to the CPU because no
    chip was found is not it."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


class Place:
    """Base place. Equality by (kind, device id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return isinstance(other, Place) and self.kind == other.kind \
            and self.device_id == other.device_id

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def get_device_id(self) -> int:
        return self.device_id

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_tpu_place(self):
        return self.kind == "tpu"

    # Paddle-API parity
    def is_gpu_place(self):
        return self.is_tpu_place()


class CPUPlace(Place):
    kind = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    kind = "tpu"


_warned_cuda = False


def CUDAPlace(device_id: int = 0) -> TPUPlace:  # noqa: N802 — Paddle class-style name
    global _warned_cuda
    if not _warned_cuda:
        warnings.warn("CUDAPlace is mapped to TPUPlace on this build", stacklevel=2)
        _warned_cuda = True
    return TPUPlace(device_id)


def XPUPlace(device_id: int = 0) -> TPUPlace:  # noqa: N802
    return TPUPlace(device_id)


def _accelerator_platform() -> Optional[str]:
    for d in jax.devices():
        if d.platform != "cpu":
            return d.platform
    return None


def _default_place() -> Place:
    return TPUPlace(0) if _accelerator_platform() else CPUPlace()


class _DeviceState(threading.local):
    def __init__(self):
        self.place: Optional[Place] = None


_state = _DeviceState()


def _current_place() -> Place:
    if _state.place is None:
        _state.place = _default_place()
    return _state.place


def set_device(device) -> Place:
    """``paddle.device.set_device('tpu:0' | 'cpu' | Place)``."""
    if isinstance(device, Place):
        _state.place = device
        return device
    s = str(device).lower()
    if s in ("cpu",):
        _state.place = CPUPlace()
    else:
        name, _, idx = s.partition(":")
        if name in ("tpu", "gpu", "cuda", "xpu"):
            _state.place = TPUPlace(int(idx) if idx else 0)
        else:
            raise ValueError(f"unknown device {device!r}")
    return _state.place


def get_device() -> str:
    p = _current_place()
    return "cpu" if p.is_cpu_place() else f"tpu:{p.device_id}"


def get_jax_device(place: Optional[Place] = None):
    """Resolve a Place to a concrete jax.Device. A ``TPUPlace`` on a
    machine whose jax found no accelerator, or with an id past the last
    chip, is an error naming what was found — never a CPU device or
    another chip under the requested name."""
    place = place or _current_place()
    if place.is_cpu_place():
        return jax.devices("cpu")[0]
    plat = _accelerator_platform()
    if plat is None:
        raise RuntimeError(
            f"{place!r} requested, but jax found no accelerator: backend "
            f"{jax.default_backend()!r}, devices {jax.devices()}")
    devs = jax.devices(plat)
    if not 0 <= place.device_id < len(devs):
        raise ValueError(
            f"{place!r} requested, but the {plat!r} platform has "
            f"{len(devs)} device(s): ids 0..{len(devs) - 1}")
    return devs[place.device_id]


def device_count() -> int:
    plat = _accelerator_platform()
    return len(jax.devices(plat)) if plat else 0


def is_compiled_with_cuda() -> bool:
    # Reference scripts gate GPU paths on this; the accelerator here is TPU.
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() is not None
