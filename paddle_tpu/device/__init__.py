"""``paddle.device`` parity (ref: ``python/paddle/device/__init__.py``).

Device selection maps onto jax device handles (core/place.py); the cuda
submodule namespace exists with honest negatives (no CUDA on this stack).
"""

from __future__ import annotations

from ..core.place import (CPUPlace, CUDAPlace, Place, TPUPlace, XPUPlace,
                          get_device, is_compiled_with_cuda,
                          is_compiled_with_tpu, is_compiled_with_xpu,
                          set_device)

__all__ = ["get_device", "set_device", "get_all_device_type",
           "get_all_custom_device_type", "get_available_device",
           "get_available_custom_device", "is_compiled_with_cuda",
           "is_compiled_with_xpu", "is_compiled_with_tpu", "device_count",
           "synchronize", "cuda"]


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()} | {"cpu"})


def get_all_custom_device_type():
    return ["tpu"] if is_compiled_with_tpu() else []


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    import jax
    return [f"tpu:{d.id}" for d in jax.devices() if d.platform == "tpu"]


def device_count() -> int:
    import jax
    return jax.device_count()


def synchronize(device=None):
    """Block until all dispatched device work completes."""
    import jax
    import jax.numpy as jnp
    # PJRT runs one device's programs in dispatch order, so a fresh tiny
    # computation on each device completes after everything queued before it
    for d in jax.local_devices():
        jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 0)


class _CudaNamespace:
    """paddle.device.cuda — honestly absent on the TPU stack."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def empty_cache():
        return None

    @staticmethod
    def get_device_properties(device=None):
        raise RuntimeError("paddle.device.cuda: no CUDA devices on the TPU "
                           "stack; use paddle.device.get_available_device()")


cuda = _CudaNamespace()
