"""One backend gate for every Pallas kernel in :mod:`paddle_tpu.kernels`.

This is the ONE place the platform / knob / interpret-mode resolution
lives; no kernel file and no caller re-derives ``jax.default_backend()``:

* :func:`on_tpu` — the raw platform predicate, for callers whose
  implementation is decided by the platform (the weight-only matmul, the
  functional attention entry).
* :func:`interpret` — whether ``pl.pallas_call`` runs in interpret mode:
  kernels compile natively on TPU and run interpreted everywhere else, so
  tier-1 (CPU) exercises the REAL kernel bodies. On TPU it is False; a
  kernel the TPU compiler refuses raises there — nothing retries it
  interpreted or through its reference.
* :func:`use_pallas` — resolve an on/off/auto knob (a ``FLAGS_*`` value or
  config field) to a dispatch decision, once, at construction. ``"auto"``
  is the platform's own path: the kernel on TPU, the XLA-composed path
  off it. ``True``/``"on"`` selects the kernel anywhere (interpret mode
  off-TPU — how tests pin the kernel path on CPU); ``False``/``None``/
  ``"off"`` selects the XLA-composed path anywhere. The choice is made
  from the platform and never from whether the kernel compiled.
"""

from __future__ import annotations

from typing import Any

import jax

__all__ = ["on_tpu", "interpret", "use_pallas"]

_ON = (True, 1, "on", "1", "true", "yes")
_OFF = (None, False, 0, "off", "0", "false", "no", "none", "")


def on_tpu() -> bool:
    """Whether the default jax backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Pallas interpret-mode switch: compile natively on TPU, interpret
    elsewhere (same kernel code, testable on the CPU mesh)."""
    return not on_tpu()


def use_pallas(knob: Any = "auto") -> bool:
    """Resolve a kernel on/off/auto knob to a dispatch decision.

    ``True``/``"on"`` -> the Pallas kernel (interpret mode off-TPU);
    ``False``/``None``/``"off"``/``""`` -> the XLA-composed path;
    ``"auto"`` -> :func:`on_tpu`. Unknown values raise a structured error
    naming the options.
    """
    k = knob.strip().lower() if isinstance(knob, str) else knob
    if isinstance(k, str):
        if k == "auto":
            return on_tpu()
        if k in _ON:
            return True
        if k in _OFF:
            return False
    elif k in (True, False, None) or isinstance(k, int):
        return bool(k)
    raise ValueError(f"unknown kernel-dispatch knob {knob!r}; options: "
                     f"True/'on', False/'off'/None, 'auto'")
