"""``paddle.jit`` — dynamic-to-static compilation (see api.py / trace.py)."""

from .api import (InputSpec, StaticFunction, TranslatedLayer, enable_to_static,
                  ignore_module, load, not_to_static, save, to_static)
from .compile_cache import enable_compile_cache
from .control_flow import cond, fori_loop, scan, while_loop
from .train_step import TrainStep, donation_supported, jit_step, make_train_step
from . import dy2static

__all__ = ["InputSpec", "StaticFunction", "TranslatedLayer", "enable_to_static",
           "ignore_module", "load", "not_to_static", "save", "to_static",
           "cond", "fori_loop", "scan", "while_loop",
           "TrainStep", "make_train_step", "jit_step", "donation_supported",
           "enable_compile_cache"]
