"""Fused donation-aware train step.

Parity target: the reference's fused training executors (the static-graph
``ParallelExecutor``/``StandaloneExecutor`` train loop, where forward,
backward and the optimizer update are one Program run end-to-end by C++)
and its ``paddle.incubate`` fused optimizer paths. TPU redesign: the
imperative ``loss.backward(); opt.step()`` sequence is functionalized onto
ONE ``jax.jit`` program via the to_static machinery (jit/trace.py) with the
program's state argument — parameters, optimizer accumulators, BatchNorm
running stats — **donated** to XLA (``donate_argnums``). Donation lets XLA
write updated parameters into the buffers the old parameters occupied, which

* halves the HBM working set of the update (no live old+new copy), and
* removes the per-step Python dispatch of every layer/op — the host issues
  one executable per step.

Degradation contract (tier-1 / CPU): XLA on CPU ignores donation and warns
per dispatch, so donation auto-disables off-TPU (``donation_supported``);
everything still runs, just undonated. Donation never changes numerics —
it is purely a buffer-aliasing contract — which the donation parity test
(tests/test_train_step.py) pins: K donated fused steps must produce results
identical to the eager tape path.

After a donated step the previous parameter buffers are dead; the framework
rebinds every state Tensor to the program's outputs (CompiledProgram), so
user-visible Tensors stay valid — only raw ``jax.Array`` references captured
*before* the step are invalidated (the standard jax donation contract).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
from typing import Callable, Optional, Sequence

import jax

from ..core.tensor import Tensor, to_tensor
from .api import StaticFunction

__all__ = ["TrainStep", "make_train_step", "jit_step", "donation_supported"]


def donation_supported(backend: Optional[str] = None) -> bool:
    """True when the backend actually implements input/output buffer
    aliasing (TPU/GPU). CPU ignores donation and emits a per-dispatch
    warning — the fused step auto-disables donation there."""
    b = backend if backend is not None else jax.default_backend()
    return b not in ("cpu",)


def jit_step(fn: Callable, donate_argnums: Sequence[int] = (),
             static_argnums: Sequence[int] = (), annotation: str = "train"):
    """``jax.jit`` for functional train steps, with the perf-layer contract:

    * ``donate_argnums`` is applied only where the backend supports donation
      (CPU would warn on every dispatch and do nothing),
    * each dispatch runs under ``profiler.annotate_step(annotation, n)``
      so profiler tools group the trace by step (free unless a profiler
      session is active). ``n`` counts the calls of THIS wrapper since it
      was built: warm-up calls are in it and it restarts at 0 after a
      resume, so it orders a trace's steps and is not the trainer's step
      number.

    The raw jitted callable is available as ``wrapped._jitted``.
    """
    donate = tuple(donate_argnums) if donation_supported() else ()
    jfn = jax.jit(fn, donate_argnums=donate,
                  static_argnums=tuple(static_argnums))

    from ..profiler import annotate_step
    calls = itertools.count()

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with annotate_step(annotation, next(calls)):
            return jfn(*args, **kwargs)

    wrapped._jitted = jfn
    wrapped._donate_argnums = donate
    return wrapped


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _sum_losses(loss):
    if isinstance(loss, (list, tuple)):
        total = loss[0]
        for l in loss[1:]:
            total = total + l
        return total
    return loss


class TrainStep:
    """One fused program per input signature: forward + loss + backward +
    optimizer update (+ BN running-stat updates) with donated state.

    ``step(inputs, labels)`` returns the loss Tensor (or ``(loss, outputs)``
    with ``return_outputs=True`` — hapi needs outputs for metrics). The
    first call per function runs eagerly (lazy state — optimizer
    accumulators, lazily-built sublayers — initializes with real values,
    exactly like ``to_static``); later calls hit the compiled donated
    program.

    ``scaler``: a GradScaler with dynamic loss scaling branches on
    ``isfinite`` host-side, which cannot live inside one compiled program —
    when an enabled scaler is passed the step runs on the eager tape path
    instead (documented divergence; bf16 AMP on TPU needs no loss scaling,
    which is the fused path's target).

    ``sentinel``: the run-health NaN/Inf/loss-spike detector
    (health.sentinel), fused INTO the step: the mutable state (params,
    optimizer accumulators, master weights, BN running stats) is
    snapshotted before the update and ``jnp.where``-gated after it, so a
    bad step is a state no-op — the same skip-step semantics GradScaler
    applies on found_inf, decided on device with no extra host sync.
    ``True`` builds a Sentinel from the FLAGS_health_* defaults, or pass a
    configured ``health.Sentinel``; ``None`` follows
    ``FLAGS_health_sentinel``. The verdict is readable after each step via
    ``step.sentinel.last_record()`` (one fetch of the packed health
    vector).
    """

    def __init__(self, model, optimizer, loss_fn: Callable, *,
                 amp: bool = False, amp_level: str = "O1",
                 amp_dtype: str = "bfloat16", scaler=None,
                 donate: Optional[bool] = None,
                 return_outputs: bool = False, sentinel=None):
        from ..nn.layer import Layer

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._amp = bool(amp)
        self._amp_level = amp_level
        self._amp_dtype = amp_dtype
        self._scaler = scaler
        self._return_outputs = bool(return_outputs)
        self.donate = donation_supported() if donate is None else bool(donate)
        self._eager_only = scaler is not None and scaler.is_enable()
        if sentinel is None:
            from ..flags import flag
            sentinel = bool(flag("FLAGS_health_sentinel"))
        if sentinel is True:
            from ..health.sentinel import Sentinel
            sentinel = Sentinel()
        self.sentinel = sentinel or None

        def _fn(ins, labs):
            from .. import amp as amp_mod
            if self.sentinel is not None:
                # snapshot BEFORE forward: BN running stats mutate in the
                # forward pass and must also survive a skipped step
                from ..health.sentinel import health_state_tensors
                snap = self.sentinel.snapshot(
                    health_state_tensors(self.model, self.optimizer))
            cm = (amp_mod.auto_cast(level=self._amp_level,
                                    dtype=self._amp_dtype)
                  if self._amp else contextlib.nullcontext())
            with cm:
                out = self.model(*ins)
                outs = list(out) if isinstance(out, (list, tuple)) else [out]
                loss = _sum_losses(self.loss_fn(*outs, *labs))
            if self._scaler is not None and self._scaler.is_enable():
                self._scaler.scale(loss).backward()
                self._scaler.step(self.optimizer)
                self._scaler.update()
            else:
                loss.backward()
                self.optimizer.step()
            if self.sentinel is not None:
                # re-enumerate: accumulators/masters created BY this step
                # (first call) roll back to their unborn state
                self.sentinel.gate(snap, loss, health_state_tensors(
                    self.model, self.optimizer))
            self.optimizer.clear_grad()
            return (loss, out) if self._return_outputs else loss

        self._fn = _fn
        self._sf = None if self._eager_only else StaticFunction(
            _fn, donate_states=self.donate,
            layer=model if isinstance(model, Layer) else None)

    def __call__(self, inputs, labels=()):
        ins = [t if isinstance(t, Tensor) else to_tensor(t)
               for t in _as_list(inputs)]
        labs = [t if isinstance(t, Tensor) else to_tensor(t)
                for t in _as_list(labels)]
        self.model.train()
        from ..health import watchdog
        from ..profiler import annotate
        watchdog.touch()   # progress tick for the hang watchdog (free when off)
        with annotate("step"):
            if self._sf is None:
                return self._fn(ins, labs)
            return self._sf(ins, labs)


def make_train_step(model, optimizer, loss_fn: Callable,
                    **kwargs) -> TrainStep:
    """Build a fused donation-aware train step over an imperative model.

        step = make_train_step(net, opt, nn.CrossEntropyLoss(), amp=True)
        for x, y in prefetch_to_device(loader):
            loss = step(x, y)

    See :class:`TrainStep` for the amp/scaler/donate knobs. hapi's
    ``Model.prepare(..., jit=True)`` rides this path; ``Optimizer.fuse`` is
    the optimizer-side spelling.
    """
    return TrainStep(model, optimizer, loss_fn, **kwargs)
