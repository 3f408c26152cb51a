"""Run-health subsystem: detect training anomalies on device, contain them
(skip), escalate to last-good restore, and fail fast on hangs.

PR 1 made the *storage* side fault tolerant (verified checkpoints,
last-good restore, preemption saves). This package is the *runtime* half
(docs/FAULT_TOLERANCE.md "Runtime anomalies"): without it a NaN loss
silently diverges the run, a corrupt sample poisons an epoch, and a
frozen rank hangs the job until a human notices. Three layers:

* :mod:`~paddle_tpu.health.sentinel` — on-device NaN/Inf/loss-spike
  detection fused into the train step (``jnp.where``-gated update, one
  scalar fetch, no recompile);
* :mod:`~paddle_tpu.health.monitor` — the skip -> restore -> abort
  escalation ladder (``HealthMonitor``) over
  ``distributed.checkpoint.AsyncCheckpointer``;
* :mod:`~paddle_tpu.health.watchdog` — in-process hang detection with
  thread-stack diagnoses; the launcher-side rank watchdog lives on
  ``distributed.elastic.HeartbeatMonitor``.

Surfaces: ``jit.train_step.TrainStep(sentinel=...)`` /
``Model.prepare(sentinel=...)``, the ``callbacks.AnomalyMonitor`` hapi
callback, ``FLAGS_health_*`` flags, and the
``nan_payload`` / ``bad_sample`` / ``dead_worker`` chaos injectors.
"""

from .monitor import (AnomalyRecord, HealthAbortError, HealthAction,
                      HealthMonitor)
from .sentinel import (Sentinel, guard_step, health_state_tensors,
                       sentinel_check, sentinel_init, tree_where,
                       unpack_health)
from .watchdog import (HUNG_EXIT_RC, HangWatchdog, WatchdogAlarm, install,
                       section, touch, uninstall)

__all__ = [
    "Sentinel", "guard_step", "sentinel_init", "sentinel_check",
    "tree_where", "unpack_health", "health_state_tensors",
    "HealthMonitor", "HealthAction", "HealthAbortError", "AnomalyRecord",
    "HangWatchdog", "WatchdogAlarm", "HUNG_EXIT_RC",
    "install", "uninstall", "touch", "section",
]
