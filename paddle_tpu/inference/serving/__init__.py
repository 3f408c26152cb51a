"""Continuous-batching LLM serving (docs/SERVING.md, docs/OPS.md).

The high-traffic decode tier: a paged KV cache (block pool + per-slot block
tables; ``models.generation`` holds the device math), an iteration-level
scheduler (retire/admit every step, Orca-style) with a pluggable admission
policy (FIFO / priority / weighted fair share / EDF — ``policies``), an
overload-safe request lifecycle (cancel / timeout / deadline / shed, every
terminal state freeing its KV blocks), and the :class:`ServingEngine` API
(`submit()/step()/stream()/run()/cancel()/health_snapshot()`) that
``inference.GenerationPredictor.serve`` rides. The production front line
sits on top (ISSUE 7): :class:`EngineSupervisor` (crash barrier, restart
budget, bit-exact resubmission, graceful drain, TPOT/autoscale telemetry)
and the asyncio :class:`ServingServer` (one event loop multiplexing many
SSE-style streaming clients onto one supervised engine thread, with
``/healthz`` / ``/readyz`` / ``/metrics`` endpoints). Above the replicas
sits the fleet tier (ISSUE 9): :class:`ServingRouter` fronts N supervised
replicas sharing one set of params and one compiled
:class:`EnginePrograms` — health-probed power-of-two-choices routing with
prefix/tenant affinity, cross-replica failover (bit-exact resume from
delivered tokens), per-replica :class:`CircuitBreaker`\\ s, hedged
retries, autoscale actuation and rolling restarts (docs/OPS.md "Serving
fleet"). Measured on the chip by ``benchmark/run.py``'s serving cells and
driven through hostile-traffic faults by ``testing.chaos``'s serving
injectors. The fleet-scale proof layer
(ISSUE 13) sits across all of it: :class:`InvariantAuditor` — one
registry of named invariants (``AUDIT_CHECKS``) replacing the asserts
scattered through the test suite, surfaced in production via
``FLAGS_serving_audit`` — and the deterministic workload replay
(:class:`WorkloadSpec` / :func:`run_replay`): seeded traces with
diurnal/bursty arrivals, Zipf tenants, shared-prefix families and
client misbehavior, driven through a multi-replica router under a
seeded chaos timeline with the autoscaler actuating, emitting a
replay manifest (bit-exact reproduction) and a capacity-planning
report (``capacity_report`` + the ``serving_replay_goodput`` metric).
"""

from .audit import AUDIT_CHECKS, InvariantAuditor, InvariantViolation
from .engine import (EnginePrograms, HEALTH_SNAPSHOT_FIELDS,
                     SUPERVISOR_SNAPSHOT_KEYS, ServingConfig, ServingEngine)
from .journal import JournalRecord, RequestJournal
from .paged_cache import BlockManager, PagedKVCache
from .policies import (AdmissionPolicy, EDFPolicy, FairSharePolicy,
                       FIFOPolicy, POLICIES, PriorityPolicy, resolve_policy)
from .scheduler import (CANCELLED, FINISHED, QUEUED, RUNNING, SHED,
                        TERMINAL_STATES, TIMED_OUT, Request, Scheduler,
                        ServingQueueFull)
from .replica import (BREAKER_CLOSED, BREAKER_HALF_OPEN, BREAKER_OPEN,
                      CircuitBreaker, Replica)
from .router import (ROUTER_HEALTH_FIELDS, RouterConfig, RouterRequest,
                     ServingRouter)
from .server import ClientStream, ServingServer, serve_requests, sse_encode
from .supervisor import (EngineSupervisor, FAILED, ServingUnavailable,
                         TrackedRequest, autoscale_signal)
from .workload import (ReplayManifest, TraceRequest, WorkloadSpec,
                       capacity_report, generate_trace, run_replay)

__all__ = ["ServingEngine", "ServingConfig", "PagedKVCache", "BlockManager",
           "Scheduler", "Request", "ServingQueueFull",
           "AdmissionPolicy", "FIFOPolicy", "PriorityPolicy",
           "FairSharePolicy", "EDFPolicy", "POLICIES", "resolve_policy",
           "QUEUED", "RUNNING", "FINISHED", "CANCELLED", "TIMED_OUT",
           "SHED", "TERMINAL_STATES", "FAILED",
           "EngineSupervisor", "ServingUnavailable", "TrackedRequest",
           "autoscale_signal", "ServingServer", "ClientStream",
           "serve_requests", "sse_encode", "EnginePrograms",
           "HEALTH_SNAPSHOT_FIELDS", "SUPERVISOR_SNAPSHOT_KEYS",
           "ServingRouter", "RouterConfig", "RouterRequest",
           "ROUTER_HEALTH_FIELDS", "Replica", "CircuitBreaker",
           "BREAKER_CLOSED", "BREAKER_OPEN", "BREAKER_HALF_OPEN",
           "InvariantAuditor", "InvariantViolation", "AUDIT_CHECKS",
           "WorkloadSpec", "TraceRequest", "generate_trace",
           "ReplayManifest", "run_replay", "capacity_report",
           "RequestJournal", "JournalRecord"]
