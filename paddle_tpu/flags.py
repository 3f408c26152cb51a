"""Runtime flag registry.

Capability parity with Paddle's FLAGS_* system (reference: ``paddle/utils/flags.h``,
registry in ``paddle/phi/core/flags.cc``; Python surface ``paddle.set_flags`` /
``paddle.get_flags``): typed flags, defined at import time, overridable from the
environment (``FLAGS_name=value``) and at runtime. Redesigned as a plain typed Python
registry — there is no C++ gflags clone to wrap because on TPU the runtime toggles that
matter (XLA options, libtpu options) pass through ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS``,
which :func:`set_flags` also accepts transparently.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

__all__ = ["define_flag", "get_flags", "set_flags", "flag", "flags_table"]


@dataclass
class _FlagDef:
    name: str
    default: Any
    type: type
    help: str
    value: Any = None
    on_change: Optional[Callable[[Any], None]] = None


_registry: Dict[str, _FlagDef] = {}
_lock = threading.Lock()


def _coerce(defn: _FlagDef, value: Any) -> Any:
    if defn.type is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    return defn.type(value)


def define_flag(name: str, default: Any, help: str = "", type: Optional[type] = None,
                on_change: Optional[Callable[[Any], None]] = None) -> None:
    """Register a flag. Environment variable ``FLAGS_<name>`` overrides the default."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    ftype = type if type is not None else default.__class__
    defn = _FlagDef(name=name, default=default, type=ftype, help=help, on_change=on_change)
    env = os.environ.get(name)
    defn.value = _coerce(defn, env) if env is not None else default
    with _lock:
        _registry[name] = defn


_MISSING = object()


def flag(name: str, default: Any = _MISSING) -> Any:
    """Fast read of a single flag value. With ``default``, an unknown
    flag returns it instead of raising (lets early-import callers read
    flags without a try/except per site)."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    d = _registry.get(name)
    if d is None:
        if default is not _MISSING:
            return default
        raise KeyError(name)
    return d.value


def flags_table(names) -> List[str]:
    """Markdown ``| flag | default | gates |`` rows for ``names``, straight
    from the live registry (the help text's first sentence). The ONE
    renderer behind every generated flag table (tools/refresh_docs.py and
    ops/gen_docs.py), so docs/SERVING.md, docs/FAULT_TOLERANCE.md and
    docs/OPS.md can never diverge in format."""
    rows = ["| flag | default | gates |", "|------|---------|-------|"]
    for name in names:
        d = _registry[name]
        first = d.help.split(". ")[0].rstrip(".") + "."
        rows.append(f"| `{name}` | `{d.default}` | {first} |")
    return rows


def get_flags(names=None) -> Dict[str, Any]:
    if names is None:
        return {k: d.value for k, d in _registry.items()}
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        out[n] = _registry[key].value
    return out


def set_flags(flags_dict: Dict[str, Any]) -> None:
    """Set flags at runtime (``paddle.set_flags`` equivalent).

    Unknown ``XLA_``/``LIBTPU_`` prefixed keys are exported to the environment so they
    reach XLA/libtpu on next backend init.
    """
    for name, value in flags_dict.items():
        if name.startswith(("XLA_", "LIBTPU_", "TPU_")):
            os.environ[name] = str(value)
            continue
        key = name if name.startswith("FLAGS_") else "FLAGS_" + name
        if key not in _registry:
            raise ValueError(f"unknown flag {name!r}; known: {sorted(_registry)[:20]}...")
        defn = _registry[key]
        defn.value = _coerce(defn, value)
        if defn.on_change is not None:
            defn.on_change(defn.value)


# ---------------------------------------------------------------------------
# Core flags (Paddle equivalents noted).
# ---------------------------------------------------------------------------
def _wire_debug_nans(value: bool) -> None:
    # jit-path coverage: XLA traps NaN production inside compiled programs
    # (the eager scan below cannot see into a jitted step)
    import jax
    jax.config.update("jax_debug_nans", bool(value))


define_flag("FLAGS_check_nan_inf", False, "Scan every op output for NaN/Inf in eager "
            "mode AND enable jax_debug_nans for compiled programs "
            "(ref: FLAGS_check_nan_inf / nan_inf_utils_detail).", bool,
            on_change=_wire_debug_nans)
define_flag("FLAGS_retain_grad_for_all_tensor", False,
            "Accumulate .grad for non-leaf tensors too.", bool)
define_flag("FLAGS_eager_op_jit", True,
            "Dispatch eager ops through a cached jax.jit per (op, shapes, dtypes).", bool)
define_flag("FLAGS_use_stride_kernel", False, "Accepted for API parity; XLA manages "
            "layout so strides are not user-visible.", bool)
define_flag("FLAGS_cudnn_deterministic", True, "Accepted for API parity; XLA on TPU is "
            "deterministic by default.", bool)
define_flag("FLAGS_embedding_deterministic", 1, "API parity; deterministic on TPU.", int)
define_flag("FLAGS_allocator_strategy", "auto_growth", "API parity; PJRT owns device "
            "memory (ref: auto_growth_best_fit_allocator).", str)
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "API parity; unused on TPU.", float)
define_flag("FLAGS_log_level", 0, "Framework VLOG level (ref: GLOG_v).", int)
define_flag("FLAGS_checkpoint_verify", True,
            "Verify SHA-256 integrity (tier-1 footer, tier-3 shard manifests) "
            "on paddle.load / distributed checkpoint load; corruption raises "
            "CheckpointCorruptionError instead of unpickling garbage "
            "(docs/FAULT_TOLERANCE.md).", bool)
define_flag("FLAGS_emergency_ckpt_deadline_s", 10.0,
            "Default deadline (s) for the SIGTERM emergency checkpoint in "
            "elastic.install_preemption_handler when the launcher's "
            "PADDLE_PREEMPT_GRACE is not set; must sit inside the "
            "infrastructure's kill grace.", float)

# ---------------------------------------------------------------------------
# Run-health sentinel / recovery (paddle_tpu.health; docs/FAULT_TOLERANCE.md
# "Runtime anomalies"). The FLAGS_health_ prefix is the generated-docs key.
# ---------------------------------------------------------------------------
define_flag("FLAGS_health_sentinel", False,
            "Default for TrainStep/Model.prepare's sentinel knob: fuse the "
            "on-device NaN/Inf/loss-spike detector into the train step and "
            "skip bad updates (jnp.where-gated).", bool)
define_flag("FLAGS_health_spike_factor", 0.0,
            "Loss-spike threshold: a step is bad when loss > factor * |EMA| "
            "(after FLAGS_health_spike_warmup good steps). 0 disables the "
            "spike test; NaN/Inf detection is always on when the sentinel "
            "is.", float)
define_flag("FLAGS_health_spike_warmup", 20,
            "Good steps required to seed the loss EMA before the spike test "
            "arms (early-training loss is legitimately volatile).", int)
define_flag("FLAGS_health_skip_threshold", 3,
            "K: consecutive bad steps before HealthMonitor escalates from "
            "skip to a last-good checkpoint restore.", int)
define_flag("FLAGS_health_max_restores", 3,
            "M: last-good restores before HealthMonitor aborts with a "
            "diagnosis (HealthAbortError) instead of burning more TPU "
            "hours.", int)
define_flag("FLAGS_health_lr_backoff", 1.0,
            "LR multiplier applied per health restore (HealthMonitor."
            "lr_scale; AnomalyMonitor applies it to the optimizer). 1.0 = "
            "no backoff.", float)
define_flag("FLAGS_health_data_retries", 0,
            "Default DataLoader retries for a failing Dataset.__getitem__ "
            "(bounded backoff between attempts). 0 keeps the raise-through "
            "behavior.", int)
define_flag("FLAGS_health_data_backoff_s", 0.05,
            "Base backoff (seconds, doubled per attempt) between "
            "Dataset.__getitem__ retries.", float)
define_flag("FLAGS_health_worker_restarts", 0,
            "Default max resurrections of a dead DataLoader worker "
            "(map-style datasets; in-flight batches are re-queued). 0 keeps "
            "the fail-fast behavior.", int)
define_flag("FLAGS_health_watchdog_timeout_s", 0.0,
            "health.watchdog.install() default: seconds without a progress "
            "tick before the in-process hang watchdog fires (stack-dump "
            "diagnosis; fatal=True exits HUNG_EXIT_RC). 0 = off.", float)

# ---------------------------------------------------------------------------
# Serving engine (paddle_tpu.inference.serving; docs/SERVING.md). The
# FLAGS_serving_ prefix is the generated-docs key. These are the DEFAULTS
# ServingConfig resolves when a field is left unset — explicit ServingConfig
# values always win.
# ---------------------------------------------------------------------------
define_flag("FLAGS_serving_block_size", 16,
            "Paged-KV-cache block size (tokens per physical block). Smaller "
            "blocks waste less capacity per sequence tail but deepen the "
            "block tables.", int)
define_flag("FLAGS_serving_max_slots", 8,
            "Decode slots in the continuous-batching step — the fixed batch "
            "dimension of the ONE compiled decode program. Retired slots "
            "are refilled from the admission queue every iteration.", int)
define_flag("FLAGS_serving_max_model_len", 2048,
            "Per-sequence KV capacity bound (prompt + generated - 1 KV "
            "entries); sets the static block-table width "
            "ceil(len / block_size).", int)
define_flag("FLAGS_serving_queue_depth", 128,
            "Admission-queue bound: submits beyond this raise "
            "ServingQueueFull instead of growing host memory unboundedly.",
            int)
define_flag("FLAGS_serving_decode_chunk", 8,
            "Cap on decode iterations per device dispatch when a live "
            "request can retire EARLY (EOS enabled) or the caller "
            "streams token events. "
            "Otherwise dispatches are schedule-sized: run to the next "
            "budget retirement (queue waiting) or drain the tail in one "
            "dispatch (queue empty) — the bound is a device scalar, so "
            "sizing never retraces.", int)
define_flag("FLAGS_serving_prefix_cache", True,
            "Automatic prefix caching: full KV blocks are content-hashed "
            "(chained block-aligned token-id keys) into the ref-counted "
            "BlockManager table, so requests sharing a system-prompt/"
            "few-shot prefix map the cached blocks instead of re-running "
            "prefill over them. Refcount-0 blocks stay cached (LRU) until "
            "allocation pressure evicts them. ServingConfig(prefix_cache="
            "None/False) disables per engine.", bool)
define_flag("FLAGS_serving_prefill_chunk", 256,
            "Chunked prefill: prompts longer than this prefill in chunks "
            "of this many tokens, each riding the decode dispatch as extra "
            "query rows of ONE mixed step, so a long admission never "
            "freezes in-flight streams. 0 "
            "disables (whole prompt in one dispatch); ServingConfig("
            "prefill_chunk=None) disables per engine.", int)
define_flag("FLAGS_serving_preempt", True,
            "On-demand KV paging: a sequence holds only the blocks it has "
            "filled, and when the pool runs dry the newest-admitted "
            "running sequence is preempted (blocks freed, re-queued for "
            "recompute-on-readmission) instead of refusing admission. "
            "False restores the legacy reservation-at-admission policy "
            "(prompt + max_new - 1 KV entries charged up front, "
            "conservative admission, no preemption).", bool)

define_flag("FLAGS_serving_paged_kernel", "auto",
            "Decode attention path for the paged serving engine "
            "(ServingConfig.paged_kernel), resolved once at engine "
            "construction from the platform: 'auto' is the Pallas "
            "flash-decoding paged-attention kernel on TPU (block tables "
            "consumed in-kernel via scalar prefetch — no dense gather of "
            "the KV blocks is ever materialized; GQA grouped in-kernel; "
            "int8 dequant fused into the block loads) and the XLA "
            "gather + masked-softmax path on every other platform; 'on' "
            "selects the kernel anywhere (interpret mode off-TPU — how "
            "tier-1 exercises the real kernel body on CPU); 'off' selects "
            "the gather path anywhere (the parity oracle). A kernel the "
            "TPU compiler refuses raises at the engine's first dispatch; "
            "nothing retries it through the gather path.", str)
define_flag("FLAGS_serving_kv_quant", "",
            "Paged KV-cache quantization (ServingConfig.kv_quant): "
            "'int8' stores K/V blocks as int8 with per-token-per-head "
            "fp32 scales alongside the pool — ~2-4x more usable blocks "
            "at a fixed byte budget, multiplying concurrent sequences, "
            "prefix-cache value and preemption headroom at once; "
            "dequantization is fused into the paged kernel's K/V loads "
            "(the gather fallback dequantizes after its gather). '' = "
            "fp pool at the model/cache dtype. Composes with the "
            "weight-only quantize='int8' path.", str)
define_flag("FLAGS_serving_spec_decode", 0,
            "Speculative decoding for the paged serving engine "
            "(ServingConfig.spec_decode): tokens DRAFTED per verify "
            "dispatch via n-gram prompt lookup (no second model — drafts "
            "come from the request's own prompt + generated context). "
            "Each verify runs ONE multi-query decode dispatch over the "
            "drafts and emits every accepted token plus the corrected "
            "next token, so a repetitive/shared-suffix stream retires "
            "several tokens per dispatch; sampled and greedy streams are "
            "BIT-IDENTICAL to non-speculative decode (per-token-index "
            "PRNG keys make acceptance exact, not approximate). 0 "
            "disables (the default).", int)
define_flag("FLAGS_serving_spec_ngram", 3,
            "n-gram length the prompt-lookup drafter matches: a draft is "
            "proposed when the last n generated/prompt tokens reoccur "
            "earlier in the request's context, continuing from the most "
            "recent prior occurrence. Smaller n drafts more aggressively "
            "(more speculation, lower acceptance on incoherent text); "
            "larger n drafts only on strong repetition.", int)
define_flag("FLAGS_serving_policy", "fifo",
            "Default admission policy for ServingEngine (ServingConfig."
            "policy): fifo (submission order — the parity baseline), "
            "priority (Request.priority classes), fair (weighted fair "
            "share across tenants), edf (earliest deadline first under "
            "TTFT SLOs). Policies reorder ADMISSION only; per-request "
            "greedy outputs are identical under every policy "
            "(docs/SERVING.md Overload & multi-tenancy).", str)
define_flag("FLAGS_serving_ttft_slo_s", 0.0,
            "Default time-to-first-token SLO (seconds) the EDF policy "
            "assumes for requests submitted without timeout_s/deadline_s "
            "— ordering only, never sheds by itself. 0 = no default "
            "(SLO-less requests sort last, FIFO among themselves).", float)
define_flag("FLAGS_serving_tenant_cache_quota", 0,
            "Max prefix-cache blocks one tenant may keep registered; at "
            "the quota a tenant recycles its OWN least-recently-released "
            "entry instead of LRU-evicting other tenants' (so one tenant "
            "flooding unique prompts cannot evict everyone's system "
            "prompt). 0 = unlimited.", int)

define_flag("FLAGS_serving_tp", 1,
            "Tensor-parallel degree for the serving engine "
            "(ServingConfig.tp): the paged KV pool shards its kv-heads "
            "axis over a 'tp' mesh of this many devices and the "
            "prefill/decode/verify programs run under shard_map — per-"
            "device KV bytes per token divide by tp, so per-chip "
            "concurrent capacity multiplies by tp at unchanged block-"
            "table logic. Requires num_kv_heads % tp == 0 and tp "
            "visible devices. 1 (the default) is the single-device "
            "engine, byte-for-byte today's code path.", int)

# KV tiering & migration (ISSUE 16): host-RAM offload tier + live
# cross-replica block migration — docs/SERVING.md "KV tiering & migration"
define_flag("FLAGS_serving_offload", False,
            "Host-RAM KV offload tier (ServingConfig.offload): refcount-0 "
            "evictable blocks (including a preemption victim's registered "
            "blocks) swap to a bounded host-side pool instead of dying "
            "when device pressure evicts them — a later prefix hit or "
            "victim readmission H2D-restores the chain with zero "
            "recompute. Write-time checksums make a corrupt host block "
            "degrade to a cache MISS (recompute), never to wrong KV; the "
            "lookup() verification contract extends to the tier. Off by "
            "default: the tier costs host RAM and D2H bandwidth.", bool)
define_flag("FLAGS_serving_offload_blocks", 256,
            "Host-tier capacity bound in KV blocks "
            "(ServingConfig.offload_blocks): the offload pool holds at "
            "most this many swapped-out blocks, LRU-evicting beyond it "
            "(an evicted host block falls back to the recompute path "
            "bit-exactly). int8-quantized blocks are ~3.5x cheaper per "
            "block, so the same bound holds ~3.5x the cached tokens.", int)
define_flag("FLAGS_serving_migrate", False,
            "Live KV migration (RouterConfig.migrate): graceful drain, "
            "rolling restart, and scale-in transfer each in-flight "
            "request's KV block chain + resolved record to an adoptive "
            "replica (same shared-programs fleet, shapes always agree) "
            "instead of resubmitting for recompute — recomputed_tokens "
            "== 0 across a clean roll, token streams bit-identical. "
            "Falls back automatically to the resubmit path when the "
            "target can't take the blocks (pool-full, mid-crash, "
            "TP-shape mismatch). Off by default.", bool)

# serving front line (ISSUE 7): asyncio server + engine supervisor
define_flag("FLAGS_serving_max_restarts", 3,
            "EngineSupervisor restart budget: unexpected step-loop "
            "exceptions (or serving-section hang-watchdog trips) tear the "
            "engine down, rebuild it and re-submit every non-terminal "
            "request — past this many restarts the replica flips to "
            "not-accepting (/readyz 503) instead of crash-looping "
            "(docs/OPS.md runbook).", int)
define_flag("FLAGS_serving_drain_deadline_s", 30.0,
            "Graceful-drain deadline (s): on SIGTERM/close() the front "
            "line stops admissions (structured 503 + retry_after_s), "
            "finishes in-flight requests within this window, then cancels "
            "the remainder. The launcher's PADDLE_PREEMPT_GRACE (minus a "
            "2s margin) overrides when exported — the same preemption "
            "window the emergency-checkpoint path uses.", float)
define_flag("FLAGS_serving_client_queue", 64,
            "Per-client event-buffer bound in the asyncio serving server. "
            "A consumer that falls this many undelivered events behind is "
            "DISCONNECTED and its request cancelled through the normal "
            "lifecycle path (KV blocks freed immediately) — a stalled SSE "
            "reader cannot pin pool blocks or host memory.", int)
define_flag("FLAGS_serving_audit", False,
            "Run the serving InvariantAuditor's structural checks "
            "(block-pool partition conservation, zero leaks at idle, "
            "terminal-state consistency, per-tenant accounting closure, "
            "monotonic counters — the AUDIT_CHECKS registry) inside "
            "ServingRouter.health_snapshot(), surfacing the verdict on "
            "/metrics. Off by default: the checks walk every block map, "
            "a cost a hot serving loop should only pay when asked to "
            "(docs/OPS.md Workload replay & capacity planning).", bool)
define_flag("FLAGS_serving_retry_after_s", 1.0,
            "Conservative retry-after hint (s) returned to shed clients "
            "BEFORE the engine has observed two retirements (cold start: "
            "no retirement interval to estimate from); once measurable, "
            "the mean recent retirement interval takes over.", float)

# serving fleet router (ISSUE 9): multi-replica routing over supervised
# replicas — docs/OPS.md "Serving fleet"
define_flag("FLAGS_serving_router_replicas", 2,
            "Replicas the ServingRouter spawns at construction when "
            "ServingRouter(replicas=) is left unset. All replicas share "
            "one set of params and ONE compiled EnginePrograms, so extra "
            "replicas cost KV-pool memory and host scheduling, never a "
            "recompile.", int)
define_flag("FLAGS_serving_router_max_replicas", 8,
            "Ceiling on fleet size: autoscale scale-up (and rejoin-file "
            "polls) stop spawning replicas at this many; scale-in never "
            "drains below 1.", int)
define_flag("FLAGS_serving_router_breaker_threshold", 3,
            "Per-replica circuit breaker: consecutive failures (probe "
            "raises, submit unavailability, supervisor restarts) before "
            "the breaker OPENS and the router stops routing to the "
            "replica.", int)
define_flag("FLAGS_serving_router_breaker_cooldown_s", 5.0,
            "Seconds an OPEN breaker waits before the router re-probes "
            "the replica HALF-OPEN (one health probe: success closes the "
            "breaker and the replica rejoins, failure re-opens with a "
            "fresh cooldown).", float)
define_flag("FLAGS_serving_router_hedge_ttft_mult", 0.0,
            "Hedged retry: a request still waiting for its FIRST token "
            "after mult x FLAGS_serving_ttft_slo_s seconds is duplicated "
            "onto a second healthy replica; whichever copy emits first "
            "wins and the loser is cancelled through the lifecycle path "
            "(KV freed — greedy outputs make the copies bit-identical, so "
            "the winner's stream is THE stream). 0 disables hedging; it "
            "also stays off while FLAGS_serving_ttft_slo_s is 0.", float)

# disaggregated prefill + fleet-wide cache directory (ISSUE 17):
# docs/SERVING.md "Disaggregated prefill & fleet cache"
define_flag("FLAGS_serving_router_prefill_replicas", 0,
            "Prefill-only replicas the ServingRouter spawns in addition "
            "to its decode replicas (Splitwise/DistServe-style compute "
            "disaggregation): long prompts (see "
            "FLAGS_serving_prefill_len_threshold) run chunked prefill "
            "there, then hand the finished KV chain + resolved record to "
            "a decode replica via the live-migration adopt path with "
            "recomputed_tokens == 0. 0 disables the split — every prompt "
            "takes the unified path. The router also collapses to the "
            "unified path automatically when the pool is empty, draining "
            "or the transfer fails.", int)
define_flag("FLAGS_serving_prefill_len_threshold", 64,
            "Prompt length (tokens) at which the router classifies a "
            "request as LONG and routes its prefill to the prefill-only "
            "pool (when FLAGS_serving_router_prefill_replicas > 0). "
            "Shorter prompts always take the unified path — their "
            "prefill is too cheap to be worth a handoff.", int)
define_flag("FLAGS_serving_fleet_cache", True,
            "Fleet-wide KV cache directory: the router tracks which "
            "replica (device pool or host tier) holds each prefix-chain "
            "key, routes submits to the replica holding the LONGEST "
            "cached chain, and otherwise PULLS the cached blocks "
            "cross-replica (checksummed like offload puts — a mismatch "
            "degrades to recompute, never wrong KV). Off: each replica's "
            "prefix cache is an island and stickiness falls back to the "
            "first-block affinity map.", bool)

# durable serving: crash-safe request journal + cold-restart recovery
# (ISSUE 18): docs/FAULT_TOLERANCE.md "Cold restart (serving)"
define_flag("FLAGS_serving_journal_dir", "",
            "Directory for the crash-safe serving request journal; empty "
            "disables durability. When set, EngineSupervisor and "
            "ServingRouter journal every submit / delivered-token cursor "
            "/ terminal transition there (crc32 + length framed WAL plus "
            "periodic snapshots), and EngineSupervisor.recover() / "
            "ServingRouter.cold_start() rebuild the fleet after a "
            "process death — every non-terminal request resubmitted "
            "bit-exactly from prompt + delivered-so-far, no delivered "
            "token ever re-emitted.", str)
define_flag("FLAGS_serving_journal_sync", "step",
            "Journal fsync policy: 'step' batches one fsync per engine "
            "step (the boundary at which tokens become visible to "
            "clients, so the journal never claims delivery of a token "
            "the caller could not have seen), 'always' fsyncs every "
            "record, 'off' leaves residency to the page cache (survives "
            "process death, not host death).", str)
define_flag("FLAGS_serving_snapshot_every", 64,
            "Engine steps (journal flushes) between serving-state "
            "snapshots; 0 disables periodic snapshots (the journal "
            "still snapshots once on graceful drain). Snapshots bound "
            "cold-restart replay to the WAL suffix written since the "
            "last good generation.", int)

# multi-adapter LoRA serving (ISSUE 19): docs/SERVING.md "Multi-adapter
# LoRA & embeddings"
define_flag("FLAGS_serving_lora_rank", 8,
            "LoRA rank r of the device-resident adapter pool: every "
            "registered adapter's per-projection A/B factors are stored "
            "at this fixed rank so one stacked [L, slots, ...] pool (and "
            "ONE compiled program gathering from it) serves every "
            "adapter. Registering an adapter with a different rank is a "
            "structured error naming this flag.", int)
define_flag("FLAGS_serving_lora_slots", 0,
            "Device-resident adapter slots of the paged adapter pool "
            "(slot 0 is the reserved zeroed BASE adapter and is not "
            "counted). 0 disables multi-adapter serving entirely — the "
            "engine compiles exactly the base programs and base traffic "
            "is bit-identical to a LoRA-less build. With N slots, up to "
            "N distinct adapters decode concurrently; colder adapters "
            "LRU-evict to the host registry and reload on demand "
            "(counted as adapter_loads).", int)
define_flag("FLAGS_serving_lora_pool", 16,
            "Host-side adapter registry capacity — the most adapters "
            "register() accepts (resident + evicted; the zeroed base "
            "adapter is free). Registration past the bound is a "
            "structured error naming this flag. Must be >= "
            "FLAGS_serving_lora_slots.", int)

