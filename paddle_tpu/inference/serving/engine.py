"""Continuous-batching serving engine over the paged KV cache.

The serving tier the ROADMAP's "heavy traffic" north star asks for:
iteration-level scheduling (Orca) + a paged KV cache (PagedAttention) on
top of the compiled decode path PR 2 built (donated buffers, one program
per shape).

Design (docs/SERVING.md):

* **One compiled decode program.** The decode step runs over a FIXED
  ``max_slots``-wide slot table — shapes never change, so it traces once
  and the per-iteration host cost is one dispatch. The iteration bound is
  a DEVICE SCALAR argument (no retrace): with work queued the dispatch
  returns exactly when the first live slot exhausts its budget, so
  retirement/admission happen with zero idle iterations; with the queue
  empty one dispatch drains the whole tail. ``decode_chunk`` caps the
  bound only when a live slot can retire EARLY (EOS enabled) or the
  caller streams (token granularity).
* **On-demand paged KV + preemption.** A sequence holds only the blocks
  covering KV it has actually written: admission allocates the prompt's
  blocks (prefix-cache hits are MAPPED, not recomputed), decode extends
  block by block ahead of each dispatch. When the pool runs dry the
  newest-admitted running sequence is PREEMPTED — blocks freed, tokens
  kept, re-queued at the front for recompute-on-readmission (greedy
  recompute is bit-identical) — so worst-case ``max_new`` budgets are
  never pre-charged and effective concurrency tracks real usage.
  ``preempt=False`` restores the legacy reservation-at-admission mode.
* **Automatic prefix caching.** Full KV blocks are content-hashed (chained
  block-aligned token-id keys) into the ref-counted ``BlockManager`` table
  as prefill/decode completes them; admissions sharing a system-prompt /
  few-shot prefix map the cached blocks and prefill only their suffix.
  Refcount-0 blocks stay cached on an LRU list until allocation pressure
  evicts them. ``prefix_cache=False`` disables.
* **Chunked prefill in the mixed step.** Prompts longer than
  ``prefill_chunk``, prefix-cache hits and readmissions prefill in
  fixed-size chunks that ride the decode dispatch as ``q_len > 1`` rows
  of ONE mixed step (the family's ``paged_mixed_step`` — per-row offset
  and length are device operands), so a long admission never freezes
  in-flight streams. Short cold prompts take the BATCHED bucketed
  prefill: one dispatch per power-of-2 length bucket with the batch dim
  padded to the power-of-2 bucket of the admission-wave size.
* **Overload-safe lifecycle + policy scheduling.** Every request ends in
  exactly one terminal state (``finished`` / ``cancelled`` /
  ``timed_out`` / ``shed``): ``cancel(rid)`` and per-request
  ``timeout_s``/``deadline_s`` free KV blocks mid-flight through the
  preemption path (free, do-not-requeue), checked every ``step()``;
  admission order is a pluggable ``AdmissionPolicy`` (FIFO default,
  priority / weighted fair share per ``tenant`` / earliest-deadline-
  first), the bounded queue SHEDS with a retry-after hint instead of
  blocking, and ``health_snapshot()`` + the global hang watchdog
  (``serving.step``/``serving.prefill``/``serving.decode`` sections)
  expose the whole thing to ops endpoints.
* **On-device sampling.** Per-request temperature / top-k / top-p ride
  the compiled decode step as DEVICE OPERANDS in the slot table (one
  compile serves every request mix — no per-request executables), with
  per-request PRNG base keys derived from ``seed``: the token at sample
  index ``t`` is drawn with ``fold_in(seed_key(seed), t)``, so sampled
  streams are reproducible per ``(request, seed)`` across
  preemption-recompute, supervisor crash-resubmit and cross-replica
  failover. ``temperature=0`` (the default) selects the argmax through a
  ``jnp.where`` and stays BIT-IDENTICAL to the v1 greedy engine — every
  greedy parity oracle extends unchanged. int8 weight-only decode rides
  transparently via ``quantize="int8"``.
* **Speculative decoding.** ``spec_decode=k`` drafts up to ``k`` tokens
  per step by n-gram prompt lookup (no second model: the draft is the
  continuation of the last ``spec_ngram`` tokens' most recent earlier
  occurrence in the request's own context) and VERIFIES them in one
  multi-query decode dispatch (``models.generation.paged_spec_step``;
  the PR 10 paged-attention kernel's second entry point). Accepted
  tokens commit their KV blocks; the rejected tail's surplus blocks
  free through the same ref-counted paths preemption exercises. Because
  sampling keys are a pure function of the token index, speculative
  output is BIT-IDENTICAL to non-speculative decode at every
  temperature — acceptance only changes speed, never tokens. Steps with
  no draftable slot fall through to the plain decode dispatch, so
  incoherent (low-acceptance) traffic pays no verify overhead.

API::

    engine = ServingEngine(params, model_cfg, ServingConfig(max_slots=8))
    rid = engine.submit(prompt_ids, max_new_tokens=64)
    while engine.pending:
        for rid, toks in engine.step().items(): ...
    # or: for rid, tok in engine.stream(): ...
    # or: outs = engine.run(prompts, max_new_tokens=64)
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ...flags import flag
from ...health import watchdog as _watchdog
from ...profiler import SpanStats, histogram_percentile
from .offload import block_crc as _block_crc
from .paged_cache import PagedKVCache
from .policies import resolve_policy
from .scheduler import (CANCELLED, DEFAULT_TENANT, SHED,  # noqa: F401
                        TIMED_OUT, Request, Scheduler, ServingQueueFull)

__all__ = ["AdoptError", "ServingConfig", "ServingEngine", "EnginePrograms",
           "HEALTH_SNAPSHOT_FIELDS", "SUPERVISOR_SNAPSHOT_KEYS"]

_UNSET = "unset"

# field -> meaning for health_snapshot(); docs/OPS.md's generated table
# (ops.gen_docs) renders this, and the snapshot test pins the live
# payload's keys to it, so the doc cannot drift from the code. The engine
# serves every field except SUPERVISOR_SNAPSHOT_KEYS, which the
# EngineSupervisor layers on top (supervisor.health_snapshot()).
HEALTH_SNAPSHOT_FIELDS = {
    "ok": "False only when the installed hang watchdog has fired "
          "(shedding is a healthy degraded mode, not unhealth)",
    "accepting": "whether a submit() right now would QUEUE rather than "
                 "shed (queue below its bound; under a supervisor also "
                 "requires not-draining and restart budget remaining)",
    "policy": "active admission policy name (fifo/priority/fair/edf)",
    "queued": "requests waiting for a slot",
    "queue_limit": "admission-queue bound; submits past it shed with "
                   "ServingQueueFull",
    "live_slots": "occupied decode slots",
    "max_slots": "slot-table width (the compiled decode batch dim)",
    "free_blocks": "KV blocks allocatable right now (free list + "
                   "evictable refcount-0 cached blocks)",
    "usable_blocks": "pool size excluding the reserved null block — the "
                     "EFFECTIVE capacity: at a fixed byte budget an int8 "
                     "pool holds ~2-4x the blocks of an fp one",
    "kv_pool_bytes": "device bytes the KV pool holds GLOBALLY (K + V + "
                     "the scale planes on quantized layouts, summed over "
                     "every tp shard) — the denominator of the int8 "
                     "capacity win",
    "tp_degree": "tensor-parallel degree of this replica "
                 "(ServingConfig.tp / FLAGS_serving_tp): the paged pool "
                 "is sharded over this many devices on its kv-heads axis; "
                 "1 = the single-device engine",
    "kv_pool_shard_bytes": "KV-pool bytes ONE device holds "
                           "(kv_pool_bytes / tp_degree — the kv-heads "
                           "split is exact): what a per-chip HBM budget "
                           "must cover, so the autoscaler and capacity "
                           "planning see sharded replicas correctly",
    "kv_quant": "KV-pool quantization mode (null = fp at the model/cache "
                "dtype; 'int8' = int8 blocks + per-token-per-head fp32 "
                "scales, dequant fused into the kernel's loads)",
    "paged_kernel": "decode attention path: true = the Pallas "
                    "flash-decoding paged-attention kernel (block tables "
                    "consumed in-kernel), false = the XLA gather + masked-"
                    "softmax fallback (FLAGS_serving_paged_kernel)",
    "spec_decode": "speculative-decoding draft width: tokens drafted per "
                   "verify dispatch via n-gram prompt lookup "
                   "(FLAGS_serving_spec_decode; 0 = off). Acceptance "
                   "counters ride stats() as spec_drafted / spec_accepted "
                   "— output streams are bit-identical to non-speculative "
                   "decode, so the knob only moves tokens/s",
    "retry_after_s": "suggested client backoff when shedding: the mean "
                     "recent retirement interval (the conservative "
                     "FLAGS_serving_retry_after_s default before two "
                     "retirements exist to estimate from)",
    "counters": "lifetime totals: admitted / retired / cancelled / "
                "timed_out / shed / preemptions / oom_truncated / "
                "prefix_hit_tokens / evictions",
    "dispatch_latency": "per-kind device-dispatch wall time (ISSUE 20): "
                        "for each of prefill / decode / mixed / spec, the "
                        "lifetime dispatch count plus p50_ms / p99_ms over "
                        "a recent window (null until that kind has "
                        "dispatched) — the prefill-stall this splits out "
                        "is exactly what mixed batching removes, so "
                        "operators can watch it",
    "phase_ms_per_step": "where the engine thread's time goes: mean host "
                         "milliseconds per engine step in each serve:* "
                         "phase since start (idle / cmds / route / deliver "
                         "from the server's pump, supervise from the "
                         "supervisor, plan / operands / dispatch / fetch / "
                         "commit / journal from the engine; fetch is the "
                         "device's time as the host sees it). The same "
                         "spans are in a profiler trace when one is taken; "
                         "the cumulative totals, counters and histograms "
                         "behind this row ride stats()['spans']",
    "request_wait": "per-request waits from cumulative histograms: "
                    "queue_wait_p50_s / queue_wait_p99_s (front-line "
                    "enqueue -> first admission, the server's command "
                    "queue included) and prefill_p50_s / prefill_p99_s "
                    "(admission -> first token); null before the first "
                    "sample",
    "real_lane_pct": "how much of the padded dispatch shapes carries a "
                     "real token, lifetime, in percent: mixed (sum of "
                     "q_len over active rows against the lanes the mixed "
                     "step computed: its waves x their lanes for a family "
                     "that packs the step and counts lanes_computed, "
                     "max_slots x Q otherwise) and prefill (sum of prompt "
                     "lengths against batch bucket x length bucket of the "
                     "batched prefill); null until that kind has "
                     "dispatched. A low share is compute spent on pad "
                     "lanes: the case for a packed step or finer buckets",
    "family": "what the served model's family reports from the counters "
              "its own programs keep (the ``health(counters, cfg)`` of the "
              "module the config object names, models.paged_family); null "
              "for a family that reports nothing. The dense (Llama-shaped) "
              "family: lanes_computed, the lanes its programs ran through "
              "the per-token parts of the model, real or not (a prefill's "
              "bucket, a decode iteration's slots, a verify step's M x Q, "
              "a mixed step's waves x their lanes), and mixed_waves, the "
              "waves its PACKED mixed steps took (a step runs its real "
              "lanes in waves of min(M x Q, 16 x max_slots) lanes: one "
              "wave in steady state, a step's decoding slots and a chunk "
              "or two). mixed_waves well above mixed dispatches in steady "
              "state means the chunks a step carries outrun a wave: each "
              "further wave streams the weights again, so lower "
              "prefill_chunk or the number of prompts admitted at once. "
              "The latent-attention "
              "family with routed experts: local_pair_pct, the (token, "
              "pick) pairs that fell on experts held HERE in percent of all "
              "pairs of real tokens (the others are left to the chips that "
              "hold them and are not computed), and load_max_over_mean, the "
              "largest row count of one held expert over the mean, averaged "
              "over layer calls (1.0 = even load); each null before the "
              "first dispatch",
    "short_row_pct": "active rows of the mixed step that carried ONE query "
                     "position (q_len 1: decoding slots), lifetime, in "
                     "percent of its active rows; null until a mixed step "
                     "has dispatched. These rows take the paged-attention "
                     "kernel's short query tile (the decode step's work); "
                     "the rest run a sub-tile per 128 query rows of their "
                     "chunk (kernels/paged_attention.py)",
    "offload": "host-RAM KV offload tier (FLAGS_serving_offload; ISSUE "
               "16): enabled + the tier's capacity / blocks (host-"
               "resident now) / swap_outs / swap_ins / tier_hits / "
               "tier_misses / corrupt_drops (checksum or token-mismatch "
               "entries dropped — degraded to a MISS, never attended) / "
               "tier_evictions; all zeros with the tier off",
    "lora": "multi-adapter LoRA serving (ISSUE 19): enabled + rank / "
            "slots (device adapter-pool rows past the reserved zeroed "
            "base slot 0) / resident (adapter names loaded on device) / "
            "adapters_registered / adapters_resident / adapter_loads "
            "(H2D uploads — cold acquires) / adapter_evictions (LRU "
            "slot reclaims) / adapter_pins (running-request pins; a "
            "pinned adapter is never evicted mid-stream); zeros with "
            "multi-adapter serving off",
    "watchdog": "global hang-watchdog state: installed / fired / "
                "timeout_s",
    "tenants": "per-tenant breakdown: queued / live / submitted / "
               "admitted / retired / cancelled / timed_out / shed / "
               "service_tokens / cached_blocks / ttft_p50_s / ttft_p99_s "
               "/ tpot_p50_s / tpot_p99_s (TPOT = mean inter-token decode "
               "latency per request; percentiles over recent requests)",
    "supervisor": "EngineSupervisor layer (supervisor snapshots only): "
                  "restarts / restart_budget / broken / draining / "
                  "accepting / resubmitted / recovered_tokens / adopted "
                  "(requests failed over FROM another replica) / "
                  "migrated_in + migrated_out (live KV migrations adopted "
                  "here / released here; ISSUE 16) / completed / crashes "
                  "(most recent restart reasons)",
    "autoscale": "autoscale_signal() record (supervisor snapshots only): "
                 "action (scale_up/scale_in/hold) + reason + "
                 "queue_pressure / utilization / shed_delta — the "
                 "telemetry an autoscaler consumes, writable as the "
                 "launcher's --elastic_rejoin_file format",
}

# snapshot fields only the EngineSupervisor adds; the engine-level payload
# is HEALTH_SNAPSHOT_FIELDS minus these (the shape test pins both layers)
SUPERVISOR_SNAPSHOT_KEYS = ("supervisor", "autoscale")


def _named(name: str, fn):
    """``fn`` under a ``__name__``: jax names a jitted program (the HLO
    module, the trace's module line) after its function, and a
    ``functools.partial`` or a ``shard_map`` wrapper has none
    (``jit__unknown``)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


class AdoptError(RuntimeError):
    """A migration target refused a serialized request (pool full, no free
    slot, KV-layout/TP-shape mismatch, over-long chain). The caller falls
    back to the resubmit path — recompute instead of transfer, outputs
    still bit-identical."""


@dataclasses.dataclass
class EnginePrograms:
    """The compiled prefill/chunk/decode executables plus the stats dict
    and bucket set their trace-counter closures mutate. Shareable across
    engine rebuilds with an IDENTICAL shape signature — the supervisor's
    restart path hands the dead engine's programs to its replacement, so
    crash recovery never recompiles (and the shared trace counters PROVE
    it: decode_traces must not grow across a restart)."""

    prefill: Any
    decode: Any
    spec: Any           # speculative verify (multi-query decode) program
    sample: Any         # first-token sampler (prefill-logits -> token)
    stats: Dict[str, int]
    prefill_buckets: set
    key: tuple          # shape signature (incl. the sampling/spec-decode
    #                     surface: spec_decode widths change the verify
    #                     program's shapes, and the LoRA pool geometry /
    #                     embed-model config change operand shapes); reuse
    #                     under a different one raises
    embed: Any = None   # prefill-only embeddings encoder (ISSUE 19);
    #                     None when no embed model is attached
    mixed: Any = None   # mixed prefill+decode step (ISSUE 20): per-row
    #                     start/q_len device operands, so one executable
    #                     per Q bucket serves every role mix


@dataclasses.dataclass
class ServingConfig:
    """Engine shape/capacity knobs. ``None`` fields resolve from the
    ``FLAGS_serving_*`` registry at construction (flags.py), so a fleet can
    retune the engine from the environment without code changes.

    The three feature knobs use the ``"unset"`` sentinel instead (the same
    convention as ``GenerationConfig.resolve``): left unset they resolve
    from their flag; an EXPLICIT ``None`` (or ``False``/``0``) disables
    the feature even when the flag enables it — ``prefix_cache=None`` and
    ``prefill_chunk=None`` are real overrides, not "not given".
    """

    block_size: Optional[int] = None
    max_slots: Optional[int] = None
    max_model_len: Optional[int] = None
    queue_depth: Optional[int] = None
    decode_chunk: Optional[int] = None
    tp: Optional[int] = None         # tensor-parallel degree (ISSUE 12):
    #                                  the paged pool shards its kv-heads
    #                                  axis over a "tp" mesh of this many
    #                                  devices and the compiled programs
    #                                  run under shard_map; None ->
    #                                  FLAGS_serving_tp (default 1 = the
    #                                  single-device engine, byte-for-byte
    #                                  today's code path). Requires
    #                                  num_kv_heads % tp == 0 (validated
    #                                  with a structured error).
    num_blocks: int = 0              # 0 = auto (max_slots full sequences)
    quantize: Optional[str] = None   # "int8" -> weight-only decode path
    cache_dtype: Any = None          # None -> model activation dtype
    kv_quant: Any = _UNSET           # "int8" -> quantized KV pool (int8
    #                                  blocks + per-token-per-head scales);
    #                                  unset -> FLAGS_serving_kv_quant;
    #                                  None/"" = fp pool. Composes with
    #                                  quantize="int8" (weights).
    paged_kernel: Any = _UNSET       # decode attention path: True/"on" =
    #                                  Pallas flash-decoding kernel
    #                                  (interpret off-TPU), False/"off" =
    #                                  XLA gather fallback, "auto" = kernel
    #                                  on TPU only; unset ->
    #                                  FLAGS_serving_paged_kernel
    prefix_cache: Any = _UNSET       # bool; None/False = off
    prefill_chunk: Any = _UNSET      # tokens/chunk; None/0 = whole prompt
    preempt: Any = _UNSET            # bool; None/False = legacy reservation
    # speculative decoding (ISSUE 11)
    spec_decode: Any = _UNSET        # draft tokens per verify dispatch
    #                                  (n-gram prompt lookup); None/0 =
    #                                  off; unset -> FLAGS_serving_
    #                                  spec_decode
    spec_ngram: Any = _UNSET         # n-gram length the drafter matches;
    #                                  unset/None -> FLAGS_serving_
    #                                  spec_ngram
    # overload / multi-tenancy (ISSUE 6)
    policy: Any = None               # AdmissionPolicy | "fifo"/"priority"/
    #                                  "fair"/"edf"; None -> FLAGS_serving_
    #                                  policy (default fifo)
    tenant_cache_quota: Any = _UNSET  # max prefix-cache blocks one tenant
    #                                   may keep registered; None/0 = off
    # host-RAM KV offload tier (ISSUE 16)
    offload: Any = _UNSET            # bool; evicted registered blocks swap
    #                                  to a bounded host pool instead of
    #                                  dying; unset -> FLAGS_serving_offload
    offload_blocks: Any = _UNSET     # host-tier capacity bound in blocks;
    #                                  unset -> FLAGS_serving_offload_blocks
    # multi-adapter LoRA serving (ISSUE 19)
    lora_rank: Optional[int] = None  # adapter rank r (fixed pool-wide);
    #                                  None -> FLAGS_serving_lora_rank
    lora_slots: Optional[int] = None  # device adapter-pool slots (on top
    #                                   of the reserved zeroed base slot
    #                                   0); 0 disables multi-adapter
    #                                   serving entirely — the compiled
    #                                   programs are then byte-identical
    #                                   to the LoRA-less engine; None ->
    #                                   FLAGS_serving_lora_slots
    lora_pool: Optional[int] = None  # host-registry capacity (adapters
    #                                  registered in total, >= lora_slots);
    #                                  None -> FLAGS_serving_lora_pool

    def __post_init__(self):
        for f, name in (("block_size", "FLAGS_serving_block_size"),
                        ("max_slots", "FLAGS_serving_max_slots"),
                        ("max_model_len", "FLAGS_serving_max_model_len"),
                        ("queue_depth", "FLAGS_serving_queue_depth"),
                        ("decode_chunk", "FLAGS_serving_decode_chunk"),
                        ("tp", "FLAGS_serving_tp"),
                        ("lora_rank", "FLAGS_serving_lora_rank"),
                        ("lora_slots", "FLAGS_serving_lora_slots"),
                        ("lora_pool", "FLAGS_serving_lora_pool")):
            if getattr(self, f) is None:
                setattr(self, f, int(flag(name)))
        self.lora_rank = int(self.lora_rank)
        self.lora_slots = int(self.lora_slots)
        self.lora_pool = int(self.lora_pool)
        if self.lora_slots < 0:
            raise ValueError(f"lora_slots must be >= 0 (0 = multi-adapter "
                             f"serving off), got {self.lora_slots}")
        if self.lora_slots and self.lora_pool < self.lora_slots:
            raise ValueError(
                f"lora_pool ({self.lora_pool}) must be >= lora_slots "
                f"({self.lora_slots}): the host registry backs every "
                f"device-resident adapter (FLAGS_serving_lora_pool / "
                f"FLAGS_serving_lora_slots)")
        self.tp = int(self.tp)
        if self.tp < 1:
            raise ValueError(f"tensor-parallel degree must be >= 1 (1 = "
                             f"the single-device engine), got tp={self.tp}")
        if self.prefix_cache == _UNSET:
            self.prefix_cache = bool(flag("FLAGS_serving_prefix_cache"))
        else:
            self.prefix_cache = bool(self.prefix_cache)
        if self.preempt == _UNSET:
            self.preempt = bool(flag("FLAGS_serving_preempt"))
        else:
            self.preempt = bool(self.preempt)
        if self.prefill_chunk == _UNSET:
            self.prefill_chunk = int(flag("FLAGS_serving_prefill_chunk"))
        self.prefill_chunk = (int(self.prefill_chunk)
                              if self.prefill_chunk else None)
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 or None/0 "
                             f"(got {self.prefill_chunk})")
        if self.spec_decode == _UNSET:
            self.spec_decode = int(flag("FLAGS_serving_spec_decode"))
        self.spec_decode = int(self.spec_decode) if self.spec_decode else 0
        if self.spec_decode < 0:
            raise ValueError(f"spec_decode must be >= 0 (draft tokens per "
                             f"verify; 0 = off), got {self.spec_decode}")
        if self.spec_ngram in (_UNSET, None):
            self.spec_ngram = int(flag("FLAGS_serving_spec_ngram"))
        self.spec_ngram = int(self.spec_ngram)
        if self.spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, "
                             f"got {self.spec_ngram}")
        if self.tenant_cache_quota == _UNSET:
            self.tenant_cache_quota = int(
                flag("FLAGS_serving_tenant_cache_quota"))
        self.tenant_cache_quota = (int(self.tenant_cache_quota)
                                   if self.tenant_cache_quota else None)
        if self.offload == _UNSET:
            self.offload = bool(flag("FLAGS_serving_offload"))
        else:
            self.offload = bool(self.offload)
        if self.offload_blocks == _UNSET:
            self.offload_blocks = int(flag("FLAGS_serving_offload_blocks"))
        self.offload_blocks = (int(self.offload_blocks)
                               if self.offload_blocks else 0)
        if self.policy is None:
            self.policy = str(flag("FLAGS_serving_policy"))
        from ...models.llama import (KV_QUANT_MODES, QUANTIZE_MODES,
                                     validate_quant_mode)
        validate_quant_mode(self.quantize, QUANTIZE_MODES)
        if self.kv_quant == _UNSET:
            self.kv_quant = str(flag("FLAGS_serving_kv_quant"))
        self.kv_quant = self.kv_quant or None      # ""/False -> fp pool
        validate_quant_mode(self.kv_quant, KV_QUANT_MODES, "kv_quant")
        if self.paged_kernel == _UNSET:
            self.paged_kernel = str(flag("FLAGS_serving_paged_kernel"))
        from ...kernels.dispatch import use_pallas
        # resolve once at construction (structured error on bad knobs);
        # the resolved bool keys the compiled-program signature
        self.paged_kernel = use_pallas(self.paged_kernel)


class ServingEngine:
    """Continuous-batching greedy decode service over a causal-LM pytree."""

    # chaos hook (testing/chaos.py ``stale_directory``): when set, the
    # NEXT export_chain() flips one byte in its payload AFTER stamping
    # the checksums, so the receiving graft_chain() must detect the
    # mismatch and degrade to recompute — the fleet-cache pull's
    # corruption drill. Class-level default; injectors set it per
    # instance and the export consumes it.
    _corrupt_next_export = False

    def __init__(self, params, model_config, serving_config:
                 Optional[ServingConfig] = None, gen_config=None,
                 programs: Optional[EnginePrograms] = None,
                 journal=None, embed_model=None,
                 spans: Optional[SpanStats] = None):
        import jax

        from ...models.generation import GenerationConfig, validate_sampling
        self.config = serving_config or ServingConfig()
        # durable serving (ISSUE 18): a RequestJournal (possibly shared
        # fleet-wide) that this engine feeds under its own lock — submit
        # records, per-step delivered-token cursors, terminal
        # transitions — with ONE flush (fsync under the 'step' policy)
        # per step. None = durability off, zero overhead.
        self.journal = journal
        self._jlive: Dict[int, int] = {}   # rid -> owned journal jid
        self._gen = gen_config or GenerationConfig()
        # the engine-default sampling knobs must themselves be servable
        # (per-request overrides are validated again at submit)
        validate_sampling(self._gen)
        # the family of model (ISSUE 27): the paged entry points, the pool
        # and the per-dispatch counters are the ones the config object
        # names (models.paged_family); what this family does not serve
        # raises here, at construction
        from ...models import paged_family
        self._family = paged_family(model_config)
        self._family.validate_serving(model_config, self.config)
        self._counter_names = tuple(self._family.PAGED_COUNTERS)
        # the query lanes a mixed step is kept to, where the family names
        # a number (the lanes of one wave of its packed step)
        budget = getattr(self._family, "mixed_lane_budget", None)
        self._lane_budget = None if budget is None else \
            budget(model_config, self.config.max_slots)
        # the served model's widths and counts under its family's names
        # (what turns the family's counters into bytes and shares); None
        # for a family that describes nothing
        self._model = self._family.describe(model_config)
        from ...models.llama import ensure_quantized
        self._params = ensure_quantized(params, self.config.quantize)
        self._cfg = model_config
        # tensor parallelism (ISSUE 12): tp > 1 builds the "tp" mesh over
        # the replica's device slice, lays the QKV projections out
        # column-sharded (everything else replicated — the ONE
        # shard_serving_params layout) and emits the paged pool sharded on
        # its kv-heads axis. The scheduler / BlockManager / prefix cache
        # below stay device-count-agnostic: block ids are global, tables
        # and slot operands replicate, only pool bytes split — per-chip KV
        # capacity multiplies by tp at unchanged block-table logic.
        if self.config.tp > 1:
            from ...distributed.topology import tp_mesh
            from ...models.generation import validate_tp
            from ...models.llama import shard_serving_params
            validate_tp(model_config, self.config.tp)
            self._mesh = tp_mesh(self.config.tp)
            self._params = shard_serving_params(self._params, self._mesh)
        else:
            self._mesh = None
        self.cache = PagedKVCache(model_config, self.config.max_slots,
                                  self.config.max_model_len,
                                  self.config.block_size,
                                  self.config.num_blocks,
                                  dtype=self.config.cache_dtype,
                                  prefix_cache=self.config.prefix_cache,
                                  tenant_quota=self.config.tenant_cache_quota,
                                  kv_quant=self.config.kv_quant,
                                  mesh=self._mesh,
                                  offload=self.config.offload,
                                  offload_blocks=self.config.offload_blocks)
        self._policy = resolve_policy(
            self.config.policy,
            ttft_slo_s=float(flag("FLAGS_serving_ttft_slo_s")))
        self._sched = Scheduler(self.cache, self.config.max_slots,
                                self.config.queue_depth,
                                preempt=self.config.preempt,
                                policy=self._policy)
        M = self.config.max_slots
        self._tokens = np.zeros((M,), np.int32)
        self._seq_lens = np.zeros((M,), np.int32)
        self._steps_left = np.zeros((M,), np.int32)
        self._done = np.ones((M,), bool)          # empty slots are inactive
        self._eos = np.full((M,), -1, np.int32)
        # per-slot sampling operands (ISSUE 11): device operands of the
        # ONE compiled decode program, so a greedy request and a
        # temperature/top-k/top-p request share an executable. keys hold
        # each request's PRNG base key; sample_idx the next token index
        # (the fold_in operand — reproducibility per (request, seed))
        self._temp = np.zeros((M,), np.float32)
        self._topk = np.zeros((M,), np.int32)     # 0 = disabled
        self._topp = np.ones((M,), np.float32)    # 1.0 = disabled
        self._keys = np.zeros((M, 2), np.uint32)
        self._sample_idx = np.zeros((M,), np.int32)
        # multi-adapter LoRA (ISSUE 19): the device adapter pool plus the
        # per-slot adapter-row operand of every dispatch (0 = the zeroed
        # base adapter) and the rid -> adapter pin map the admission gate
        # maintains (pins persist across preemption; released only at a
        # terminal state, so an in-flight stream's weights never swap out)
        if self.config.lora_slots:
            from ...models.lora import AdapterPool
            self._lora = AdapterPool(model_config, self.config.lora_rank,
                                     self.config.lora_slots,
                                     self.config.lora_pool,
                                     mesh=self._mesh)
        else:
            self._lora = None
        self._adapters = np.zeros((M,), np.int32)
        self._lora_pinned: Dict[int, str] = {}
        # embeddings endpoint (ISSUE 19): an optional (BertConfig, params)
        # encoder serving prefill-only requests (kind "embed") — proof the
        # engine is model-agnostic beyond llama. Replicated even under TP
        # (a BERT-base forward is tiny next to the LM's KV traffic).
        if embed_model is not None:
            self._embed_cfg, self._embed_params = embed_model
        else:
            self._embed_cfg = self._embed_params = None
        # speculative decoding (ISSUE 11)
        self._spec_k = int(self.config.spec_decode)
        self._spec_n = int(self.config.spec_ngram)
        # every mutation (submit/cancel/step) and every snapshot read runs
        # under this lock, so stats()/health_snapshot() are safe from ANY
        # thread — the metrics endpoint polls while the engine thread
        # serves, and a mid-step torn read (counters from one dispatch,
        # slot table from the next) must be impossible. Reentrant: the
        # stream() GeneratorExit path cancels while a step frame may still
        # hold the lock on the same thread.
        self._lock = threading.RLock()
        # widest token buffer one dispatch can emit per slot (a budget
        # never exceeds max_model_len KV entries, so neither can steps)
        self._out_width = int(self.config.max_model_len)
        self._jax = jax
        # tp (the mesh shape) is part of the signature: engines at
        # different mesh shapes never share programs; same shape shares —
        # a supervisor rebuild or router spawn of a TP replica reuses the
        # dead engine's executables without retracing (flat decode_traces)
        key = (model_config, self.config.block_size, self.config.max_slots,
               self.config.max_model_len, self.config.quantize,
               str(self.config.cache_dtype), self.config.kv_quant,
               self.config.paged_kernel, self.config.spec_decode,
               self.config.tp,
               # LoRA pool geometry changes the gathered-matmul operand
               # shapes (rank normalized to 0 when disabled so base
               # engines share programs regardless of the rank flag);
               # the embed config keys the encoder program's shapes
               self.config.lora_rank if self.config.lora_slots else 0,
               self.config.lora_slots, self._embed_cfg)
        if programs is not None:
            if programs.key != key:
                raise ValueError(
                    "EnginePrograms were compiled for a different engine "
                    "shape; rebuild with programs=None")
            # SHARED stats/buckets: trace counters keep accumulating in
            # one place across rebuilds, proving recovery never retraces
            self._stats = programs.stats
            self._prefill_buckets = programs.prefill_buckets
            self._jprefill, self._jdecode = (programs.prefill,
                                             programs.decode)
            self._jspec, self._jsample = programs.spec, programs.sample
            self._jembed = programs.embed
            self._jmixed = programs.mixed
            self.programs = programs
        else:
            self._stats = {"decode_traces": 0, "prefill_traces": 0,
                           "chunks": 0,
                           "steps": 0, "spec_traces": 0,
                           "sample_traces": 0, "spec_steps": 0,
                           "embed_traces": 0, "embeds": 0,
                           "mixed_traces": 0, "prefill_dispatches": 0,
                           "decode_dispatches": 0, "mixed_dispatches": 0,
                           "spec_dispatches": 0}
            self._prefill_buckets = set()
            (self._jprefill, self._jdecode, self._jspec, self._jsample,
             self._jmixed) = self._build(jax)
            self._jembed = (self._build_embed(jax)
                            if self._embed_params is not None else None)
            self.programs = EnginePrograms(
                self._jprefill, self._jdecode, self._jspec, self._jsample,
                self._stats, self._prefill_buckets, key,
                embed=self._jembed, mixed=self._jmixed)
        # per-dispatch wall-time observability (ISSUE 20): bounded recent
        # windows per dispatch KIND, feeding the p50/p99 rows stats() and
        # health_snapshot() expose. Per-engine (not shared with the
        # programs): latency is a property of THIS replica's host+device,
        # not of the executables
        self._dispatch_ms = {k: collections.deque(maxlen=512)
                             for k in ("prefill", "decode", "mixed",
                                       "spec")}
        # where the pump thread's time goes, always on: every phase of a
        # step is one flat ``serve:*`` span (profiler.annotate, so it is
        # in the trace when one is taken) summed here with the lane and
        # iteration counters and the per-request wait histograms.
        # Cumulative, never reset: stats()["spans"] read twice subtracts
        # to a window. The supervisor and the server's pump write their
        # phases into THIS aggregator, and a supervisor rebuild hands it
        # to the next engine, so the totals survive a restart.
        self.spans = spans if spans is not None else SpanStats()
        self.step_no = 0          # the ``step=`` argument of every span

    # ---- compiled programs ------------------------------------------------

    def _build(self, jax):
        import jax.numpy as jnp
        from jax import lax

        from ...jit.train_step import donation_supported
        from ...models import generation as G
        F = self._family          # the family's paged entry points
        counted = bool(self._counter_names)
        use_kernel = self.config.paged_kernel
        cfg, stats, Cmax = self._cfg, self._stats, self._out_width
        if self._mesh is not None:
            # the LOCAL config the shard_map'd programs close over: head
            # counts stay global (the paged entry points derive the local
            # slice from the pool shard's shape); tp_axis names the mesh
            # axis the attention-output merge all_gathers over
            cfg = dataclasses.replace(cfg, tp_axis="tp")

        # every program takes the LoRA operand LAST ({"ids": per-row
        # adapter slots, "layers": the stacked pool} — a device operand
        # like the sampling knobs, so adapter churn never retraces); with
        # multi-adapter serving off it is bound to None below and the
        # traced computation is byte-identical to the LoRA-less engine
        def prefill_fn(params, ids, prompt_lens, block_tables, pool, active,
                       lora):
            stats["prefill_traces"] += 1           # trace-time only
            return F.paged_prefill(params, cfg, ids, prompt_lens,
                                   block_tables, pool, active, lora=lora,
                                   use_kernel=use_kernel)

        def _next_tokens(logits, keys, sample_idx, temp, topk, topp):
            """One compiled sampling step over per-slot DEVICE operands:
            per-row keys fold the slot's base key with its sample index,
            then greedy rows take the argmax bitwise (sample_tokens'
            where-select) — gated behind a runtime cond so an all-greedy
            dispatch never pays the sampling sort."""
            kt = jax.vmap(jax.random.fold_in)(keys, sample_idx)
            return lax.cond(
                (temp > 0.0).any(),
                lambda lg: G.sample_tokens(lg, kt, temp, topk, topp),
                lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32),
                logits)

        def decode_fn(params, pool, tokens, seq_lens, steps_left, done,
                      block_tables, eos_ids, limit, keys, sample_idx,
                      temp, topk, topp, lora):
            stats["decode_traces"] += 1            # trace-time only
            M = tokens.shape[0]

            # while (not scan): the chunk EXITS the moment every live row
            # is done, so a retirement wave mid-chunk costs nothing — the
            # same alive-mask early exit the batch generate() loop uses.
            # ``limit`` is a device scalar, so the host can size every
            # dispatch to the schedule (return at the next budget
            # retirement; drain the tail in one go) without retracing
            def body(carry):
                i, tokens, seq_lens, steps_left, done, sample_idx, pool, \
                    out, *counts = carry
                active = (~done) & (steps_left > 0)
                logits, pool, aux = F.paged_decode_step(
                    params, cfg, tokens, seq_lens, block_tables, pool,
                    active, use_kernel=use_kernel, lora=lora)
                counts = [c + aux for c in counts]
                nxt = _next_tokens(logits, keys, sample_idx, temp, topk,
                                   topp)
                nxt = jnp.where(active, nxt, tokens)
                done = done | (active & (nxt == eos_ids))
                seq_lens = seq_lens + active
                sample_idx = sample_idx + active
                steps_left = steps_left - active.astype(jnp.int32)
                out = lax.dynamic_update_slice(out, nxt[:, None], (0, i))
                return (i + 1, nxt, seq_lens, steps_left, done, sample_idx,
                        pool, out, *counts)

            def cond(carry):
                i, _, _, steps_left, done = carry[:5]
                return (i < limit) & ((~done) & (steps_left > 0)).any()

            out0 = jnp.zeros((M, Cmax), jnp.int32)
            # a family that counts on the device carries the sums of its
            # iterations through the loop and returns them last
            counts0 = ([jnp.zeros((len(self._counter_names),), jnp.int32)]
                       if counted else [])
            (_, tokens, seq_lens, steps_left, done, _, pool, out,
             *counts) = lax.while_loop(
                cond, body, (jnp.int32(0), tokens, seq_lens, steps_left,
                             done, sample_idx, pool, out0, *counts0))
            return (pool, tokens, seq_lens, steps_left, done, out, *counts)

        def spec_fn(params, pool, tokens, seq_lens, draft_lens, steps_left,
                    done, block_tables, keys, sample_idx, temp, topk, topp,
                    lora):
            """One speculative VERIFY dispatch: multi-query decode over
            ``tokens [M, Q]`` (last token + drafts), then sample each
            position with its own per-index key and count the accepted
            draft prefix. Tokens match non-speculative decode bitwise —
            index ``t`` is always drawn with ``fold_in(base, t)``."""
            stats["spec_traces"] += 1              # trace-time only
            M, Q = tokens.shape
            active = (~done) & (steps_left > 0)
            logits, pool, aux = F.paged_spec_step(
                params, cfg, tokens, seq_lens, draft_lens, block_tables,
                pool, active, use_kernel=use_kernel, lora=lora)
            V = logits.shape[-1]
            idx = sample_idx[:, None] + jnp.arange(Q)[None, :]   # [M, Q]
            kt = jax.vmap(jax.vmap(jax.random.fold_in,
                                   in_axes=(None, 0)))(keys, idx)

            def _sampled(lg):
                return G.sample_tokens(
                    lg.reshape(M * Q, V), kt.reshape(M * Q, 2),
                    jnp.repeat(temp, Q), jnp.repeat(topk, Q),
                    jnp.repeat(topp, Q)).reshape(M, Q)

            cand = lax.cond(
                (temp > 0.0).any(), _sampled,
                lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32),
                logits)
            # accepted = length of the leading draft prefix the sampled
            # chain reproduces (cand[q] is the token AFTER tokens[:q+1],
            # verified against draft tokens[q+1])
            ok = (cand[:, :-1] == tokens[:, 1:]) & \
                (jnp.arange(Q - 1)[None, :] < draft_lens[:, None])
            acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
            return (pool, cand, acc, aux) if counted else (pool, cand, acc)

        def mixed_fn(params, pool, tokens, starts, q_lens, active,
                     block_tables, keys, sample_idx, temp, topk, topp,
                     lora):
            """ONE mixed prefill+decode dispatch (ISSUE 20): per-row
            ``starts``/``q_lens`` DEVICE operands carry each slot's role
            — a decode slot is a ``q_len == 1`` row sampling its next
            token, a mid-prefill prompt a ``q_len == n`` row scattering
            its chunk's KV from ``starts`` (= ``num_computed``); the
            sampled token is that prompt's FIRST token when the chunk
            completes it, discarded otherwise. Role churn never
            retraces: one executable per Q bucket serves every mix."""
            stats["mixed_traces"] += 1             # trace-time only
            logits, pool, aux = F.paged_mixed_step(
                params, cfg, tokens, starts, q_lens, block_tables, pool,
                active, use_kernel=use_kernel, lora=lora)
            nxt = _next_tokens(logits, keys, sample_idx, temp, topk, topp)
            return (pool, nxt, aux) if counted else (pool, nxt)

        def sample_fn(logits, keys, idx, temp, topk, topp):
            """First-token sampler over a prefill wave's logits (one
            executable per wave-batch bucket, like prefill itself)."""
            stats["sample_traces"] += 1            # trace-time only
            kt = jax.vmap(jax.random.fold_in)(keys, idx)
            return G.sample_tokens(logits, kt, temp, topk, topp)

        if self._lora is None:
            # bind the LoRA operand away: the jitted surface (and under
            # TP the shard_map arity) is exactly the LoRA-less engine's
            import functools
            prefill_fn = functools.partial(prefill_fn, lora=None)
            decode_fn = functools.partial(decode_fn, lora=None)
            spec_fn = functools.partial(spec_fn, lora=None)
            mixed_fn = functools.partial(mixed_fn, lora=None)
        if self._mesh is not None:
            # tensor parallelism: every pool-touching program runs under
            # shard_map on the replica's "tp" mesh — params enter at the
            # serving_param_specs layout (QKV column-sharded, the rest
            # replicated), the pool at its kv-heads split, and every
            # scheduler operand (tokens / tables / slot state / sampling
            # knobs / the iteration bound) REPLICATED, so the host-side
            # dispatch code below this point is identical at every tp.
            # The sampler (sample_fn) touches neither params nor pool and
            # stays a plain jit on the replicated prefill logits.
            from jax import shard_map
            from jax.sharding import PartitionSpec
            from ...models.llama import serving_param_specs
            ps = serving_param_specs(self._params, self._mesh)
            zs = F.paged_pool_specs(self.cache.pool, self._mesh)
            R = PartitionSpec()
            # a counting family's programs return their counters last
            # (computed from replicated operands: the same on every shard)
            cs = (R,) if counted else ()
            if self._lora is not None:
                # the adapter pool shards like the projections it feeds
                # (qB/kB/vB on their output-feature axis, the rest
                # replicated); the per-row slot ids replicate like every
                # other scheduler operand
                from ...models.lora import lora_pool_specs
                ls = ({"ids": R,
                       "layers": lora_pool_specs(self._lora.layers,
                                                 self._mesh)},)
            else:
                ls = ()
            prefill_fn = shard_map(prefill_fn, mesh=self._mesh,
                                   in_specs=(ps, R, R, R, zs, R) + ls,
                                   out_specs=(R, zs, R), check_vma=False)
            decode_fn = shard_map(decode_fn, mesh=self._mesh,
                                  in_specs=(ps, zs) + (R,) * 12 + ls,
                                  out_specs=(zs, R, R, R, R, R) + cs,
                                  check_vma=False)
            spec_fn = shard_map(spec_fn, mesh=self._mesh,
                                in_specs=(ps, zs) + (R,) * 11 + ls,
                                out_specs=(zs, R, R) + cs, check_vma=False)
            mixed_fn = shard_map(mixed_fn, mesh=self._mesh,
                                 in_specs=(ps, zs) + (R,) * 10 + ls,
                                 out_specs=(zs, R) + cs, check_vma=False)
        donate = donation_supported()

        def jit(name, fn, *donated):
            return jax.jit(_named(name, fn),
                           donate_argnums=donated if donate else ())

        jpre = jit("paged_prefill", prefill_fn, 4)
        jdec = jit("paged_decode", decode_fn, 1)
        jspec = jit("paged_spec", spec_fn, 1)
        jmix = jit("paged_mixed", mixed_fn, 1)
        jsamp = jit("sample_tokens", sample_fn)
        return jpre, jdec, jspec, jsamp, jmix

    def _build_embed(self, jax):
        """The prefill-only embeddings program (ISSUE 19): one jitted
        ``bert_encode`` forward, compiled per ``(batch, length)`` bucket
        exactly like the batched prefill. Plain jit even under TP — the
        encoder runs replicated (params and activations are tiny next to
        the LM's sharded KV traffic)."""
        from ...models.bert import bert_encode
        ecfg, stats = self._embed_cfg, self._stats

        def paged_embed(params, ids, lengths):
            stats["embed_traces"] += 1             # trace-time only
            return bert_encode(params, ecfg, ids, lengths)

        return jax.jit(paged_embed)

    def _lora_operand(self, ids) -> tuple:
        """The trailing LoRA dispatch operand: per-row adapter pool slots
        + the stacked pool leaves, or () with multi-adapter serving off
        (the programs were then partial-bound to ``lora=None``)."""
        if self._lora is None:
            return ()
        import jax.numpy as jnp
        return ({"ids": jnp.asarray(np.asarray(ids, np.int32)),
                 "layers": self._lora.layers},)

    @staticmethod
    def _bucket(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    def _span(self, name: str, kind: Optional[str] = None):
        """One flat ``serve:*`` phase span of the current step."""
        return self.spans.span(name, kind, step=self.step_no)

    def _record_dispatch(self, kind: str, t0: float, t1: float) -> None:
        """Count + time ONE device dispatch by kind (ISSUE 20), from the
        ``serve:dispatch`` span's start to the ``serve:fetch`` span's
        end (the same two ``perf_counter`` stamps, no second pair). Every
        dispatch — batched prefill, embed encode, decode loop, mixed
        step, spec verify — lands here, so ``chunks`` is the all-kinds
        dispatch total, the per-kind ``*_dispatches`` counters split it,
        and the wall time feeds the bounded window behind the p50/p99
        dispatch-latency rows in stats()/health_snapshot()."""
        self._stats["chunks"] += 1
        self._stats[kind + "_dispatches"] += 1
        self._dispatch_ms[kind].append((t1 - t0) * 1e3)
        # what the pool holds, once a dispatch (over the dispatches of a
        # window: the mean share of the pool in use), and of it what the
        # bounded groups of a grouped cache hold
        self.spans.count("kv_blocks_in_use_sum",
                         self.cache.manager.blocks_in_use)
        if not self.cache.uniform:
            self.spans.count("kv_window_blocks_in_use_sum", sum(
                self.cache.window_blocks(len(r.blocks))
                for r in self._sched.live if r.blocks))

    def _count_dispatch(self, aux=None) -> Dict[str, int]:
        """Add one dispatch's device counters (the small array a counting
        family's program returns, fetched here inside the ``serve:fetch``
        the dispatch makes anyway) to the span aggregator under the
        family's names, and return them. A family that counts nothing
        passes nothing."""
        if aux is None or not self._counter_names:
            return {}
        counts = dict(zip(self._counter_names, np.asarray(aux).tolist()))
        for name, n in counts.items():
            self.spans.count(name, n)
        return counts

    def _dispatch_latency(self) -> Dict[str, Dict[str, float]]:
        """p50/p99 dispatch wall time per kind over the recent window —
        the stall mixed batching removes, as a number operators watch."""
        out: Dict[str, Dict[str, float]] = {}
        for kind, window in self._dispatch_ms.items():
            n = int(self._stats.get(kind + "_dispatches", 0))
            if window:
                xs = np.asarray(window, np.float64)
                out[kind] = {
                    "count": n,
                    "p50_ms": round(float(np.percentile(xs, 50)), 3),
                    "p99_ms": round(float(np.percentile(xs, 99)), 3)}
            else:
                out[kind] = {"count": n, "p50_ms": None, "p99_ms": None}
        return out

    # ---- request lifecycle ------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = "unset",
               timeout_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None, priority: int = 0,
               temperature: Any = "unset", top_k: Any = "unset",
               top_p: Any = "unset", seed: Any = "unset",
               adapter_id: Optional[str] = None,
               enqueue_t: Optional[float] = None) -> int:
        """Queue one prompt; returns the request id. ``eos_token_id``
        defaults to the engine's GenerationConfig (pass ``None`` explicitly
        to disable EOS for this request).

        Sampling knobs (ISSUE 11) resolve through the ONE
        ``GenerationConfig`` struct (left unset -> the engine's
        ``gen_config`` defaults; explicit ``None`` DISABLES top_k/top_p):
        ``temperature`` 0 = greedy argmax on device, bit-identical to the
        greedy-only engine; > 0 samples with per-request PRNG keys
        derived from ``seed``, so the stream is reproducible per
        ``(request, seed)`` across preemption, crash resubmit and
        failover. Genuinely unsupported combinations (negative/non-finite
        temperature, ``top_k < 1``, ``top_p`` outside ``(0, 1]``) raise a
        structured ``ValueError`` naming the supported surface.

        Lifecycle/policy knobs (ISSUE 6): ``timeout_s`` (relative to now) /
        ``deadline_s`` (absolute ``time.time()``) bound the request's wall
        time — expiry while QUEUED sheds it (state ``shed``), expiry after
        it started terminates it mid-flight (state ``timed_out``), both
        freeing its KV blocks; the earlier of the two wins when both are
        given. ``tenant`` scopes fair-share scheduling, per-tenant stats
        and prefix-cache quotas; ``priority`` orders the priority policy
        (higher first).

        ``adapter_id`` (ISSUE 19) selects a registered LoRA adapter for
        this request (None = base traffic — the zeroed slot-0 adapter,
        bit-identical to the LoRA-less engine). The adapter must already
        be :meth:`register_adapter`-ed; admission pins it device-resident
        for the request's whole lifetime (preemption included), so its
        weights can never be evicted mid-stream.

        ``enqueue_t`` (``time.time()``) is when a front line first saw
        the request, if one stands before this call: the ``queue_wait_s``
        histogram counts from it, so the wait in the server's command
        queue is part of the number.

        Raises :class:`ServingQueueFull` — carrying ``queue_depth`` /
        ``live_slots`` / ``retry_after_s`` for the caller's backoff — when
        the bounded admission queue is full: the submit is SHED, not
        blocked."""
        deadline = deadline_s
        if timeout_s is not None:
            t = time.time() + float(timeout_s)
            deadline = t if deadline is None else min(deadline, t)
        req = self._make_request(prompt, max_new_tokens, eos_token_id,
                                 tenant, priority, deadline,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p, seed=seed,
                                 adapter_id=adapter_id)
        req.enqueue_t = enqueue_t
        with self._lock:
            rid = self._sched.submit(req)
            self._journal_submit(req)
            return rid

    def _make_request(self, prompt, max_new_tokens, eos_token_id, tenant,
                      priority, deadline, tokens: Sequence[int] = (),
                      temperature: Any = "unset", top_k: Any = "unset",
                      top_p: Any = "unset", seed: Any = "unset",
                      adapter_id: Optional[str] = None) -> Request:
        """One Request from user-facing arguments — the single place
        submit() and resubmit() resolve GenerationConfig defaults (the
        sampling knobs included), the "unset" sentinels and the tenant
        key, so fresh and crash-recovered requests can never diverge in
        defaults."""
        from ...models.generation import GenerationConfig, validate_sampling
        g = GenerationConfig.resolve(
            self._gen, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, temperature=temperature,
            top_k=top_k, top_p=top_p, seed=seed)
        validate_sampling(g)
        req = Request(
            rid=-1, prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(g.max_new_tokens),
            eos_token_id=g.eos_token_id,
            temperature=float(g.temperature),
            top_k=int(g.top_k) if g.top_k is not None else None,
            top_p=float(g.top_p) if g.top_p is not None else None,
            seed=int(g.seed),
            tenant=str(tenant) if tenant is not None else DEFAULT_TENANT,
            priority=int(priority),
            deadline=float(deadline) if deadline is not None else None)
        req.tokens = [int(t) for t in tokens]
        if req.tokens and req.eos_token_id is not None and \
                req.tokens[-1] == req.eos_token_id:
            req.eos_seen = True
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.prompt_len < 1:
            raise ValueError("prompt must contain at least one token")
        if adapter_id is not None:
            if self._lora is None:
                raise ValueError(
                    "adapter_id requires multi-adapter serving: set "
                    "ServingConfig.lora_slots / FLAGS_serving_lora_slots "
                    "> 0")
            if not self._lora.is_registered(adapter_id):
                raise ValueError(
                    f"adapter {adapter_id!r} is not registered on this "
                    f"engine (register_adapter() first; registered: "
                    f"{self._lora.registered()})")
            req.adapter_id = str(adapter_id)
        return req

    def resubmit(self, prompt, tokens: Sequence[int] = (),
                 max_new_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = "unset",
                 deadline: Optional[float] = None,
                 tenant: Optional[str] = None, priority: int = 0,
                 temperature: Any = "unset", top_k: Any = "unset",
                 top_p: Any = "unset", seed: Any = "unset",
                 jid: Optional[int] = None,
                 adapter_id: Optional[str] = None,
                 enqueue_t: Optional[float] = None) -> int:
        """Re-queue a request recovered from a torn-down engine with the
        tokens it had already emitted — the supervisor's restart path.
        Rides the preemption-recompute machinery: prefill recomputes KV
        for ``prompt + tokens[:-1]`` and decode resumes from the last
        token, so outputs are bit-identical to an uninterrupted run
        (greedy by determinism; sampled because the per-token key is a
        pure function of ``(seed, token index)`` — the caller passes the
        original RESOLVED sampling knobs) and the already-delivered
        tokens are never re-emitted. ``deadline`` is ABSOLUTE (the
        original request's). Bypasses the queue-depth shed — everything
        resubmitted was already accepted once, and the recovered set
        (old queue + old slots) can exceed the admission bound by up to
        ``max_slots``.

        ``jid`` re-attaches the request to an existing journal record
        (crash recovery / cross-replica failover under a shared journal):
        the record is resumed in place — no duplicate submit event — so
        recovery is idempotent across repeated crashes. An unknown or
        already-terminal jid falls back to a fresh journal record seeded
        with the delivered tokens."""
        req = self._make_request(prompt, max_new_tokens, eos_token_id,
                                 tenant, priority, deadline, tokens=tokens,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p, seed=seed,
                                 adapter_id=adapter_id)
        req.enqueue_t = enqueue_t
        if req.finished:
            raise ValueError(
                f"request is already finished ({len(req.tokens)} tokens of "
                f"{req.max_new_tokens}); record it, don't resubmit it")
        with self._lock:
            rid = self._sched.submit(req, enforce_bound=False)
            self._journal_submit(req, jid)
            return rid

    # ---- durable journal hooks (ISSUE 18) ---------------------------------

    def _journal_submit(self, req: Request,
                        jid: Optional[int] = None) -> None:
        """Attach a just-admitted request to the journal: resume an
        existing record when ``jid`` names a live one (recovery /
        failover / adoption), else append a fresh submit event carrying
        the RESOLVED record. Caller holds the engine lock."""
        if self.journal is None:
            return
        if jid is not None and jid >= 0 \
                and self.journal.resume(jid, req.tokens):
            req.jid = jid
        else:
            req.jid = self.journal.log_submit(
                prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                eos_token_id=req.eos_token_id,
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, seed=req.seed, tenant=req.tenant,
                priority=req.priority, deadline=req.deadline,
                tokens=req.tokens, adapter_id=req.adapter_id)
        self._jlive[req.rid] = req.jid

    def _journal_end(self, req: Request) -> None:
        """Journal a terminal transition the moment it happens (deadline
        expiry, cancel, shed) — a disowned request (jid -1) logs
        nothing. Caller holds the engine lock."""
        self._jlive.pop(req.rid, None)
        if self.journal is not None and req.jid >= 0:
            self.journal.log_terminal(req.jid, req.state)

    def _journal_step(self, emitted: Dict[int, List[int]]) -> None:
        """The per-step journal hook, run under the engine lock right
        after ``_step``: log every delivered-token cursor advance, log
        terminal transitions the retire sweep made, then flush — ONE
        fsync per step under the default policy, at exactly the boundary
        where the emitted tokens become visible to the caller."""
        if self.journal is None:
            return
        for rid, toks in emitted.items():
            jid = self._jlive.get(rid)
            if jid is not None and toks:
                self.journal.log_tokens(jid, toks)
        fin = self._sched.finished
        for rid in [r for r in self._jlive if r in fin]:
            req = fin[rid]
            self._jlive.pop(rid, None)
            if req.jid >= 0:
                self.journal.log_terminal(req.jid, req.state)
        self.journal.flush()

    def _journal_flush(self) -> None:
        if self.journal is not None:
            self.journal.flush()

    def journal_disown(self, rid: int) -> None:
        """Detach a live request from its journal record WITHOUT ending
        it — the deliberate same-fleet moves (migration release, prefill
        handoff release, hedge copies) cancel their vacated copy, and
        that cancel must not mark the still-live logical request
        terminal. The new owner re-attaches via :meth:`journal_own` or
        ``resubmit(jid=)``/``adopt``."""
        with self._lock:
            self._jlive.pop(rid, None)
            req = self._sched.find(rid)
            if req is not None:
                req.jid = -1

    def journal_own(self, rid: int, jid: int, tokens) -> bool:
        """Attach a live request to journal record ``jid`` (hedge
        promotion: the winning copy inherits the logical request's
        record), rebasing the record's delivered cursor to ``tokens`` —
        what the client actually saw. False when the record is unknown /
        terminal or the rid is not live."""
        with self._lock:
            if self.journal is None:
                return False
            req = self._sched.find(rid)
            if req is None or not self.journal.resume(jid, tokens):
                return False
            req.jid = int(jid)
            self._jlive[rid] = req.jid
            return True

    # ---- multi-adapter LoRA + embeddings endpoint (ISSUE 19) --------------

    def register_adapter(self, name: str, adapter_params) -> None:
        """Accept one LoRA adapter (host-side checksummed copy; rank must
        match ``lora_rank``) so requests may select it via
        ``submit(adapter_id=name)``. Re-registering an unpinned adapter
        replaces its weights; a pinned one (running requests) refuses."""
        with self._lock:
            if self._lora is None:
                raise ValueError(
                    "multi-adapter serving is off: set ServingConfig."
                    "lora_slots / FLAGS_serving_lora_slots > 0")
            self._lora.register(name, adapter_params)

    def adapter_registered(self, name: str) -> bool:
        with self._lock:
            return self._lora is not None and \
                self._lora.is_registered(name)

    def adapter_resident(self, name: str) -> bool:
        """Whether ``name`` is loaded in the device pool right now — the
        router's adapter-affinity signal (land a request where its
        adapter is already resident and skip the H2D load)."""
        with self._lock:
            return self._lora is not None and \
                self._lora.slot_of(name) is not None

    def adapter_partition(self) -> Optional[Dict[str, Any]]:
        """A consistent view of the adapter pool under the engine lock —
        what the InvariantAuditor's ``adapter_pool_partition`` check
        reads: every registered adapter is resident XOR evicted, every
        live request's adapter is resident at the slot the request
        carries, and every such request holds a pin. None with
        multi-adapter serving off."""
        with self._lock:
            if self._lora is None:
                return None
            running = {r.rid: (r.adapter_id, int(r.adapter_slot))
                       for r in self._sched.live
                       if r.adapter_id is not None}
            return {"registered": self._lora.registered(),
                    "resident": self._lora.resident(),
                    "evicted": self._lora.evicted(),
                    "pinned": self._lora.pinned(),
                    "running": running}

    def submit_embedding(self, prompt, timeout_s: Optional[float] = None,
                         deadline_s: Optional[float] = None,
                         tenant: Optional[str] = None,
                         priority: int = 0) -> int:
        """Queue one prefill-only EMBEDDING request (ISSUE 19): it rides
        the admission queue (bounded — sheds with ServingQueueFull like
        generate traffic), runs through the attached encoder in the next
        step's batched bucketed dispatch, and retires at prefill
        completion with the pooled hidden states readable via
        :meth:`embedding`. Embeds hold no decode slot and no KV blocks
        and are NOT journaled — they carry no generation state, so a
        crash loses nothing a stateless client retry cannot recompute."""
        if self._embed_params is None:
            raise ValueError(
                "no embedding model attached: construct the engine with "
                "embed_model=(BertConfig, params) to serve embeddings")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError("prompt must contain at least one token")
        if prompt.shape[0] > self._embed_cfg.max_position_embeddings:
            raise ValueError(
                f"embedding prompt has {prompt.shape[0]} tokens > the "
                f"encoder's max_position_embeddings "
                f"{self._embed_cfg.max_position_embeddings}")
        deadline = deadline_s
        if timeout_s is not None:
            t = time.time() + float(timeout_s)
            deadline = t if deadline is None else min(deadline, t)
        req = Request(
            rid=-1, prompt=prompt, max_new_tokens=1,
            tenant=str(tenant) if tenant is not None else DEFAULT_TENANT,
            priority=int(priority),
            deadline=float(deadline) if deadline is not None else None,
            kind="embed")
        with self._lock:
            return self._sched.submit(req)

    def embedding(self, rid: int) -> np.ndarray:
        """The pooled ``[hidden_size]`` fp32 embedding of a finished
        embed request (KeyError while still queued/in-flight)."""
        with self._lock:
            return self._sched.finished[rid].embedding

    # ---- live KV migration (ISSUE 16) -------------------------------------

    def kv_shape_key(self) -> tuple:
        """The KV-layout signature two engines must share for a block
        chain to transfer byte-for-byte: block size, quantization mode,
        TP degree and every pool leaf's per-block shape/dtype (the block
        axis itself excluded — pools of different sizes interoperate).
        In a shared-programs fleet these always agree; :meth:`adopt`
        refuses a mismatched payload so a heterogeneous fleet falls back
        to resubmit instead of writing garbage KV."""
        return (int(self.config.block_size), str(self.config.kv_quant),
                int(self.config.tp),
                tuple(sorted((name, str(a.dtype),
                              tuple(int(s) for i, s in enumerate(a.shape)
                                    if i != 1))
                             for name, a in self.cache.pool.items())))

    def serialize_request(self, rid: int) -> Optional[Dict[str, Any]]:
        """Snapshot one live request for adoption by another replica: the
        resolved record (prompt, delivered tokens, sampling knobs, tenant
        / priority / deadline) plus — for a request holding a slot — its
        KV block chain's device bytes (one gather per pool leaf over the
        blocks with committed entries, materialized D2H). Returns None
        for unknown/terminal requests and for finished ones awaiting the
        retire sweep (their work is done; migrating it would re-deliver).
        Queued and preempted-requeued requests serialize with ``kv:
        None`` — they hold no KV, so adoption degrades to a plain
        resubmit of the record."""
        with self._lock:
            req = self._sched.find(rid)
            if req is None or req.terminal or req.finished:
                return None
            payload: Dict[str, Any] = {
                "prompt": np.array(req.prompt, np.int32),
                "tokens": list(req.tokens),
                "max_new_tokens": req.max_new_tokens,
                "eos_token_id": req.eos_token_id,
                "temperature": req.temperature,
                "top_k": req.top_k, "top_p": req.top_p, "seed": req.seed,
                "tenant": req.tenant, "priority": req.priority,
                "deadline": req.deadline,
                "jid": req.jid,
                "adapter_id": req.adapter_id,
                "kv": None,
            }
            if req.slot is None or not req.blocks or not self.cache.uniform:
                return payload       # grouped cache: recompute on arrival
            if req.prefilling:
                entries = int(req.num_computed)
            else:
                entries = int(self._seq_lens[req.slot])
            bs = self.config.block_size
            nd = min(-(-entries // bs), len(req.blocks)) if entries else 0
            data = None
            if nd:
                idx = np.asarray(req.blocks[:nd], np.int32)
                data = {name: np.asarray(arr[:, idx])
                        for name, arr in self.cache.pool.items()}
            payload["kv"] = {
                "entries": entries,
                "prefilling": bool(req.prefilling),
                "data_blocks": nd,
                "total_blocks": len(req.blocks),
                "data": data,
                "shape_key": self.kv_shape_key(),
            }
            return payload

    def adopt(self, payload: Dict[str, Any]) -> int:
        """Adopt a request serialized on another replica, KV included:
        allocate the chain, H2D-write the committed blocks, seat the
        request directly in a RUNNING slot (mid-chunked-prefill resumes
        at its chunk offset; decoding resumes from its last token with
        the sampling cursor continuing at the same PRNG index, so the
        stream stays bit-identical) and re-register the chain's prefix
        keys. Raises :class:`AdoptError` when the blocks can't land here
        — no free slot, pool full, KV-layout/TP-shape mismatch — and the
        caller falls back to the resubmit/recompute path. A ``kv: None``
        payload (queued/preempted origin) is queued via the resubmit
        path directly."""
        with self._lock:
            aid = payload.get("adapter_id")
            if aid is not None and (self._lora is None
                                    or not self._lora.is_registered(aid)):
                raise AdoptError(
                    f"adapter {aid!r} is not registered on this replica; "
                    f"falling back to resubmit")
            req = self._make_request(
                payload["prompt"], payload["max_new_tokens"],
                payload["eos_token_id"], payload["tenant"],
                payload["priority"], payload["deadline"],
                tokens=payload["tokens"],
                temperature=payload["temperature"],
                top_k=payload["top_k"], top_p=payload["top_p"],
                seed=payload["seed"], adapter_id=aid)
            if req.finished:
                raise AdoptError("request already finished; record it, "
                                 "don't migrate it")
            kv = payload.get("kv")
            if kv is None:
                rid = self._sched.submit(req, enforce_bound=False)
                self._journal_submit(req, payload.get("jid"))
                return rid
            if tuple(kv["shape_key"]) != self.kv_shape_key():
                raise AdoptError("KV layout mismatch (block size / "
                                 "kv_quant / TP shape differ); falling "
                                 "back to resubmit")
            if req.kv_tokens > self.cache.max_model_len:
                raise AdoptError("chain exceeds this engine's "
                                 "max_model_len")
            free = [m for m, r in enumerate(self._sched.slots) if r is None]
            if not free:
                raise AdoptError("no free decode slot")
            total = int(kv["total_blocks"])
            if total > self.cache.blocks_per_seq:
                raise AdoptError("chain longer than the block table")
            if not self.cache.manager.can_alloc(total):
                raise AdoptError("pool full")
            blocks = self.cache.manager.alloc(total)
            nd = int(kv["data_blocks"])
            try:
                if nd:
                    self.cache.write_blocks(blocks[:nd], kv["data"])
            except Exception as e:
                self.cache.manager.free(blocks)
                raise AdoptError(f"KV restore failed: {e}")
            if req.adapter_id is not None:
                # pin the adapter resident BEFORE seating: a fully pinned
                # pool refuses the migration (recompute elsewhere beats
                # evicting someone's in-flight weights)
                aslot = self._lora.acquire(req.adapter_id)
                if aslot is None:
                    self.cache.manager.free(blocks)
                    raise AdoptError(
                        f"adapter pool fully pinned; cannot seat adapter "
                        f"{req.adapter_id!r} — falling back to resubmit")
                req.adapter_slot = aslot
            slot = free[0]
            self._clear_slot(slot)
            self._sched.adopt_running(req, slot, blocks)
            if req.adapter_id is not None:
                self._lora_pinned[req.rid] = req.adapter_id
            self.cache.assign(slot, blocks)
            entries = int(kv["entries"])
            if kv["prefilling"]:
                # resume the chunked prefill exactly at its chunk offset:
                # the next step's mixed dispatch picks the slot up
                req.prefill_ids = req.build_prefill_ids()
                req.num_computed = entries
            else:
                req.prefill_ids = None
                self._start_decode(req)
            # re-derive the prefix-cache registration chain (the chained
            # content keys are a pure function of the token ids, so the
            # adopted blocks register under exactly the origin's keys)
            req.reg_state = self.cache.register_prefix(
                req.build_prefill_ids(), blocks, entries,
                tenant=req.tenant, namespace=req.adapter_id)
            self._journal_submit(req, payload.get("jid"))
            return req.rid

    # ---- fleet-wide cache pulls (ISSUE 17) --------------------------------

    def export_chain(self, chain) -> Optional[Dict[str, Any]]:
        """Serialize the longest CONTIGUOUS prefix of ``chain`` — a list
        of ``(key, tokens)`` pairs in :func:`~.paged_cache.
        prefix_block_chain` order — that this replica holds: device
        blocks gather D2H through :meth:`PagedKVCache.read_block` (the
        device-scalar index discipline, one compiled slice program),
        host-tier blocks come from a verified non-destructive
        :meth:`HostOffloadTier.peek`. Every block's leaves are stamped
        with a write-time CRC32 (the ``offload.py`` checksum), so the
        receiving :meth:`graft_chain` can detect any corruption in
        flight and degrade to recompute — never wrong KV. The export is
        a COPY: refcounts, registrations and tier entries on this
        replica are untouched. Returns None when not even the first key
        resolves (a stale directory entry — the benign miss)."""
        with self._lock:
            blocks: List[Dict[str, Any]] = []
            for key, toks in chain:
                toks = tuple(int(t) for t in toks)
                data = None
                b = self.cache.manager.lookup(key, toks)
                if b is not None:
                    data = {name: np.asarray(arr)
                            for name, arr in
                            self.cache.read_block(b).items()}
                elif self.cache.offload is not None:
                    hit = self.cache.offload.peek(key, toks)
                    if hit is not None:
                        data = {name: np.array(arr) for name, arr
                                in hit.items()}
                if data is None:
                    break                 # contiguity ends at first miss
                blocks.append({"key": int(key), "tokens": toks,
                               "data": data,
                               "crc": {n: _block_crc(a)
                                       for n, a in data.items()}})
            if not blocks:
                return None
            if self._corrupt_next_export:
                # chaos drill: flip one byte AFTER the checksums stamped
                self._corrupt_next_export = False
                leaf = sorted(blocks[0]["data"])[0]
                arr = np.array(blocks[0]["data"][leaf], copy=True)
                arr.reshape(-1).view(np.uint8)[0] ^= 0xFF
                blocks[0]["data"][leaf] = arr
            return {"blocks": blocks, "shape_key": self.kv_shape_key()}

    def graft_chain(self, payload: Dict[str, Any]) -> Dict[str, int]:
        """Graft an exported chain into this replica's prefix cache:
        verify each block's checksums, allocate a device block,
        H2D-write the bytes and register the chain key — the block then
        parks refcount-0 on the evictable list exactly like a locally
        computed cached block, where the next ``admit()`` hits it. Walks
        in chain order and STOPS at the first checksum mismatch (the
        rest of the chain is downstream of corrupt KV), already-present
        key, or dry pool. Returns ``{"grafted", "present", "corrupt"}``
        — the caller's submit degrades to recompute for whatever did
        not land, so a failed pull can only cost time."""
        counts = {"grafted": 0, "present": 0, "corrupt": 0}
        if payload is None:
            return counts
        with self._lock:
            if tuple(payload["shape_key"]) != self.kv_shape_key():
                raise AdoptError("KV layout mismatch (block size / "
                                 "kv_quant / TP shape differ); pull "
                                 "falls back to recompute")
            for ent in payload["blocks"]:
                key, toks = int(ent["key"]), tuple(ent["tokens"])
                if self.cache.manager._hash2block.get(key) is not None:
                    counts["present"] += 1
                    continue              # first writer won locally
                bad = any(_block_crc(np.asarray(a)) != ent["crc"][n]
                          for n, a in ent["data"].items())
                if bad:
                    counts["corrupt"] += 1
                    break
                if not self.cache.manager.can_alloc(1):
                    break                 # pool pressure: partial graft
                [b] = self.cache.manager.alloc(1)
                self.cache.write_block(b, ent["data"])
                self.cache.manager.register(key, b, toks)
                # release to the evictable list: cached, shareable, and
                # reclaimable under pressure — never a leak at quiesce
                self.cache.manager.free([b])
                counts["grafted"] += 1
            return counts

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request: its remaining work is
        dropped and every KV block it holds returns to the pool
        immediately (the preemption free path — free, do NOT requeue).
        Safe at any lifecycle point — queued, mid-chunked-prefill,
        decoding, or preempted-and-requeued. Returns True when the
        request was live and is now ``cancelled``; False when it already
        reached a terminal state (or the rid is unknown) — cancellation
        is idempotent, racing a retirement is not an error. The partial
        output stays readable via :meth:`request`/``result``."""
        with self._lock:
            req = self._sched.find(rid)
            if req is None:
                return False
            if self._retire_if_finished(req):
                return False         # its work completed first: not an error
            self._terminate(req, CANCELLED)
            self._journal_flush()
            return True

    def cancel_all(self) -> int:
        """Cancel every queued and running request (the abandoned-stream
        path); returns how many were cancelled."""
        with self._lock:
            n = 0
            for req in list(self._sched.queue) + self._sched.live:
                if self._retire_if_finished(req):
                    continue
                self._terminate(req, CANCELLED)
                n += 1
            if n:
                self._journal_flush()
            return n

    # ---- adapter pin lifecycle (ISSUE 19) ---------------------------------

    def _lora_gate(self, req: Request) -> bool:
        """The scheduler's admission gate: pin the pick's adapter
        device-resident (loading it over the LRU unpinned victim when
        cold) and stamp its pool slot on the request. False — skip this
        pick, no head-of-line blocking — when every pool slot is pinned
        by other running requests. Idempotent per request: a pick that
        pinned but then waited for KV blocks (or was preempted) keeps
        its pin and slot."""
        if req.adapter_id is None:
            req.adapter_slot = 0
            return True
        if req.rid in self._lora_pinned:
            return True
        slot = self._lora.acquire(req.adapter_id)
        if slot is None:
            return False
        self._lora_pinned[req.rid] = req.adapter_id
        req.adapter_slot = slot
        return True

    def _lora_release(self, req: Request) -> None:
        """Drop a terminal request's adapter pin (the adapter stays
        resident-warm until the LRU needs its slot)."""
        if self._lora is None:
            return
        name = self._lora_pinned.pop(req.rid, None)
        if name is not None:
            self._lora.release(name)

    def _lora_sweep(self) -> None:
        """Release pins whose requests the retire sweep finished — the
        step-boundary companion to the explicit terminal-path releases,
        mirroring how ``_journal_step`` collects finished jids."""
        if self._lora is None or not self._lora_pinned:
            return
        fin = self._sched.finished
        for rid in [r for r in self._lora_pinned if r in fin]:
            self._lora.release(self._lora_pinned.pop(rid))

    def _retire_if_finished(self, req: Request) -> bool:
        """A request can sit FINISHED in its slot until the next step's
        retire sweep (e.g. oom-truncated with no decode dispatch after
        it); a cancel or deadline racing that sweep must retire it as the
        completed work it is, never reclassify it. Only slot-holders can
        be in this state — a queued request has produced nothing to
        finish."""
        if req.slot is None or not req.finished:
            return False
        m = req.slot
        self._sched.finish(req)
        self._clear_slot(m)
        self._lora_release(req)
        self._journal_end(req)
        return True

    def _clear_slot(self, m: int) -> None:
        self._tokens[m] = 0
        self._seq_lens[m] = 0
        self._steps_left[m] = 0
        self._done[m] = True
        self._eos[m] = -1
        self._temp[m] = 0.0
        self._topk[m] = 0
        self._topp[m] = 1.0
        self._keys[m] = 0
        self._sample_idx[m] = 0
        self._adapters[m] = 0

    def _terminate(self, req: Request, state: str) -> None:
        m = req.slot
        self._sched.terminate(req, state)
        if m is not None:
            self._clear_slot(m)
        self._lora_release(req)
        self._journal_end(req)

    def _expire_deadlines(self, now: float) -> None:
        """Terminal-state sweep, run once per step and only while some
        live request carries a deadline: queued requests past theirs are
        SHED (they never ran — admission control, the client should back
        off), except preempted ones which already ran and so TIME OUT;
        running requests past theirs TIME OUT, freeing their blocks
        mid-flight so a stuck consumer can never pin the pool."""
        if not self._sched.deadline_requests:
            return
        for req in [r for r in self._sched.queue
                    if r.deadline is not None and r.deadline < now]:
            self._terminate(req,
                            SHED if not (req.preemptions or req.tokens)
                            else TIMED_OUT)
        # a request that already FINISHED but has not been swept by
        # retire_finished yet (e.g. oom-truncated with no decode dispatch
        # after it) keeps its completed record — its work is done, an
        # expired deadline must not reclassify it as timed out
        for req in [r for r in self._sched.live
                    if r.deadline is not None and r.deadline < now
                    and not r.finished]:
            self._terminate(req, TIMED_OUT)

    def _chain_ids(self, req: Request, start: int, stop: int) -> np.ndarray:
        """Token ids backing the KV entries ``[start, stop)`` a running
        request has written (entry p < prompt_len holds prompt[p]'s KV,
        entry p >= prompt_len holds tokens[p - prompt_len]'s) — the
        prefix-cache registration chain. Sliced, not the whole history:
        rebuilding prompt+tokens per filled block would cost O(seq_len^2)
        per request in the continuous-batching hot loop."""
        pl = len(req.prompt)
        if stop <= pl:
            return req.prompt[start:stop]
        gen = np.asarray(req.tokens[max(0, start - pl):stop - pl], np.int32)
        if start >= pl:
            return gen
        return np.concatenate([req.prompt[start:], gen])

    def _start_decode(self, req: Request) -> None:
        """Move a request whose prefill just completed into the decode slot
        arrays. Fresh requests enter with their first sampled token already
        in ``tokens``; readmitted ones resume from their last token — and
        from their next SAMPLE INDEX, so the per-index PRNG keys line up
        with an uninterrupted run."""
        from ...models.generation import seed_key
        m = req.slot
        self._tokens[m] = req.tokens[-1]
        self._seq_lens[m] = req.prompt_len + len(req.tokens) - 1
        self._steps_left[m] = req.max_new_tokens - len(req.tokens)
        self._done[m] = False
        self._eos[m] = -1 if req.eos_token_id is None else req.eos_token_id
        self._temp[m] = req.temperature
        self._topk[m] = req.top_k if req.top_k is not None else 0
        self._topp[m] = req.top_p if req.top_p is not None else 1.0
        self._keys[m] = seed_key(req.seed)
        self._sample_idx[m] = len(req.tokens)
        self._adapters[m] = req.adapter_slot

    def _emit_first(self, req: Request, tok0: int, now: float,
                    emitted: Dict[int, List[int]]) -> None:
        req.first_token_t = now
        if req.admit_t is not None:
            self.spans.observe("prefill_s", now - req.admit_t)
        req.tokens.append(tok0)
        emitted.setdefault(req.rid, []).append(tok0)
        if req.eos_token_id is not None and tok0 == req.eos_token_id:
            req.eos_seen = True
        if req.finished:
            self._sched.finish(req)
        else:
            self._start_decode(req)

    def _plan_admissions(self) -> List[Tuple[int, List[Request]]]:
        """Admit every request the policy and the pool allow, then split
        the wave: COLD short prompts take the batched bucketed prefill
        (returned here as ``(length bucket, requests)`` waves — one
        dispatch per power-of-2 length bucket, batch dim padded to the
        wave-size bucket); prefix-cache hits (prefill starts at an
        offset), long prompts (chunked), and readmissions (recompute) go
        through the mixed step, one row each.
        A request's FIRST admission lands its queue wait (enqueue ->
        admit, the wait in the server's command queue included) in the
        ``queue_wait_s`` histogram."""
        gate = self._lora_gate if self._lora is not None else None
        admitted: List[Request] = []
        while (req := self._sched.next_admission(gate=gate)) is not None:
            admitted.append(req)
            if not req.preemptions and not req.tokens:
                self.spans.observe("queue_wait_s",
                                   req.admit_t - req.enqueue_t)
        chunk = self.config.prefill_chunk
        by_bucket: Dict[int, List[Request]] = {}
        for req in admitted:
            if req.num_computed == 0 and not req.tokens \
                    and (chunk is None or req.prompt_len <= chunk):
                by_bucket.setdefault(self._bucket(req.prompt_len),
                                     []).append(req)
        return sorted(by_bucket.items())

    def _prefill_dispatch(self, Sb: int, group: List[Request],
                          emitted: Dict[int, List[int]]) -> None:
        """ONE batched bucketed prefill over a wave of cold short
        prompts; every request in it emits its first token."""
        import jax.numpy as jnp
        with self._span("serve:operands", "prefill"):
            self._prefill_buckets.add(Sb)
            Bb = 1
            while Bb < len(group):
                Bb *= 2
            Bb = min(Bb, self.config.max_slots)
            ids = np.zeros((Bb, Sb), np.int32)
            plens = np.ones((Bb,), np.int32)      # pad rows: harmless len 1
            tables = np.zeros((Bb, self.cache.blocks_per_seq), np.int32)
            act = np.zeros((Bb,), bool)
            aids = np.zeros((Bb,), np.int32)      # pad rows: base adapter
            for r, req in enumerate(group):
                ids[r, :req.prompt_len] = req.prompt
                plens[r] = req.prompt_len
                tables[r] = self.cache.tables[req.slot]
                act[r] = True
                aids[r] = req.adapter_slot
            ops = (jnp.asarray(ids), jnp.asarray(plens), jnp.asarray(tables))
            tail = (jnp.asarray(act), *self._lora_operand(aids))
        with _watchdog.section("serving.prefill"):
            with self._span("serve:dispatch", "prefill") as d:
                logits, self.cache.pool, aux = self._jprefill(
                    self._params, *ops, self.cache.pool, *tail)
            with self._span("serve:fetch", "prefill") as f:
                first = self._first_tokens(logits, group, Bb)
                self._count_dispatch(aux)
        with self._span("serve:commit", "prefill"):
            self._record_dispatch("prefill", d.t0, f.t1)
            self.spans.count("prefill_tokens",
                             sum(r.prompt_len for r in group))
            self.spans.count("prefill_lanes_real",
                             sum(r.prompt_len for r in group))
            self.spans.count("prefill_lanes_total", Bb * Sb)
            now = time.time()
            for r, req in enumerate(group):
                req.num_computed = req.prompt_len
                req.reg_state = self.cache.register_prefix(
                    req.prompt, req.blocks, req.prompt_len, req.reg_state,
                    tenant=req.tenant, namespace=req.adapter_id)
                self._emit_first(req, int(first[r]), now, emitted)

    def _plan_embeds(self) -> List[Tuple[int, List[Request]]]:
        """Take every queued embedding request (ISSUE 19) off the queue,
        grouped into power-of-2 length buckets — exactly the batched-
        bucketed-prefill shape discipline."""
        if self._embed_params is None:
            return []
        by_bucket: Dict[int, List[Request]] = {}
        for req in self._sched.admit_embeds():
            by_bucket.setdefault(self._bucket(req.prompt_len),
                                 []).append(req)
        return sorted(by_bucket.items())

    def _embed_dispatch(self, Sb: int, grp: List[Request]) -> None:
        """One jitted ``bert_encode`` dispatch for one bucket of
        embedding requests. The whole batch admits, encodes and FINISHES
        inside one locked step — embeds hold no decode slot and no KV
        blocks, so no observer ever sees one mid-flight."""
        import jax.numpy as jnp
        with self._span("serve:operands", "embed"):
            Bb = 1
            while Bb < len(grp):
                Bb *= 2
            ids = np.zeros((Bb, Sb), np.int32)
            lens = np.zeros((Bb,), np.int32)      # pad rows: length 0
            for r, req in enumerate(grp):
                ids[r, :req.prompt_len] = req.prompt
                lens[r] = req.prompt_len
            ops = (jnp.asarray(ids), jnp.asarray(lens))
        with _watchdog.section("serving.prefill"):
            with self._span("serve:dispatch", "embed") as d:
                pooled = self._jembed(self._embed_params, *ops)
            with self._span("serve:fetch", "embed") as f:
                pooled = np.asarray(pooled)
        with self._span("serve:commit", "embed"):
            self._record_dispatch("prefill", d.t0, f.t1)
            now = time.time()
            for r, req in enumerate(grp):
                req.embedding = pooled[r]
                req.first_token_t = now
                self._stats["embeds"] += 1
                self._sched.finish(req)

    def _first_tokens(self, logits, group, Bb: int) -> np.ndarray:
        """Sample each admitted request's FIRST token (sample index 0)
        from its prefill logits. All-greedy waves take the literal host
        argmax (the v1 path, bitwise); a wave with any sampling row runs
        the compiled per-row sampler — greedy rows inside it still argmax
        through sample_tokens' where-select."""
        if all(r.temperature == 0.0 for r in group):
            return np.argmax(np.asarray(logits), axis=-1)
        import jax.numpy as jnp

        from ...models.generation import seed_key
        keys = np.zeros((Bb, 2), np.uint32)
        temp = np.zeros((Bb,), np.float32)
        topk = np.zeros((Bb,), np.int32)
        topp = np.ones((Bb,), np.float32)
        for r, req in enumerate(group):
            keys[r] = seed_key(req.seed)
            temp[r] = req.temperature
            topk[r] = req.top_k if req.top_k is not None else 0
            topp[r] = req.top_p if req.top_p is not None else 1.0
        return np.asarray(self._jsample(
            logits, jnp.asarray(keys), jnp.zeros((Bb,), jnp.int32),
            jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(topp)))

    # ---- decode dispatch sizing -------------------------------------------

    def _limit(self, decoding, max_iters: Optional[int]) -> int:
        """Iterations for the next decode dispatch (no row is mid-prefill
        here: a step with one dispatches the mixed step instead). Queue
        waiting: run to the FIRST budget retirement (admit with zero idle
        iterations). Queue empty: drain the whole tail in one dispatch
        (the in-graph alive-mask exit handles rows finishing early).
        ``decode_chunk`` caps when a live row can retire EARLIER than its
        budget (EOS enabled) so admission latency stays bounded;
        ``max_iters`` when the caller asked for streaming granularity."""
        sl = [int(self._steps_left[r.slot]) for r in decoding]
        n = min(sl) if self._sched.queue else max(sl)
        if max_iters is None and any(r.eos_token_id is not None
                                     for r in decoding):
            max_iters = self.config.decode_chunk
        if max_iters is not None:
            n = min(n, int(max_iters))
        return max(1, min(n, self._out_width))

    def _ensure_blocks(self, want: int) -> int:
        """Make the pool cover ``want`` decode iterations for every
        decoding slot — each needs blocks for ``seq_len + min(want,
        steps_left)`` KV entries. Returns the feasible iteration count
        (shrunk to what the pool can back), PREEMPTING the newest-admitted
        live request (never the oldest — that's the no-livelock proof)
        whenever even one iteration doesn't fit. If the sole survivor
        still can't get a block the pool is truly exhausted relative to
        its budget: it is retired early with ``oom_truncated`` set rather
        than hung."""
        bf = self.cache.blocks_for

        while True:
            decoding = self._sched.decoding
            if not decoding:
                return 0

            def need(k: int) -> int:
                tot = 0
                for r in decoding:
                    e = int(self._seq_lens[r.slot]) + \
                        min(k, int(self._steps_left[r.slot]))
                    tot += max(0, bf(e) - len(r.blocks))
                return tot

            avail = self.cache.free_blocks
            if need(1) <= avail:
                lo, hi = 1, max(1, want)
                while lo < hi:                    # largest feasible k
                    mid = (lo + hi + 1) // 2
                    if need(mid) <= avail:
                        lo = mid
                    else:
                        hi = mid - 1
                for r in decoding:
                    e = int(self._seq_lens[r.slot]) + \
                        min(lo, int(self._steps_left[r.slot]))
                    if self.cache.extend(r.slot, r.blocks, e) is None:
                        break                     # raced an estimate; retry
                else:
                    return lo
                continue
            if not self._relieve_pressure(decoding):
                return 0

    def _relieve_pressure(self, decoding: List[Request]) -> bool:
        """The pool can't cover even the minimal next dispatch: preempt
        the newest-admitted live request (never the oldest — the
        no-livelock proof) and return True so the caller replans; with
        nothing left to preempt the sole survivor's budget exceeds the
        whole pool — truncate it (retire with the tokens it has, never
        hang the drain loop) and return False. The ONE preempt/truncate
        ladder the decode and spec block planners share."""
        victim = self._sched.preempt_victim()
        if victim is not None:
            self._preempt(victim)
            return True
        r = decoding[0]
        r.oom_truncated = True
        self._sched.oom_truncated += 1
        self._done[r.slot] = True
        return False

    def _preempt(self, req: Request) -> None:
        m = req.slot
        self._sched.preempt(req)
        self._clear_slot(m)

    # ---- speculative decoding (ISSUE 11) ----------------------------------

    def _ctx_at(self, req: Request, i: int) -> int:
        """Token backing context position ``i`` (prompt, then generated)
        without materializing the concatenation."""
        pl = req.prompt_len
        return int(req.prompt[i]) if i < pl else int(req.tokens[i - pl])

    def _draft_tokens(self, req: Request) -> List[int]:
        """n-gram prompt-lookup drafting (no second model): when the last
        ``spec_ngram`` tokens of the request's context (prompt +
        generated) reoccur earlier, propose the continuation of the most
        recent PRIOR occurrence — preferring one with a full
        ``spec_decode`` window of continuation. Capped so the verify can
        never emit past the token budget (``draft <= steps_left - 1``:
        emission is ``accepted + 1``). Returns [] when nothing matches —
        the step then falls through to the plain decode dispatch.

        An incremental per-request n-gram presence index (O(1) amortized
        per generated token) gates the scan: when the trailing n-gram
        has never occurred before, the miss costs O(ngram), not
        O(context) — so incoherent/long-context traffic pays nothing per
        step. The full O(context) occurrence scan (which preserves the
        exact most-recent/full-window selection) only runs when a draft
        WILL be proposed — steps where a verify dispatch is about to pay
        for itself anyway."""
        k = min(self._spec_k, int(self._steps_left[req.slot]) - 1)
        if k < 1:
            return []
        n = self._spec_n
        L = req.prompt_len + len(req.tokens)
        if L <= n:
            return []
        st = req.spec_index
        if st is None:
            st = req.spec_index = {"end": n - 1, "seen": set()}
        # index every n-gram ENDING at positions (st["end"], L-1] — one
        # tuple per newly appended token since the last call
        for e in range(st["end"] + 1, L):
            st["seen"].add(tuple(self._ctx_at(req, e - n + j)
                                 for j in range(n)))
        st["end"] = L - 1
        tail = tuple(self._ctx_at(req, L - n + j) for j in range(n))
        if tail not in st["seen"]:
            return []
        ctx = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        pat = ctx[-n:]
        win = np.lib.stride_tricks.sliding_window_view(ctx[:-1], n)
        hits = np.nonzero((win == pat).all(axis=1))[0]
        if not hits.size:                  # unreachable given the index;
            return []                      # kept as a safety net
        # prefer the most recent occurrence with k tokens of continuation
        # inside the context; fall back to the most recent one at all
        full = hits[hits + n + k <= len(ctx)]
        j = int(full[-1]) if full.size else int(hits[-1])
        return [int(t) for t in ctx[j + n:j + n + k]]

    def _ensure_blocks_spec(self, drafts: Dict[int, List[int]]
                            ) -> List[Request]:
        """Block planning for one verify dispatch: every decoding slot
        needs blocks covering ``seq_len + draft_len + 1`` KV entries (the
        verify writes the last token's KV plus one per draft). When the
        pool can't cover the drafts they are DROPPED first — the caller
        then falls through to the plain decode loop, which batches
        iterations far cheaper than a pad-lane verify would — before any
        preemption; the preempt/truncate ladder is the shared
        :meth:`_relieve_pressure`. Returns the decoding set (possibly
        shrunk by preemption; empty = nothing to do)."""
        bf = self.cache.blocks_for

        while True:
            decoding = self._sched.decoding
            if not decoding:
                return []

            def need(with_drafts: bool) -> int:
                tot = 0
                for r in decoding:
                    dl = len(drafts.get(r.rid, ())) if with_drafts else 0
                    e = int(self._seq_lens[r.slot]) + dl + 1
                    tot += max(0, bf(e) - len(r.blocks))
                return tot

            avail = self.cache.free_blocks
            if need(True) <= avail:
                with_drafts = True
            elif need(False) <= avail:
                with_drafts = False
                drafts.clear()         # pool-pressure fallback: no drafts
            elif self._relieve_pressure(decoding):
                continue
            else:
                return []
            for r in decoding:
                dl = len(drafts.get(r.rid, ())) if with_drafts else 0
                e = int(self._seq_lens[r.slot]) + dl + 1
                if self.cache.extend(r.slot, r.blocks, e) is None:
                    break                     # raced an estimate; retry
            else:
                return decoding

    def _rollback_blocks(self, req: Request) -> None:
        """Free the surplus blocks a verify's REJECTED tail left behind:
        after acceptance the slot's committed KV spans ``seq_len``
        entries, so any block past ``blocks_for(seq_len)`` holds only
        stale draft KV — it returns to the ref-counted manager through
        the same free path preemption uses (never a registered block:
        registration stops at the last committed full block). The stale
        entries INSIDE the kept tail block are overwritten by the next
        dispatch's write at ``seq_len`` or hidden by the ``j <= seq_len``
        mask."""
        keep = self.cache.blocks_for(int(self._seq_lens[req.slot]))
        tail = req.blocks[keep:]
        if not tail:
            return
        self.cache.manager.free(tail)
        del req.blocks[keep:]
        self.cache.tables[req.slot, keep:] = 0

    def _spec_dispatch(self, decoding: List[Request],
                       drafts: Dict[int, List[int]],
                       emitted: Dict[int, List[int]]) -> None:
        """One speculative verify: build the ``[M, Q]`` token matrix
        (last token + drafts, pad lanes repeat the last token), dispatch
        the compiled verify program, then commit ``accepted + 1`` tokens
        per slot (EOS truncates), advance the sampling cursor, register
        freshly-filled prefix blocks and roll back the rejected tail's
        surplus blocks."""
        import jax.numpy as jnp
        with self._span("serve:operands", "spec"):
            Q = self._spec_k + 1
            M = self.config.max_slots
            toks = np.zeros((M, Q), np.int32)
            dl = np.zeros((M,), np.int32)
            for req in decoding:
                m = req.slot
                d = drafts.get(req.rid, [])
                toks[m, 0] = self._tokens[m]
                toks[m, 1:1 + len(d)] = d
                toks[m, 1 + len(d):] = self._tokens[m]   # pad: a real token
                dl[m] = len(d)
            ops = (jnp.asarray(toks),
                   jnp.asarray(self._seq_lens), jnp.asarray(dl),
                   jnp.asarray(self._steps_left), jnp.asarray(self._done),
                   jnp.asarray(self.cache.tables), jnp.asarray(self._keys),
                   jnp.asarray(self._sample_idx), jnp.asarray(self._temp),
                   jnp.asarray(self._topk), jnp.asarray(self._topp),
                   *self._lora_operand(self._adapters))
        with _watchdog.section("serving.decode"):
            with self._span("serve:dispatch", "spec") as d:
                self.cache.pool, cand, acc, *aux = self._jspec(
                    self._params, self.cache.pool, *ops)
                del ops
            with self._span("serve:fetch", "spec") as f:
                cand = np.asarray(cand)
                acc = np.asarray(acc)
                self._count_dispatch(*aux)
        with self._span("serve:commit", "spec"):
            self._record_dispatch("spec", d.t0, f.t1)
            for req in decoding:
                m = req.slot
                if self._done[m] or self._steps_left[m] <= 0:
                    continue
                got = [int(t) for t in cand[m, :int(acc[m]) + 1]]
                eos = req.eos_token_id
                if eos is not None and eos in got:
                    got = got[:got.index(eos) + 1]
                    self._done[m] = True
                    req.eos_seen = True
                e = len(got)
                req.tokens.extend(got)
                emitted.setdefault(req.rid, []).extend(got)
                req.spec_drafted += int(dl[m])
                req.spec_accepted += e - 1
                self._sched.spec_drafted += int(dl[m])
                self._sched.spec_accepted += e - 1
                self._tokens[m] = got[-1]
                self._seq_lens[m] += e
                self._steps_left[m] -= e
                self._sample_idx[m] = len(req.tokens)
                self._register_filled(req)
                if not req.finished:
                    self._rollback_blocks(req)
            self._stats["spec_steps"] += 1
            self._sched.retire_finished()

    # ---- mixed batching (ISSUE 20) ----------------------------------------

    def _mixed_dispatch(self, prefills: List[Request],
                        include_decode: bool,
                        emitted: Dict[int, List[int]]) -> None:
        """ONE mixed prefill+decode dispatch: every mid-prefill slot
        contributes its next chunk as a ``q_len > 1`` row (KV scattered
        from its per-row ``num_computed`` start), every decoding slot a
        ``q_len == 1`` row that samples its next token — per-row
        ``start``/``q_len`` are DEVICE operands of one executable per Q
        bucket, so role churn never retraces. A chunk that COMPLETES its
        prompt samples the first token in this same dispatch (TTFT no
        longer waits for the next step's decode); incomplete chunks and
        readmission recomputes discard their sampled lane."""
        import jax.numpy as jnp

        from ...models.generation import seed_key
        with self._span("serve:operands", "mixed"):
            chunk = self.config.prefill_chunk
            M = self.config.max_slots
            decode_rows = [r for r in self._sched.decoding
                           if include_decode and not self._done[r.slot]
                           and self._steps_left[r.slot] > 0]
            plan: List[Tuple[Request, int]] = []
            qmax = 1
            for req in prefills:
                n = len(req.prefill_ids) - req.num_computed
                if chunk is not None:
                    n = min(n, chunk)
                plan.append((req, n))
                qmax = max(qmax, n)
            Q = self._bucket(qmax)
            toks = np.zeros((M, Q), np.int32)
            starts = np.zeros((M,), np.int32)
            qlens = np.ones((M,), np.int32)       # pad rows: harmless q=1
            active = np.zeros((M,), bool)
            keys = np.zeros((M, 2), np.uint32)
            sidx = np.zeros((M,), np.int32)
            temp = np.zeros((M,), np.float32)
            topk = np.zeros((M,), np.int32)
            topp = np.ones((M,), np.float32)
            adapters = np.array(self._adapters)
            for r in decode_rows:
                m = r.slot
                toks[m, :] = self._tokens[m]      # pad lanes: a real token
                starts[m] = self._seq_lens[m]
                active[m] = True
                keys[m] = self._keys[m]
                sidx[m] = self._sample_idx[m]
                temp[m] = self._temp[m]
                topk[m] = self._topk[m]
                topp[m] = self._topp[m]
            for req, n in plan:
                m = req.slot
                ids = req.prefill_ids[req.num_computed:req.num_computed + n]
                toks[m, :n] = ids
                toks[m, n:] = ids[-1]             # pad lanes: a real token
                starts[m] = req.num_computed
                qlens[m] = n
                active[m] = True
                # the completing chunk's sampled lane IS the prompt's
                # first token: the same (seed, index 0) key
                # _first_tokens uses
                keys[m] = seed_key(req.seed)
                sidx[m] = 0
                temp[m] = req.temperature
                topk[m] = req.top_k if req.top_k is not None else 0
                topp[m] = req.top_p if req.top_p is not None else 1.0
                adapters[m] = req.adapter_slot
            ops = (jnp.asarray(toks),
                   jnp.asarray(starts), jnp.asarray(qlens),
                   jnp.asarray(active), jnp.asarray(self.cache.tables),
                   jnp.asarray(keys), jnp.asarray(sidx), jnp.asarray(temp),
                   jnp.asarray(topk), jnp.asarray(topp),
                   *self._lora_operand(adapters))
        with _watchdog.section("serving.decode"):
            with self._span("serve:dispatch", "mixed") as d:
                self.cache.pool, nxt, *aux = self._jmixed(
                    self._params, self.cache.pool, *ops)
                del ops
            with self._span("serve:fetch", "mixed") as f:
                nxt = np.asarray(nxt)
                counts = self._count_dispatch(*aux)
        with self._span("serve:commit", "mixed"):
            self._record_dispatch("mixed", d.t0, f.t1)
            self.spans.count("prefill_tokens", sum(n for _, n in plan))
            self.spans.count("decode_tokens", len(decode_rows))
            # query lanes that carried a real token against the lanes the
            # step computed: the family's own count where it reports one
            # (a packed step's waves), the M x Q handed over otherwise
            self.spans.count("mixed_lanes_real", int(qlens[active].sum()))
            self.spans.count("mixed_lanes_total",
                             counts.get("lanes_computed", M * Q))
            # rows that take the paged kernel's short query tile (one
            # query position), of the rows it runs
            self.spans.count("attn_rows_short",
                             int((qlens[active] == 1).sum()))
            self.spans.count("attn_rows", int(active.sum()))
            now = time.time()
            # prefill rows first, then the decode rows' commits
            for req, n in plan:
                m = req.slot
                req.num_computed += n
                req.reg_state = self.cache.register_prefix(
                    req.prefill_ids, req.blocks, req.num_computed,
                    req.reg_state, tenant=req.tenant,
                    namespace=req.adapter_id)
                if req.prefilling:
                    continue                      # more chunks to go
                if req.tokens:                    # readmission: resume
                    self._start_decode(req)
                else:
                    self._emit_first(req, int(nxt[m]), now, emitted)
            # decode rows: exactly one iteration of the decode loop's
            # commit
            for req in decode_rows:
                m = req.slot
                t = int(nxt[m])
                req.tokens.append(t)
                emitted.setdefault(req.rid, []).append(t)
                self._tokens[m] = t
                self._seq_lens[m] += 1
                self._steps_left[m] -= 1
                self._sample_idx[m] = len(req.tokens)
                if req.eos_token_id is not None and t == req.eos_token_id:
                    self._done[m] = True
                    req.eos_seen = True
                self._register_filled(req)
            self._sched.retire_finished()

    def _register_filled(self, req: Request) -> None:
        """Blocks a dispatch just completed become shareable; skip the
        chain-ids build unless a block actually filled (``reg_state``
        makes registration itself incremental)."""
        bs = self.config.block_size
        sl = int(self._seq_lens[req.slot])
        base = req.reg_state[0] * bs
        if self.config.prefix_cache and sl // bs > req.reg_state[0]:
            req.reg_state = self.cache.register_prefix(
                self._chain_ids(req, base, sl), req.blocks, sl,
                req.reg_state, base=base, tenant=req.tenant,
                namespace=req.adapter_id)

    def _decode_dispatch(self, decoding: List[Request], k: int,
                         emitted: Dict[int, List[int]]) -> None:
        """One dispatch of the decode loop over the slot table, up to
        ``k`` iterations (a device scalar: no retrace)."""
        import jax.numpy as jnp
        with self._span("serve:operands", "decode"):
            before = self._steps_left.copy()
            ops = (jnp.asarray(self._tokens),
                   jnp.asarray(self._seq_lens),
                   jnp.asarray(self._steps_left),
                   jnp.asarray(self._done), jnp.asarray(self.cache.tables),
                   jnp.asarray(self._eos), jnp.asarray(k, jnp.int32),
                   jnp.asarray(self._keys), jnp.asarray(self._sample_idx),
                   jnp.asarray(self._temp), jnp.asarray(self._topk),
                   jnp.asarray(self._topp),
                   *self._lora_operand(self._adapters))
        with _watchdog.section("serving.decode"):
            with self._span("serve:dispatch", "decode") as d:
                out = self._jdecode(self._params, self.cache.pool, *ops)
                del ops      # released here, inside the span that used them
            with self._span("serve:fetch", "decode") as f:
                self.cache.pool = out[0]
                tokens, seq_lens, steps_left, done, toks = (
                    np.asarray(x) for x in out[1:6])
                self._count_dispatch(*out[6:])
                del out
        with self._span("serve:commit", "decode"):
            self._record_dispatch("decode", d.t0, f.t1)
            # np.array (copy): zero-copy views of jax outputs are
            # read-only, and admission writes these slots in place next
            # step
            self._tokens = np.array(tokens)
            self._seq_lens = np.array(seq_lens)
            self._steps_left = np.array(steps_left)
            self._done = np.array(done)
            # iterations the loop actually ran (it exits early once every
            # live row is done): wall time per iteration is one-valued
            # where wall time per dispatch is not
            self.spans.count("decode_iterations",
                             int((before - self._steps_left).max()))
            self.spans.count("decode_tokens",
                             int((before - self._steps_left).sum()))
            for req in decoding:
                m = req.slot
                n = int(before[m] - self._steps_left[m])
                if n <= 0:
                    continue
                got = toks[m, :n].tolist()
                req.tokens.extend(got)
                self._sample_idx[m] = len(req.tokens)
                if bool(self._done[m]):
                    req.eos_seen = True
                emitted.setdefault(req.rid, []).extend(got)
                self._register_filled(req)
            self._sched.retire_finished()

    def _plan_dispatch(self, max_iters: Optional[int]
                       ) -> Tuple[Optional[str], tuple]:
        """Which ONE decode-side dispatch this step makes, with its block
        planning done: ``("spec", (decoding, drafts))``, ``("mixed",
        (prefills, include_decode))``, ``("decode", (decoding, k))`` or
        ``(None, ())``."""
        decoding = self._sched.decoding
        if decoding and self._spec_k:
            # speculative path: draft by prompt lookup; with at least one
            # draft the step runs ONE multi-query verify dispatch instead
            # of the decode loop (draft-less slots ride it as a plain
            # single step). No draft anywhere — none found, or the block
            # planner DROPPED them under pool pressure — falls through to
            # the decode loop: a verify with all-pad lanes would pay
            # ~Q x the FLOPs of a decode iteration to emit one token per
            # slot, while the loop batches many iterations per dispatch.
            drafts = {r.rid: self._draft_tokens(r) for r in decoding}
            if any(drafts.values()):
                decoding = self._ensure_blocks_spec(drafts)
                if decoding and any(drafts.values()):
                    return "spec", (decoding, drafts)
            decoding = self._sched.decoding
        if any(r.prefilling for r in self._sched.live):
            # mixed batching (ISSUE 20): every mid-prefill slot's chunk
            # rides the decode dispatch as a q_len > 1 row of ONE mixed
            # step, and decoding slots advance in the SAME step a new
            # prompt prefills. Precedence: a step with spec drafts
            # dispatched verify above and never reaches here. Block
            # planning is the decode planner's (_ensure_blocks for the
            # decode rows' one iteration; a preemption inside it may
            # shrink either role set, so both are re-read after).
            kd = self._ensure_blocks(1) if decoding else 0
            prefills = [r for r in self._sched.live if r.prefilling]
            if prefills:
                if self._lane_budget is not None:
                    prefills = self._within_lane_budget(
                        prefills,
                        len(self._sched.decoding) if kd >= 1 else 0)
                return "mixed", (prefills, kd >= 1)
            decoding = self._sched.decoding
        k = 0
        if decoding:
            want = self._limit(decoding, max_iters)
            k = self._ensure_blocks(want)
            decoding = self._sched.decoding       # preemption may shrink it
            if decoding and k >= 1:
                # an in-call preemption re-queued its victim, flipping the
                # sizing policy from drain-the-tail to first-retirement;
                # re-derive the cap so the victim isn't stalled for the
                # survivors' whole remaining budget (no-op otherwise)
                k = min(k, self._limit(decoding, max_iters))
        if decoding and k >= 1:
            return "decode", (decoding, k)
        return None, ()

    def _within_lane_budget(self, prefills: List[Request],
                            decode_rows: int) -> List[Request]:
        """The prompts whose next chunk rides this mixed step where the
        family keeps a step to a number of query lanes: oldest request
        first (a slot's index says nothing of its age, and a closed loop
        refills the low slots for ever), as many as fit beside the decode
        rows, and at least one. The others keep their slot and their
        blocks and wait a step."""
        chunk = self.config.prefill_chunk
        left = self._lane_budget - decode_rows
        kept: List[Request] = []
        for req in sorted(prefills, key=lambda r: r.rid):
            n = len(req.prefill_ids) - req.num_computed
            if chunk is not None:
                n = min(n, chunk)
            if kept and n > left:
                break
            kept.append(req)
            left -= n
        return kept

    # ---- the scheduler iteration ------------------------------------------

    def step(self, max_iters: Optional[int] = None) -> Dict[int, List[int]]:
        """One scheduler iteration: expire deadlines -> retire -> admit
        (+ batched prefill) -> extend/preempt for blocks -> ONE decode-side
        dispatch: a spec verify, a mixed step carrying every mid-prefill
        slot's next chunk beside the decoding rows, or the decode loop of
        up to ``_limit()`` iterations (``max_iters`` caps it). Returns
        ``{rid: [tokens emitted]}``.
        Each step stamps the global :mod:`~paddle_tpu.health.watchdog`
        (progress tick + ``serving.step``/``serving.prefill``/
        ``serving.decode`` section markers), so a frozen dispatch is
        named in the hang diagnosis exactly like a training section.

        The step's wall time is covered by flat, disjoint ``serve:*``
        spans (``plan`` / ``operands`` / ``dispatch`` / ``fetch`` /
        ``commit`` / ``journal``; none encloses another, so the one
        covering a device idle gap names its cause), each carrying
        ``step=`` and, at a dispatch site, ``kind=``."""
        _watchdog.touch()
        with self._lock, _watchdog.section("serving.step"):
            self.step_no += 1
            emitted = self._step(max_iters)
            with self._span("serve:journal"):
                self._lora_sweep()
                self._journal_step(emitted)
            return emitted

    def _step(self, max_iters: Optional[int]) -> Dict[int, List[int]]:
        emitted: Dict[int, List[int]] = {}
        with self._span("serve:plan"):
            self._expire_deadlines(time.time())
            self._sched.retire_finished()
            embeds = self._plan_embeds()
            waves = self._plan_admissions()
        for Sb, group in embeds:
            self._embed_dispatch(Sb, group)
        for Sb, group in waves:
            self._prefill_dispatch(Sb, group, emitted)
        with self._span("serve:plan"):
            kind, args = self._plan_dispatch(max_iters)
        if kind == "spec":
            self._spec_dispatch(*args, emitted)
        elif kind == "mixed":
            self._mixed_dispatch(*args, emitted)
        elif kind == "decode":
            self._decode_dispatch(*args, emitted)
        self._stats["steps"] += 1
        return emitted

    def stream(self, finish_events: bool = False
               ) -> Iterator[Tuple[int, Any]]:
        """Drain the engine, yielding ``(rid, token)`` events in emission
        order (within a step, by request id). Dispatches are capped at
        ``decode_chunk`` iterations so events surface with bounded latency
        instead of arriving in one tail-drain burst. With
        ``finish_events=True``, each request's retirement additionally
        yields ``(rid, dict)`` carrying its serving record —
        ``prefix_hit_tokens`` / ``preemptions`` / ``recomputed_tokens`` /
        ``tokens`` / ``ttft_s`` — so a streaming caller observes the
        paging machinery per request, not just in aggregate stats().

        Consumer abandonment: closing the generator (``gen.close()``, a
        ``break`` followed by GC, the SSE client vanishing) CANCELS every
        request still queued or running — their KV blocks return to the
        pool immediately instead of leaking until someone else drains the
        engine. The partial outputs stay readable via :meth:`request`."""
        try:
            while self.pending:
                seen = set(self._sched.finished) if finish_events else None
                for rid, toks in sorted(
                        self.step(self.config.decode_chunk).items()):
                    for t in toks:
                        yield rid, int(t)
                if finish_events:
                    for rid in sorted(r for r in self._sched.finished
                                      if r not in seen):
                        req = self._sched.finished[rid]
                        yield rid, {
                            "finished": True,
                            "state": req.state,
                            "tokens": len(req.tokens),
                            "prefix_hit_tokens": req.prefix_hit_tokens,
                            "preemptions": req.preemptions,
                            "recomputed_tokens": req.recomputed_tokens,
                            "oom_truncated": req.oom_truncated,
                            "ttft_s": req.ttft_s,
                        }
        except GeneratorExit:
            # the consumer walked away mid-stream: nobody will ever pump
            # step() for these requests again through this generator —
            # cancel them so their blocks can't sit pinned in the pool
            self.cancel_all()
            raise

    def run(self, prompts: Sequence, max_new_tokens=None,
            eos_token_id="unset") -> List[np.ndarray]:
        """Submit every prompt, drain, return outputs in submission order.
        ``max_new_tokens`` may be one int or a per-prompt sequence."""
        n = len(prompts)
        mnt = ([max_new_tokens] * n
               if max_new_tokens is None or np.isscalar(max_new_tokens)
               else list(max_new_tokens))
        if len(mnt) != n:
            raise ValueError(f"max_new_tokens has {len(mnt)} entries for "
                             f"{n} prompts")
        rids = [self.submit(p, max_new_tokens=m, eos_token_id=eos_token_id)
                for p, m in zip(prompts, mnt)]
        while self.pending:
            self.step()
        return [self._sched.result(r) for r in rids]

    # ---- introspection ----------------------------------------------------

    @property
    def pending(self) -> bool:
        return self._sched.pending

    def depth(self) -> int:
        """Queued + live request count under the engine lock — the
        router-visible load signal its power-of-two-choices pick
        compares (cheaper than a full health_snapshot per submit)."""
        with self._lock:
            return self._sched.depth

    def request(self, rid: int) -> Request:
        """The finished request record (tokens + latency timestamps +
        prefix-hit/preemption counters)."""
        with self._lock:
            return self._sched.finished[rid]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return self._stats_locked()

    def _stats_locked(self) -> Dict[str, Any]:
        return {**self._stats,
                "prefill_buckets": len(self._prefill_buckets),
                "admitted": self._sched.admitted,
                "retired": self._sched.retired,
                "cancelled": self._sched.cancelled,
                "timed_out": self._sched.timed_out,
                "shed": self._sched.shed,
                "queued": len(self._sched.queue),
                "live_slots": len(self._sched.live),
                "max_slots": self.config.max_slots,
                "policy": self._policy.name,
                "free_blocks": self.cache.free_blocks,
                "prefix_hit_tokens": self._sched.prefix_hit_tokens,
                "preemptions": self._sched.preemptions,
                "recomputed_tokens": self._sched.recomputed_tokens,
                "oom_truncated": self._sched.oom_truncated,
                "cached_blocks": self.cache.manager.cached_blocks,
                "evictions": self.cache.manager.evictions,
                "usable_blocks": self.cache.manager.num_blocks - 1,
                "kv_quant": self.config.kv_quant,
                "paged_kernel": self.config.paged_kernel,
                "spec_decode": self.config.spec_decode,
                "spec_drafted": self._sched.spec_drafted,
                "spec_accepted": self._sched.spec_accepted,
                "tp_degree": self.config.tp,
                "kv_pool_bytes": self.cache.kv_bytes(),
                "kv_pool_shard_bytes": self.cache.kv_bytes(per_shard=True),
                "kv_pool_mb": round(self.cache.kv_bytes() / 2**20, 2),
                "dispatch_latency": self._dispatch_latency(),
                "spans": self.spans.snapshot(),
                "model": self._model,
                "offload": (self.cache.offload.stats()
                            if self.cache.offload is not None else None),
                "lora": (self._lora.stats()
                         if self._lora is not None else None)}

    def health_snapshot(self) -> Dict[str, Any]:
        """One JSON-serializable health/ops record (docs/OPS.md): overall
        readiness, capacity headroom, lifecycle/shed counters, hang-
        watchdog state and per-tenant queue-depth/TTFT/shed breakdowns —
        the payload a ``/healthz`` or metrics endpoint should serve.
        ``ok`` goes False only when the installed hang watchdog has fired
        (the engine itself degrades by shedding, which is healthy);
        ``accepting`` says whether a submit() right now would be queued
        rather than shed. Safe to call from any thread — the whole
        payload is built under the engine lock, so a metrics endpoint
        polling mid-trace never sees a torn mid-step state."""
        with self._lock:
            return self._health_snapshot_locked()

    def block_partition(self) -> Dict[str, int]:
        """A consistent view of the pool partition (free / evictable /
        in-use / usable) under the engine lock — the conservation
        invariant the InvariantAuditor (audit.py) checks every step:
        free + evictable + in_use == usable. With the host offload tier
        attached, ``host``/``host_capacity`` report the host-resident
        side of the two-tier partition (the auditor's ``tier_partition``
        check: a key is device-resident XOR host-resident)."""
        with self._lock:
            bm = self.cache.manager
            tier = self.cache.offload
            return {"free": len(bm._free),
                    "evictable": len(bm._evictable),
                    "in_use": bm.blocks_in_use,
                    "usable": bm.num_blocks - 1,
                    "host": tier.blocks if tier is not None else 0,
                    "host_capacity": tier.capacity
                    if tier is not None else 0}

    def _health_snapshot_locked(self) -> Dict[str, Any]:
        sched = self._sched
        wd = _watchdog.current()

        def pct(xs, q):
            return (round(float(np.percentile(np.asarray(xs), q)), 4)
                    if xs else None)

        snap = self.spans.snapshot()
        # one serve:journal span a step; unlike step_no the aggregator's
        # count survives a supervisor rebuild, as the seconds do
        steps = max(1, snap["spans"].get("serve:journal",
                                         {}).get("count", 0))

        def share(part, whole):
            total = snap["counters"].get(whole, 0)
            return (round(100.0 * snap["counters"][part] / total, 2)
                    if total else None)

        def lanes(kind):
            return share(f"{kind}_lanes_real", f"{kind}_lanes_total")

        def wait(name, q):
            h = snap["histograms"].get(name)
            p = histogram_percentile(h, q) if h else None
            return None if p is None else round(p, 4)

        # tenants past MAX_TENANTS were folded into the overflow record
        # at submit; by_tenant() folds queued/live the same way (or the
        # overflow row would report 0 forever)
        occupancy = sched.by_tenant()
        tenants = {}
        for name, t in sched.tenants.items():
            ttfts = list(t["ttfts"])
            tpots = list(t["tpots"])
            tenants[name] = {
                "queued": occupancy[name]["queued"],
                "live": occupancy[name]["live"],
                "submitted": t["submitted"], "admitted": t["admitted"],
                "retired": t["retired"], "cancelled": t["cancelled"],
                "timed_out": t["timed_out"], "shed": t["shed"],
                "service_tokens": t["service_tokens"],
                "cached_blocks": self.cache.manager.tenant_cached(name),
                "ttft_p50_s": pct(ttfts, 50), "ttft_p99_s": pct(ttfts, 99),
                # TPOT (time per output token): each retirement's mean
                # inter-token decode latency is one sample, so the
                # percentiles track the SLO a streaming client feels
                "tpot_p50_s": pct(tpots, 50), "tpot_p99_s": pct(tpots, 99),
            }
        return {
            "ok": wd is None or not wd.fired.is_set(),
            "accepting": len(sched.queue) < sched.queue_depth,
            "policy": self._policy.name,
            "queued": len(sched.queue),
            "queue_limit": sched.queue_depth,
            "live_slots": len(sched.live),
            "max_slots": self.config.max_slots,
            "free_blocks": self.cache.free_blocks,
            "usable_blocks": self.cache.manager.num_blocks - 1,
            "kv_pool_bytes": self.cache.kv_bytes(),
            "tp_degree": self.config.tp,
            "kv_pool_shard_bytes": self.cache.kv_bytes(per_shard=True),
            "kv_quant": self.config.kv_quant,
            "paged_kernel": self.config.paged_kernel,
            "spec_decode": self.config.spec_decode,
            "retry_after_s": sched.retry_after_s(),
            "counters": {
                "admitted": sched.admitted, "retired": sched.retired,
                "cancelled": sched.cancelled, "timed_out": sched.timed_out,
                "shed": sched.shed, "preemptions": sched.preemptions,
                "oom_truncated": sched.oom_truncated,
                "prefix_hit_tokens": sched.prefix_hit_tokens,
                "evictions": self.cache.manager.evictions,
            },
            "dispatch_latency": self._dispatch_latency(),
            "phase_ms_per_step": {
                name[len("serve:"):]: round(row["seconds"] * 1e3 / steps, 4)
                for name, row in sorted(snap["spans"].items())
                if name.startswith("serve:")},
            "request_wait": {
                f"{short}_p{q}_s": wait(name, q)
                for name, short in (("queue_wait_s", "queue_wait"),
                                    ("prefill_s", "prefill"))
                for q in (50, 99)},
            "real_lane_pct": {"mixed": lanes("mixed"),
                              "prefill": lanes("prefill")},
            "short_row_pct": share("attn_rows_short", "attn_rows"),
            "family": self._family.health(snap["counters"], self._cfg),
            "offload": {
                "enabled": self.cache.offload is not None,
                **(self.cache.offload.stats()
                   if self.cache.offload is not None else
                   {"capacity": 0, "blocks": 0, "swap_outs": 0,
                    "swap_ins": 0, "tier_hits": 0, "tier_misses": 0,
                    "corrupt_drops": 0, "tier_evictions": 0}),
            },
            "lora": {
                "enabled": self._lora is not None,
                **(self._lora.snapshot() if self._lora is not None else
                   {"rank": 0, "slots": 0, "resident": [],
                    "adapters_registered": 0, "adapters_resident": 0,
                    "adapter_loads": 0, "adapter_evictions": 0,
                    "adapter_pins": 0}),
            },
            "watchdog": {
                "installed": wd is not None,
                "fired": bool(wd.fired.is_set()) if wd is not None else False,
                "timeout_s": wd.timeout if wd is not None else None,
            },
            "tenants": tenants,
        }
