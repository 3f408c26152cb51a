"""Continuous-batching scheduler — iteration-level request lifecycle.

Orca-style scheduling recast as pure host logic: a FIFO admission queue
feeding a fixed table of ``max_slots`` decode slots. Every engine step (1)
RETIRES slots whose request finished (EOS sampled or token budget spent),
returning their KV blocks to the pool, (2) ADMITS queued requests into free
slots while the block pool covers their PROMPT (on-demand allocation —
decode extends block by block as the sequence grows), and (3) hands the
engine the live slots for prefill-chunk and decode dispatches. When the
pool runs dry mid-decode the engine PREEMPTS the newest-admitted running
sequence (:meth:`Scheduler.preempt`): its blocks return to the pool, its
generated-so-far tokens are kept, and it re-queues at the FRONT for
recompute-on-readmission. The OLDEST running sequence is never preempted,
so at least one request always progresses — no livelock. The scheduler
never touches the device — the engine owns dispatch; this module owns WHO
is running WHERE and the per-request records (tokens, timestamps, prefix
hits, preemptions) the bench's stats come from.

Admission ORDER is a pluggable :class:`~.policies.AdmissionPolicy`
(FIFO default — strict submission order; priority / weighted fair share /
earliest-deadline-first ship alongside), and with reservation gone a large
queue head no longer charges its worst case up front — it admits on its
prompt footprint alone, and chunked prefill (engine-side) keeps a long
prompt from freezing in-flight decode streams.

Lifecycle (ISSUE 6): every request ends in exactly ONE terminal state —

    queued -> running -> FINISHED   (EOS / budget spent / oom-truncated)
                      -> CANCELLED  (engine.cancel / abandoned stream)
                      -> TIMED_OUT  (deadline passed after it started)
           ->          SHED         (deadline passed while queued, or the
                                     bounded queue refused the submit)

Terminal transitions release every block the request held (mid-flight via
the same free path preemption uses — free and do NOT requeue), so a stuck
or vanished consumer can never pin pool blocks, and the terminal record
(tokens so far, timestamps, counters) lands in ``finished`` like a normal
retirement. Per-tenant counters (queue depth, TTFT samples, shed/cancel/
timeout counts, service tokens) feed the engine's ``health_snapshot()``
and the fair-share policy.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from ...flags import flag
from .policies import AdmissionPolicy, FIFOPolicy

__all__ = ["Request", "Scheduler", "ServingQueueFull",
           "completes_by_tokens",
           "QUEUED", "RUNNING", "FINISHED", "CANCELLED", "TIMED_OUT",
           "SHED", "TERMINAL_STATES"]


def completes_by_tokens(tokens, max_new_tokens: int,
                        eos_token_id: Optional[int]) -> bool:
    """Whether an already-delivered token list alone completes a request
    (budget spent, or EOS delivered last) — the ONE completion test the
    supervisor's and the router's recovery records share, so their views
    of "record it, don't re-run it" can never diverge."""
    if len(tokens) >= max_new_tokens:
        return True
    return (eos_token_id is not None and bool(tokens)
            and tokens[-1] == eos_token_id)

# request lifecycle states (Request.state)
QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"
CANCELLED = "cancelled"
TIMED_OUT = "timed_out"
SHED = "shed"
TERMINAL_STATES = frozenset({FINISHED, CANCELLED, TIMED_OUT, SHED})

DEFAULT_TENANT = "default"


class ServingQueueFull(RuntimeError):
    """submit() beyond the admission queue's depth bound — the engine is
    LOAD SHEDDING instead of queueing unboundedly. Structured context for
    the caller's backoff logic (a 429/Retry-After response, a client-side
    retry budget):

    * ``queue_depth`` — requests queued when the submit was refused
    * ``live_slots`` — decode slots currently occupied
    * ``retry_after_s`` — suggested backoff: the scheduler's estimate of
      one retirement interval; before two retirements have been observed
      (cold start — nothing to estimate from) it is the conservative
      ``FLAGS_serving_retry_after_s`` default, never None/0
    """

    def __init__(self, message: str, queue_depth: Optional[int] = None,
                 live_slots: Optional[int] = None,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.live_slots = live_slots
        self.retry_after_s = retry_after_s


@dataclasses.dataclass
class Request:
    """One generation request and its serving-side record."""

    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    # sampling knobs (ISSUE 11), RESOLVED through GenerationConfig at
    # submit: temperature 0 = greedy argmax (bit-identical to the v1
    # engine); top_k/top_p None = disabled; seed derives the per-request
    # PRNG base key — the token at sample index t is drawn with
    # fold_in(seed_key(seed), t), a pure function of (request, seed, t),
    # so sampled streams reproduce exactly across preemption-recompute,
    # supervisor crash-resubmit, cross-replica failover AND speculative
    # verify
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    # multi-tenancy + lifecycle (ISSUE 6): the tenant key scopes fair-share
    # accounting and cache quotas; priority orders the priority policy;
    # deadline is ABSOLUTE (time.time()) — engine.submit derives it from
    # timeout_s/deadline_s; state walks queued -> running -> one terminal
    tenant: str = DEFAULT_TENANT
    priority: int = 0
    deadline: Optional[float] = None
    state: str = QUEUED
    # when the front line first saw the request (the server stamps it on
    # its event loop, before the command queue; None -> submit_t) and
    # when it was first admitted to a slot: queue wait = admit_t -
    # enqueue_t, prefill = first_token_t - admit_t. A supervisor
    # resubmission keeps the first enqueue_t.
    enqueue_t: Optional[float] = None
    admit_t: Optional[float] = None
    submit_t: float = 0.0
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    eos_seen: bool = False
    blocks: Optional[List[int]] = None
    slot: Optional[int] = None
    # prefill progress: KV entries mapped-or-written so far (cache hits
    # count — their KV already exists). prefilling == num_computed short of
    # the full prefill set; the slot joins decode when they meet.
    num_computed: int = 0
    prefill_ids: Optional[np.ndarray] = None   # tokens prefill must cover
    admit_seq: int = -1                # admission order (newest = preempt
    #                                    victim; re-admission re-stamps)
    # incremental prefix-registration cursor: (full blocks registered,
    # chained key of the last one) — PagedKVCache.register_prefix state
    reg_state: Tuple[int, Optional[int]] = (0, None)
    # observability counters (engine stats() aggregates these)
    prefix_hit_tokens: int = 0
    preemptions: int = 0
    recomputed_tokens: int = 0
    spec_drafted: int = 0              # draft tokens verified for this
    spec_accepted: int = 0             # ... and how many were emitted
    # incremental n-gram presence index for the prompt-lookup drafter
    # (engine-owned; see ServingEngine._draft_tokens): {"end": positions
    # indexed so far, "seen": n-gram tuples ending before the context
    # end}. Survives preemption (the context it indexes — prompt +
    # kept tokens — never shrinks); a crash resubmission starts a fresh
    # Request and rebuilds it lazily.
    spec_index: Optional[Dict] = None
    computed_hwm: int = 0              # most KV entries ever written; caps
    #                                    the recompute charge on readmission
    #                                    (a mid-prefill preemption only
    #                                    repeats what it had finished)
    oom_truncated: bool = False        # pool exhausted with nothing left to
    #                                    preempt: retired early, output kept
    # durable serving (ISSUE 18): the journal record this request owns
    # (-1 = unjournaled). Ownership moves with the request across
    # migration / handoff / hedge resolution — the vacated copy is
    # DISOWNED before its cancel so the record stays live.
    jid: int = -1
    # multi-adapter LoRA (ISSUE 19): the adapter this request decodes
    # under (None = base traffic) and the device pool slot the engine's
    # admission gate pinned for it (0 = the zeroed base adapter). The
    # pin — and with it the slot — survives preemption: a readmission
    # must find the SAME weights resident, so the adapter releases only
    # at a terminal state.
    adapter_id: Optional[str] = None
    adapter_slot: int = 0
    # embeddings endpoint (ISSUE 19): kind "embed" requests are
    # prefill-only — they retire at prefill completion with the pooled
    # hidden states in ``embedding`` and never occupy a decode slot or
    # KV blocks (see Scheduler.admit_embeds)
    kind: str = "generate"
    embedding: Optional[np.ndarray] = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def kv_tokens(self) -> int:
        """Worst-case KV entries: the prompt plus every generated token's
        KV except the last sampled token (its KV is never written)."""
        return self.prompt_len + self.max_new_tokens - 1

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.tokens)

    @property
    def finished(self) -> bool:
        if self.kind == "embed":
            return self.embedding is not None
        return self.eos_seen or self.remaining <= 0 or self.oom_truncated

    @property
    def terminal(self) -> bool:
        return self.state in TERMINAL_STATES

    @property
    def prefilling(self) -> bool:
        return self.prefill_ids is not None and \
            self.num_computed < len(self.prefill_ids)

    def build_prefill_ids(self) -> np.ndarray:
        """The token ids prefill must compute KV for: the prompt, plus —
        after a preemption — every generated token except the last (whose
        KV the first decode step writes). Greedy determinism makes the
        recomputed KV bit-identical to what was freed."""
        if self.tokens:
            return np.concatenate(
                [self.prompt, np.asarray(self.tokens[:-1], np.int32)])
        return self.prompt

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def tok_latency_s(self) -> Optional[float]:
        """Mean decode latency per token after the first — the request's
        TPOT sample. None for 1-token requests and for crash-recovered
        resubmissions (their first token predates this engine, so no
        ``first_token_t`` exists to measure from)."""
        if self.finish_t is None or self.first_token_t is None \
                or len(self.tokens) < 2:
            return None
        return (self.finish_t - self.first_token_t) / (len(self.tokens) - 1)

    def output(self) -> np.ndarray:
        return np.asarray(self.tokens, np.int32)


class Scheduler:
    """Policy-ordered admission queue + slot table over a
    :class:`PagedKVCache`.

    ``preempt=True`` (the default) is the on-demand mode: admission maps
    prefix-cache hits and allocates only the prompt's remaining blocks;
    ``preempt=False`` restores the legacy worst-case reservation (no
    preemption machinery needed, conservative admission). ``policy`` is
    an :class:`~.policies.AdmissionPolicy` (default FIFO) choosing which
    queued request admits next.
    """

    # hostile traffic can mint a new tenant string per request; past this
    # many distinct tenants new ones aggregate under one overflow key so
    # the stats dict cannot grow without bound
    MAX_TENANTS = 256
    _OVERFLOW_TENANT = "_overflow"
    # TTFT samples retained per tenant for the health snapshot's p50/p99
    TTFT_SAMPLES = 128

    def __init__(self, cache, max_slots: int, queue_depth: int,
                 preempt: bool = True,
                 policy: Optional[AdmissionPolicy] = None):
        self.cache = cache
        self.max_slots = int(max_slots)
        self.queue_depth = int(queue_depth)
        self.preempt_enabled = bool(preempt)
        self.policy = policy if policy is not None else FIFOPolicy()
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_slots
        # finished-record retention is BOUNDED (a long-lived engine must
        # not leak every prompt it ever served): insertion-ordered dict,
        # oldest evicted past queue_depth + 2*max_slots — the most
        # requests that can be in flight at once (a supervisor crash
        # resubmission bypasses the queue bound by up to max_slots, plus
        # the slots themselves), so one mass termination (drain
        # cancel_all) can never evict a record before the supervisor's
        # sweep collects it, and one full run()/drain cycle can always
        # collect its results afterwards. Terminal records
        # (cancelled/timed-out/shed) land here too.
        self.finished: Dict[int, Request] = {}
        self.keep_finished = self.queue_depth + 2 * self.max_slots
        self._next_rid = 0
        self._admit_seq = 0
        self.admitted = 0
        self.retired = 0
        self.preemptions = 0
        self.prefix_hit_tokens = 0
        self.recomputed_tokens = 0
        self.oom_truncated = 0
        # speculative-decoding totals (ISSUE 11): drafts verified vs
        # drafts emitted — the live acceptance-rate signal
        self.spec_drafted = 0
        self.spec_accepted = 0
        # lifecycle counters (terminal states other than FINISHED)
        self.cancelled = 0
        self.timed_out = 0
        self.shed = 0
        # live requests carrying a deadline — the engine skips the
        # per-step expiry sweep entirely while this is 0
        self.deadline_requests = 0
        # recent retirement timestamps -> the retry-after estimate; the
        # conservative default covers the cold-start window before two
        # retirements exist to measure an interval from
        self._finish_times: Deque[float] = deque(maxlen=16)
        self.default_retry_after_s = float(
            flag("FLAGS_serving_retry_after_s", 1.0))
        # absolute time the active drain completes (stamped by the
        # supervisor's request_drain/drain): while set and in the future,
        # retry_after_s() reports the drain-deadline REMAINDER — a client
        # shed by a leaving replica must not be told to retry into it on
        # the retirement-interval estimate (ISSUE 16 satellite)
        self.drain_deadline: Optional[float] = None
        self.tenants: Dict[str, Dict] = {}

    # ---- per-tenant accounting ---------------------------------------------

    def tenant(self, name: str) -> Dict:
        """The (lazily created) stats record for one tenant key."""
        d = self.tenants.get(name)
        if d is None:
            if len(self.tenants) >= self.MAX_TENANTS and \
                    name != self._OVERFLOW_TENANT:
                return self.tenant(self._OVERFLOW_TENANT)
            d = self.tenants[name] = {
                "submitted": 0, "admitted": 0, "retired": 0,
                "cancelled": 0, "timed_out": 0, "shed": 0,
                "service_tokens": 0,
                "ttfts": deque(maxlen=self.TTFT_SAMPLES),
                "tpots": deque(maxlen=self.TTFT_SAMPLES),
            }
        return d

    def by_tenant(self) -> Dict[str, Dict[str, int]]:
        """Queued/live request counts per TENANT ROW — tenants past
        ``MAX_TENANTS`` fold into the overflow row exactly as
        :meth:`tenant` folded their counters at submit, so the rows
        always close against the counter dict. The ONE folding used by
        the engine's ``health_snapshot()`` per-tenant breakdown and the
        InvariantAuditor's accounting-closure check."""
        def tkey(name: str) -> str:
            return name if name in self.tenants else self._OVERFLOW_TENANT

        out = {name: {"queued": 0, "live": 0} for name in self.tenants}
        for r in self.queue:
            out[tkey(r.tenant)]["queued"] += 1
        for r in self.slots:
            if r is not None:
                out[tkey(r.tenant)]["live"] += 1
        return out

    @property
    def prefill_queue_depth(self) -> int:
        """Requests still ahead of their FIRST token on this replica:
        everything queued plus live slots mid-prefill. The backlog a
        prefill-pool replica's retry hint must account for — and the
        saturation signal the router's prefill-pool sizing reads."""
        return len(self.queue) + \
            sum(1 for r in self.live if r.prefilling)

    def retry_after_s(self) -> float:
        """Suggested backoff when shedding: the mean interval between the
        most recent retirements (one retirement frees one slot, which is
        what drains one queued request), SCALED by the prefill backlog —
        a shed request re-arriving after one mean retirement interval
        meets the same full queue if ``prefill_queue_depth`` requests
        are still ahead of it, so the hint multiplies the interval by
        the backlog (floor 1: an idle replica keeps the plain estimate).
        Before two retirements have been observed there is no interval
        to estimate, so the conservative ``FLAGS_serving_retry_after_s``
        default is returned instead of a degenerate None/0 a client
        would turn into a hot retry loop.

        During an ACTIVE drain the retirement-interval estimate is the
        wrong signal entirely — this replica is leaving, and a client
        retrying into it on a sub-second interval estimate just gets
        shed again. The hint becomes the drain deadline REMAINDER: after
        that long, this replica is gone and the retry belongs to
        whatever replaced it."""
        if self.drain_deadline is not None:
            remaining = self.drain_deadline - time.time()
            if remaining > 0:
                return round(remaining, 3)
        if len(self._finish_times) < 2:
            return self.default_retry_after_s
        span = self._finish_times[-1] - self._finish_times[0]
        if span <= 0:
            return 0.001
        est = span / (len(self._finish_times) - 1)
        return round(est * max(1, self.prefill_queue_depth), 3)

    # ---- lifecycle --------------------------------------------------------

    def submit(self, req: Request, enforce_bound: bool = True) -> int:
        """Queue one request. ``enforce_bound=False`` bypasses the
        queue-depth shed — the supervisor's crash-recovery resubmission
        path, where every request was ALREADY accepted once and the
        re-queued set (old queue + old slots) can legitimately exceed the
        admission bound by up to ``max_slots``."""
        if enforce_bound and len(self.queue) >= self.queue_depth:
            # SHED, don't queue: a bounded queue with a retry-after hint
            # keeps tail latency bounded under overload — an unbounded one
            # converts overload into unbounded TTFT for everyone
            self.shed += 1
            self.tenant(req.tenant)["shed"] += 1
            ra = self.retry_after_s()
            hint = f"; retry in ~{ra}s" if ra is not None else ""
            raise ServingQueueFull(
                f"admission queue full ({self.queue_depth}): request shed"
                f"{hint}; drain with step()/stream() or raise "
                f"FLAGS_serving_queue_depth",
                queue_depth=len(self.queue), live_slots=len(self.live),
                retry_after_s=ra)
        # fail fast on requests the pool can NEVER hold (vs transiently
        # full); the bound is KV entries, not blocks — block granularity
        # would admit up to block_size-1 entries past max_model_len.
        # Embedding requests (ISSUE 19) bypass both: they run through the
        # encoder without KV blocks, so pool geometry cannot reject them.
        if req.kind != "embed":
            if req.kv_tokens > self.cache.max_model_len:
                raise ValueError(
                    f"request needs {req.kv_tokens} KV entries "
                    f"(prompt {req.prompt_len} + {req.max_new_tokens} new) "
                    f"> max_model_len {self.cache.max_model_len}")
            usable = self.cache.manager.num_blocks - 1  # block 0 is null
            if self.preempt_enabled:
                # on-demand: only the PROMPT must fit the pool (a max_new
                # worst case is a budget, not a charge — EOS usually lands
                # first, and a genuinely over-budget sole survivor is
                # truncated, not hung)
                n = self.cache.blocks_for(req.prompt_len)
                what = f"prompt ({req.prompt_len} tokens)"
            else:
                # reservation mode admits only full worst-case footprints
                n = self.cache.blocks_for(req.kv_tokens)
                what = f"worst case ({req.kv_tokens} KV entries)"
            if n > usable:
                raise ValueError(
                    f"request {what} needs {n} KV blocks but the pool only "
                    f"has {usable} usable blocks (num_blocks="
                    f"{self.cache.manager.num_blocks} incl. the null "
                    f"block); admitting it would wait forever")
        req.rid = self._next_rid
        self._next_rid += 1
        req.submit_t = time.time()
        if req.enqueue_t is None:
            req.enqueue_t = req.submit_t
        req.state = QUEUED
        if req.deadline is not None:
            self.deadline_requests += 1
        self.tenant(req.tenant)["submitted"] += 1
        self.queue.append(req)
        return req.rid

    def next_admission(self, gate=None) -> Optional[Request]:
        """Pop the policy's pick into a free slot if its blocks fit; None
        when nothing can be admitted this iteration. On-demand mode maps
        prefix-cache hits and allocates only the remaining prompt blocks;
        reservation mode allocates the full worst case. Admission never
        preempts running work — it waits for retirement to free blocks,
        and is head-of-line PER THE POLICY'S ORDER: when the pick's
        blocks don't fit, admission waits rather than skipping to a
        smaller request (skipping would starve large requests).

        ``gate`` (ISSUE 19) is the engine's adapter-pool admission hook:
        called with the pick BEFORE any blocks are allocated, returning
        False when the pick cannot be seated right now (its adapter has
        no free pool slot — every slot pinned by running requests). A
        gated-out pick is SKIPPED for this iteration only — the policy
        re-selects among the remaining candidates, so one starved
        adapter never head-of-line blocks base traffic or other
        adapters — and stays queued for the next step, when a
        retirement may have unpinned a slot."""
        candidates = [r for r in self.queue if r.kind != "embed"]
        while candidates:
            if not [m for m, r in enumerate(self.slots) if r is None]:
                return None
            # a preempted request re-queued at the FRONT outranks any
            # policy pick: its generated tokens are already paid for, and
            # the no-livelock argument assumes it readmits at the next
            # retirement
            if candidates[0] is self.queue[0] and self.queue[0].preemptions:
                req = candidates[0]
            else:
                req = self.policy.select(candidates, self, time.time())
            if gate is None or gate(req):
                break
            candidates.remove(req)
        else:
            return None
        free = [m for m, r in enumerate(self.slots) if r is None]
        ids = req.build_prefill_ids()
        res = self.cache.admit(
            ids, reserve_kv=None if self.preempt_enabled else req.kv_tokens,
            namespace=req.adapter_id)
        if res is None:
            return None                       # the pick waits for blocks
        blocks, hit, reg_state = res
        self.queue.remove(req)
        slot = free[0]
        req.blocks, req.slot = blocks, slot
        req.prefill_ids = ids
        req.num_computed = hit
        req.reg_state = reg_state
        req.prefix_hit_tokens += hit
        self.prefix_hit_tokens += hit
        if req.preemptions:
            # KV this readmission re-runs prefill over: cache hits exempt,
            # and never more than the request ever actually computed
            rec = max(0, min(req.computed_hwm, len(ids)) - hit)
            req.recomputed_tokens += rec
            self.recomputed_tokens += rec
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        if req.admit_t is None:
            req.admit_t = time.time()
        req.state = RUNNING
        self.cache.assign(slot, blocks)
        self.slots[slot] = req
        self.admitted += 1
        t = self.tenant(req.tenant)
        t["admitted"] += 1
        t["service_tokens"] += req.prompt_len     # prefill work charged now
        return req

    def adopt_running(self, req: Request, slot: int,
                      blocks: List[int]) -> int:
        """Seat a MIGRATED request (ISSUE 16) directly into a slot,
        bypassing the queue: its KV chain arrived with it, so there is
        no prefill to schedule and no admission to wait for. The engine
        has already allocated ``blocks`` and written the chain; this
        stamps the full submit+admit bookkeeping (rid, timestamps,
        counters, tenant accounting) in one step so every closure
        invariant the auditor checks (submitted >= admitted >= ...,
        tenant rows, deadline_requests) holds exactly as if the request
        had been submitted and admitted here."""
        if self.slots[slot] is not None:
            raise RuntimeError(f"adopt into occupied slot {slot}")
        req.rid = self._next_rid
        self._next_rid += 1
        req.submit_t = time.time()
        if req.deadline is not None:
            self.deadline_requests += 1
        t = self.tenant(req.tenant)
        t["submitted"] += 1
        req.blocks, req.slot = blocks, slot
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        req.state = RUNNING
        self.slots[slot] = req
        self.admitted += 1
        t["admitted"] += 1
        t["service_tokens"] += req.prompt_len
        return req.rid

    def admit_embeds(self) -> List[Request]:
        """Pop EVERY queued embedding request (``kind == "embed"``) for
        the engine's batched encoder dispatch (ISSUE 19). Embeds need no
        decode slot and no KV blocks, so admission is unconditional and
        slot-free; the engine completes the whole batch — encoder
        forward, pooled output, :meth:`finish` — inside the same locked
        step, so no observer ever sees a RUNNING request without a slot.
        Stamps the full admit bookkeeping so the auditor's accounting
        closure (admitted >= retired, tenant rows) holds exactly as for
        generate traffic."""
        out = [r for r in self.queue if r.kind == "embed"]
        for req in out:
            self.queue.remove(req)
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            req.state = RUNNING
            self.admitted += 1
            t = self.tenant(req.tenant)
            t["admitted"] += 1
            t["service_tokens"] += req.prompt_len
        return out

    def preempt(self, req: Request) -> None:
        """Free a RUNNING request's blocks and re-queue it at the FRONT for
        recompute-on-readmission (tokens kept — greedy recompute is
        bit-identical). The engine calls this only when the pool is dry,
        picking its newest-admitted victim via :meth:`preempt_victim`."""
        done = (req.num_computed if req.prefilling
                else req.prompt_len + max(len(req.tokens) - 1, 0))
        req.computed_hwm = max(req.computed_hwm, done)
        self.cache.release(req.slot, req.blocks)
        self.slots[req.slot] = None
        req.blocks, req.slot = None, None
        req.num_computed = 0
        req.prefill_ids = None
        req.reg_state = (0, None)          # readmission re-seeds from hits
        req.preemptions += 1
        self.preemptions += 1
        req.state = QUEUED
        self.queue.appendleft(req)

    def preempt_victim(self) -> Optional[Request]:
        """The newest-admitted live request — UNLESS it is the only one
        (the oldest is never preempted; its monotonic progress is the
        livelock-freedom proof)."""
        live = [r for r in self.slots if r is not None]
        if len(live) < 2:
            return None
        return max(live, key=lambda r: r.admit_seq)

    def finish(self, req: Request) -> None:
        """Mark finished + free its KV back to the pool."""
        self._release(req)
        req.state = FINISHED
        self._record(req)
        self.retired += 1
        self._finish_times.append(req.finish_t)
        t = self.tenant(req.tenant)
        t["retired"] += 1
        t["service_tokens"] += len(req.tokens)    # decode work charged here
        if req.ttft_s is not None:
            t["ttfts"].append(req.ttft_s)
        if req.tok_latency_s is not None:
            t["tpots"].append(req.tok_latency_s)

    def terminate(self, req: Request, state: str) -> None:
        """Force a queued or running request into a terminal state —
        CANCELLED (explicit cancel / abandoned stream), TIMED_OUT
        (deadline passed after it started), or SHED (deadline passed
        while still queued). Frees any blocks it holds via the same path
        preemption uses (free, do NOT requeue) and records it in
        ``finished`` so ``result()``/``request()`` still find the partial
        output. The caller (engine) is responsible for clearing its slot
        arrays when the request held a slot."""
        assert state in TERMINAL_STATES and state != FINISHED, state
        if req.slot is None:
            # queued (possibly preempted-and-requeued): no blocks held
            try:
                self.queue.remove(req)
            except ValueError:
                pass
        self._release(req)
        req.state = state
        self._record(req)
        counter = {CANCELLED: "cancelled", TIMED_OUT: "timed_out",
                   SHED: "shed"}[state]
        setattr(self, counter, getattr(self, counter) + 1)
        t = self.tenant(req.tenant)
        t[counter] += 1
        t["service_tokens"] += len(req.tokens)
        if req.tok_latency_s is not None:     # timed-out/cancelled partials
            t["tpots"].append(req.tok_latency_s)    # are real decode work

    def _release(self, req: Request) -> None:
        req.finish_t = time.time()
        if req.blocks is not None:
            # blocks and slot are only ever assigned together in
            # next_admission, so a request with blocks always holds a slot
            self.cache.release(req.slot, req.blocks)
            self.slots[req.slot] = None
            req.blocks = None
        req.slot = None
        if req.deadline is not None:
            self.deadline_requests -= 1

    def _record(self, req: Request) -> None:
        self.finished[req.rid] = req
        while len(self.finished) > self.keep_finished:
            del self.finished[next(iter(self.finished))]

    def find(self, rid: int) -> Optional[Request]:
        """The queued or running request with this id (None when unknown
        or already terminal)."""
        for r in self.queue:
            if r.rid == rid:
                return r
        for r in self.slots:
            if r is not None and r.rid == rid:
                return r
        return None

    def retire_finished(self) -> List[Request]:
        done = [r for r in self.slots if r is not None and r.finished]
        for r in done:
            self.finish(r)
        return done

    # ---- introspection ----------------------------------------------------

    @property
    def live(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    @property
    def decoding(self) -> List[Request]:
        """Live requests past prefill (the decode dispatch's active set)."""
        return [r for r in self.slots if r is not None and not r.prefilling]

    @property
    def pending(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    @property
    def depth(self) -> int:
        """Outstanding work — queued plus live requests. The router's
        power-of-two-choices load signal: cheap enough to read per
        submit, and proportional to the time a new admission waits."""
        return len(self.queue) + sum(r is not None for r in self.slots)

    def result(self, rid: int) -> np.ndarray:
        return self.finished[rid].output()
