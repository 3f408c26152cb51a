"""Generate the op-surface reference from the schema registry.

The reference drives codegen (C++ API, grad nodes, bindings, docs) from
``paddle/phi/api/yaml/ops.yaml``; here the registry IS the runtime op table
(``core.dispatch.OP_REGISTRY``) and this generator derives the docs from it
— one source of truth, no drift.

    python -m paddle_tpu.ops.gen_docs [out_path]
"""

from __future__ import annotations

import inspect
import sys


def generate(out_path: str = "docs/OPS.md") -> str:
    import os

    # populate the registry: the tensor surface plus every domain that
    # registers kernels (upstream: one ops.yaml covers them all)
    import paddle_tpu.ops  # noqa: F401
    import paddle_tpu.nn.functional  # noqa: F401
    import paddle_tpu.sparse  # noqa: F401
    import paddle_tpu.signal  # noqa: F401
    import paddle_tpu.geometric  # noqa: F401
    import paddle_tpu.vision.ops  # noqa: F401
    import paddle_tpu.fft  # noqa: F401
    import paddle_tpu.audio  # noqa: F401
    import paddle_tpu.incubate.nn.functional  # noqa: F401
    import paddle_tpu.distributed.moe_utils  # noqa: F401
    import paddle_tpu.optimizer  # noqa: F401
    import paddle_tpu.distributed.ps  # noqa: F401
    import paddle_tpu.vision.transforms  # noqa: F401
    import paddle_tpu.text  # noqa: F401
    import paddle_tpu.metric  # noqa: F401
    from paddle_tpu.core.dispatch import OP_REGISTRY
    from paddle_tpu.ops.sweep_specs import attach_specs, sweep_coverage
    attach_specs()

    lines = ["# Op surface reference",
             "",
             "Generated from `core.dispatch.OP_REGISTRY` (the ops.yaml-"
             "equivalent single source of truth) by "
             "`python -m paddle_tpu.ops.gen_docs`. Do not edit by hand.",
             "",
             f"{len(OP_REGISTRY)} registered ops.",
             "",
             "Sweep coverage (tests/test_op_sweep.py: numpy/scipy oracle + "
             "finite-difference grad + bf16 legs, from the schema's "
             "category tags and OpDef.sweep specs): "
             f"**{sweep_coverage()[0]} of {sweep_coverage()[1]} ops "
             f"({100 * sweep_coverage()[0] // sweep_coverage()[1]}%)**; "
             "the rest are covered by hand-written domain tests "
             "(tests/test_*.py) or are stateful/random/IO ops outside the "
             "oracle pattern.",
             ""]
    # serving ops surface (ISSUE 6): the health_snapshot() payload an ops
    # endpoint serves, generated from the engine's field registry (the
    # snapshot test pins the live payload to the same registry)
    from paddle_tpu.inference.serving.engine import HEALTH_SNAPSHOT_FIELDS
    lines += ["## Serving health surface",
              "",
              "`inference.serving.ServingEngine.health_snapshot()` "
              "(docs/SERVING.md \"Overload & multi-tenancy\") returns one "
              "JSON-serializable record per call — the payload the "
              "serving endpoints below serve. "
              "`EngineSupervisor.health_snapshot()` adds the "
              "supervisor-level fields on top:",
              "",
              "| field | meaning |",
              "|---|---|"]
    lines += [f"| `{k}` | {v} |" for k, v in HEALTH_SNAPSHOT_FIELDS.items()]
    # serving front line (ISSUE 7): endpoints + drain/restart runbook +
    # the server flag table, all generated from the live registries so
    # the runbook cannot drift from the code
    from paddle_tpu.flags import flags_table, get_flags
    lines += [
        "",
        "## Serving front line (`inference.serving.server`)",
        "",
        "`ServingServer` multiplexes any number of streaming clients "
        "onto ONE supervised engine thread: submissions cross a "
        "thread-safe command queue, token/finish events come back on "
        "bounded per-client asyncio queues (SSE frames over the TCP "
        "transport; dict events over the in-process transport the tier-1 "
        "tests use). A consumer that falls `FLAGS_serving_client_queue` "
        "events behind is disconnected and its request cancelled — KV "
        "freed, nothing pinned.",
        "",
        "### Endpoints",
        "",
        "| endpoint | verb | serves | status |",
        "|---|---|---|---|",
        "| `/healthz` | GET | liveness: pump thread alive and the hang "
        "watchdog quiet | 200 / 503 |",
        "| `/readyz` | GET | readiness: accepting (not draining/closed) "
        "AND engine restart budget intact AND queue below its bound | "
        "200 / 503 |",
        "| `/metrics` | GET | the full supervisor `health_snapshot()` "
        "(fields above), incl. per-tenant TTFT/TPOT p50/p99 and the "
        "`autoscale` recommendation | 200 |",
        "| `/generate` | POST | SSE token stream for `{\"prompt\": "
        "[ids], ...submit kwargs}`; 503 + `retry_after_s` while "
        "draining/broken, 429 + `retry_after_s` when the bounded queue "
        "sheds | 200 / 429 / 503 / 400 |",
        "",
        "### Restart runbook (engine supervision)",
        "",
        "The engine step loop runs under `EngineSupervisor`'s crash "
        "barrier: an unexpected exception — or a hang-watchdog trip "
        "naming a `serving.*` section — tears the engine down, rebuilds "
        "it from the same params/config (reusing the compiled "
        "`EnginePrograms`: recovery never recompiles), and re-submits "
        "every non-terminal request (queued verbatim; running from "
        "`prompt + tokens so far` on the preemption-recompute path — "
        "greedy outputs stay bit-identical, no delivered token "
        "repeats). Each recovery consumes one unit of the "
        "`FLAGS_serving_max_restarts` budget; when it runs out the "
        "replica flips BROKEN: `/readyz` 503, submits refused, in-flight "
        "requests failed with partials readable. Page on: `restarts` "
        "climbing (crash loop brewing), `broken: true` (replace the "
        "replica), `watchdog.fired` (a dispatch hung).",
        "",
        "### Drain runbook (deploys / preemption)",
        "",
        "SIGTERM — forwarded by the elastic launcher on preemption "
        "(`--preempt_grace`, exported as `PADDLE_PREEMPT_GRACE`) — or "
        "`close()` starts a graceful drain: (1) admissions stop, new "
        "submits get the structured 503 + `retry_after_s`; (2) in-flight "
        "requests finish within the deadline "
        "(`PADDLE_PREEMPT_GRACE - 2s` when the launcher set it, else "
        "`FLAGS_serving_drain_deadline_s`); (3) the remainder is "
        "cancelled, every KV block returns to the pool (the drain "
        "report's `leaked_blocks` must read 0).",
        "",
        "### Cold-restart runbook (durable serving)",
        "",
        "With `FLAGS_serving_journal_dir` set, every replica logs its "
        "request lifecycle to ONE shared `RequestJournal` "
        "(`inference.serving.journal`): an append-only WAL of "
        "crc-framed submit / token-cursor / ownership-rebase / terminal "
        "events, fsynced once per engine step "
        "(`FLAGS_serving_journal_sync`; admissions fsync at submit so "
        "an ACKED request is never lost), plus a serving-state snapshot "
        "every `FLAGS_serving_snapshot_every` flushes (tmp + fsync + "
        "rename, newest two generations kept) that bounds replay "
        "length. KV is NEVER persisted — recovery recomputes it through "
        "the resubmit path. After a `kill -9` (or host loss with the "
        "journal on durable storage): "
        "`EngineSupervisor.recover(journal_dir, params, cfg, ...)` for "
        "one replica, `ServingRouter.cold_start(journal_dir, ...)` for "
        "a fleet. Recovery loads the newest snapshot that verifies "
        "(corrupt generations are skipped — `snapshot_fallbacks` "
        "counts them), replays the WAL suffix (a torn tail is "
        "truncated to the last whole frame — `torn_tail_bytes`), "
        "closes records whose delivered tokens already complete them, "
        "and resubmits everything else bit-exactly from `prompt + "
        "delivered-so-far` under its original journal id — zero lost "
        "requests, zero re-delivered tokens, greedy and seeded streams "
        "bit-identical (the `durable_exactly_once` auditor check and "
        "`tests/test_journal.py`'s kill-point fuzz hold the line). A "
        "graceful SIGTERM "
        "drain writes a final snapshot, so the next cold start replays "
        "nothing. Watch: `torn_tail_bytes` > 0 (the crash cut a "
        "write), `snapshot_fallbacks` climbing (snapshot corruption — "
        "check the disk), `resubmitted`/`recovered_tokens` (work "
        "re-entering the fleet after recovery).",
        "",
        "### Autoscale hook",
        "",
        "`EngineSupervisor.autoscale_signal()` turns queue-depth / "
        "shed-rate / slot-utilization telemetry into `scale_up` / "
        "`scale_in` / `hold`, and can write the elastic launcher's "
        "`--elastic_rejoin_file` format "
        "(`distributed.launch.main.write_rejoin_file`: empty file = "
        "take what you need, integer = offered worker count) so a "
        "watching launcher scales the job out.",
        "",
        "### Server / serving flags",
        ""]
    lines += flags_table(sorted(
        n for n in get_flags()
        if n.startswith("FLAGS_serving_")
        and not n.startswith("FLAGS_serving_router_")))
    # serving fleet (ISSUE 9): the multi-replica router tier — breaker
    # states, failover/rolling-restart runbooks, the router snapshot
    # registry and the router flag table, all from the live registries
    from paddle_tpu.inference.serving.router import ROUTER_HEALTH_FIELDS
    lines += [
        "",
        "## Serving fleet (`inference.serving.router`)",
        "",
        "`ServingRouter` fronts N in-process replicas — each a full "
        "supervisor/server stack — sharing ONE set of params and ONE "
        "compiled `EnginePrograms` (spawning or rebuilding a replica "
        "never recompiles). Every submit probes the candidates "
        "(`/readyz` predicate + `health_snapshot()`; a raising probe is "
        "a breaker failure) and picks by power-of-two-choices on queue "
        "depth, with tenant/prefix-affinity stickiness keeping "
        "shared-prefix traffic on the replica that holds its cached KV "
        "blocks. `ServingServer` front-lines a router exactly as it "
        "front-lines one supervisor — same endpoints, same SSE streams.",
        "",
        "### Circuit breaker states",
        "",
        "| state | traffic | transition |",
        "|---|---|---|",
        "| `closed` | flows; consecutive failures counted | "
        "`FLAGS_serving_router_breaker_threshold` failures in a row "
        "(probe raises, submit unavailability, supervisor restarts) "
        "-> `open`; a replica going BROKEN trips it immediately |",
        "| `open` | none — the router routes around the replica and "
        "EVACUATES its in-flight requests (failover from delivered "
        "tokens, bit-exact) | after "
        "`FLAGS_serving_router_breaker_cooldown_s` the next routing "
        "decision runs a half-open probe |",
        "| `half_open` | one health probe, no user traffic at risk | "
        "probe success -> `closed` (the replica rejoins); failure -> "
        "`open` with a fresh cooldown |",
        "",
        "### Failover runbook",
        "",
        "A replica that exhausts its restart budget (`broken`) or opens "
        "its breaker loses its traffic: every non-terminal request is "
        "resubmitted to a healthy replica from `prompt + tokens "
        "delivered so far` (`EngineSupervisor.resubmit`, the "
        "preemption-recompute path) — greedy outputs stay bit-identical "
        "and no delivered token repeats. With NO routable replica left "
        "the request goes state `failed` (partial readable) and "
        "`counters.failed` increments — page on it. Watch: "
        "`counters.failovers` climbing (a replica is flapping), "
        "`fleet.routable` vs `fleet.size` (capacity lost), "
        "`replicas.<rid>.breaker.state` (who is walled off).",
        "",
        "### Rolling-restart runbook (deploys)",
        "",
        "`start_rolling_restart()` (or the blocking `rolling_restart()`)"
        " drains ONE replica at a time — admissions shift to the rest of "
        "the fleet, in-flight work finishes (or fails over at the drain "
        "deadline), the replica rebuilds from the shared programs "
        "(generation bumps, breaker resets), and the roll moves on. A "
        "live trace served across the roll completes with ZERO failed "
        "requests — `counters.failed` staying 0 is the acceptance "
        "invariant. A `broken` replica is healed by the roll: its "
        "rebuild gets a fresh restart budget.",
        "",
        "### Drain-with-migration runbook (live KV migration)",
        "",
        "With `FLAGS_serving_migrate` on (or `RouterConfig(migrate="
        "True)`), every router-initiated drain — `drain_replica()` for "
        "scale-in, each per-replica drain of a rolling restart, and the "
        "deadline sweep before evacuation — first LIVE-MIGRATES the "
        "draining replica's in-flight requests instead of waiting them "
        "out: `EngineSupervisor.export_request` serializes the request's "
        "resolved decode state plus its KV block chain "
        "(`ServingEngine.serialize_request`), a healthy candidate "
        "adopts it (`adopt` — shape-key-checked, all-or-nothing: any "
        "refusal frees everything it touched and raises `AdoptError`), "
        "and only after the adoptive route is installed is the origin "
        "copy released (`release_migrated` — exactly-once by "
        "construction: the route moves before the origin cancel, so the "
        "drain-cancel sweep can never double-failover the request). "
        "Decoding continues on the survivor with ZERO recomputed "
        "tokens and a bit-identical stream; PRNG continuity for sampled "
        "requests rides the serialized state. When NO candidate can "
        "take the blocks (pool full, no slot, mismatched shape key) the "
        "request falls back to the PR 9 resubmit path at the drain "
        "deadline — `counters.migration_fallbacks` counts these; "
        "correctness is unchanged, only the recompute cost returns. "
        "Watch: `counters.migrations` / `migration_tokens` (work "
        "preserved), `migration_fallbacks` climbing (targets too full "
        "to adopt — add capacity before rolling), and the auditor's "
        "`migration_exactly_once` check, which fails the fleet if a "
        "migrated stream ever diverges from its router-side mirror.",
        "",
        "### Autoscale actuation",
        "",
        "`router.autoscale()` acts on the fleet-aggregated "
        "`autoscale_signal()`: scale-up SPAWNS a replica (up to "
        "`FLAGS_serving_router_max_replicas`) and optionally writes the "
        "elastic launcher's `--elastic_rejoin_file`; scale-in DRAINS the "
        "least-loaded replica (never below one). `router.poll_rejoin()` "
        "consumes the same file format back "
        "(`distributed.launch.main.consume_rejoin_file`), so an external "
        "autoscaler can drive fleet size through one file.",
        "",
        "### Router health surface",
        "",
        "`ServingRouter.health_snapshot()` — keys pinned to "
        "`ROUTER_HEALTH_FIELDS` by the snapshot test:",
        "",
        "| field | meaning |",
        "|---|---|"]
    lines += [f"| `{k}` | {v} |" for k, v in ROUTER_HEALTH_FIELDS.items()]
    lines += [
        "",
        "### Router flags",
        ""]
    lines += flags_table(sorted(
        n for n in get_flags()
        if n.startswith("FLAGS_serving_router_")))
    # fleet-scale replay + invariant audit (ISSUE 13): the auditor check
    # table renders straight from the AUDIT_CHECKS registry and the
    # replay runbook documents the manifest contract, so neither can
    # drift from audit.py/workload.py
    from paddle_tpu.inference.serving.audit import AUDIT_CHECKS
    lines += [
        "",
        "## Workload replay & capacity planning "
        "(`inference.serving.workload` / `.audit`)",
        "",
        "The fleet-scale proof layer: a DETERMINISTIC workload generator "
        "(`WorkloadSpec`/`generate_trace` — diurnal/bursty arrivals, "
        "Zipf tenants, shared-prefix prompt families, mixed greedy/"
        "sampled knobs, priorities/deadlines, client cancels/disconnects/"
        "abandons, and 429/503 retries that back off by the returned "
        "`retry_after_s`), replayed through a multi-replica router by "
        "`run_replay` under a seeded step-indexed chaos timeline "
        "(`testing.chaos.chaos_timeline`) while the autoscaler actuates, "
        "with the `InvariantAuditor` sampling throughout and running "
        "exhaustively at quiesce.",
        "",
        "### Invariant auditor",
        "",
        "`InvariantAuditor` evaluates the registry below against a live "
        "engine / supervisor / router; a failure raises a structured "
        "`InvariantViolation` naming the CHECK, the REPLICA and the "
        "replay MANIFEST that reproduces it. Three deployment modes: "
        "per-step in tests (the one definition of each invariant the "
        "test suite's fuzzes call), sampled in long replays "
        "(`WorkloadSpec.audit_every`), and in production — "
        "`router.audit()`, folded into `health_snapshot()` behind "
        "`FLAGS_serving_audit` (off by default: the checks walk every "
        "block map).",
        "",
        "| check | proves |",
        "|---|---|"]
    lines += [f"| `{k}` | {v} |" for k, v in AUDIT_CHECKS.items()]
    lines += [
        "",
        "### Replay runbook",
        "",
        "1. Every `run_replay` emits a `ReplayManifest` (seed + spec + "
        "chaos schedule + the resolved `ServingConfig` and "
        "`RouterConfig` scalars + the starting replica count, plus the "
        "`FLAGS_serving_*` values recorded for the operator's "
        "reference — both configs resolve from them eagerly, so the "
        "shape fields already carry the values that mattered; "
        "`manifest_json` in the report) and stamps it into every "
        "violation. To reproduce a fleet-scale failure bit-exactly: "
        "`run_replay(params, cfg, "
        "manifest=ReplayManifest.from_json(s))` — the captured engine "
        "+ fleet shape is re-applied (pass `serving_config=` / "
        "`router_config=` / `replicas=` to override), same per-request "
        "token streams, same chaos firing order, same audit trail "
        "(`retry_policy=\"fixed\"`; the `\"hint\"` policy honors the "
        "measured wall-clock `retry_after_s`, so shed counts then track "
        "host load).",
        "2. Chaos timelines are STEP-indexed, never wall-clock: an event "
        "fires at the identical point in the request stream on every "
        "replay. `replica_kill` is skipped (and logged) when fewer than "
        "two adoption-capable replicas remain — killing the sole "
        "survivor proves nothing about failover.",
        "3. The driver's clients are part of the workload: a shed submit "
        "retries after the backoff its policy dictates, misbehaving "
        "clients cancel/disconnect/abandon at scripted token counts, "
        "and client-side step deadlines cancel overdue work.",
        "4. The report's acceptance surface: `violations == []`, "
        "`failed == 0` (no request stranded without a replica), "
        "`leaked_blocks == 0` on every replica at quiesce, autoscale "
        "`spawns`/`drains` >= 1 each with the measured arrival-TTFT "
        "p99 effect vs the fixed-fleet counterfactual "
        "(`tests/test_replay.py` asserts it on a small trace).",
        "",
        "### Capacity report",
        "",
        "`capacity_report` (emitted with every replay, standalone "
        "callable) combines the `paged_pool_block_bytes` arithmetic — "
        "per-chip block cost and concurrent sequences across fp/int8 x "
        "TP degree at an HBM budget — with the replay's measured "
        "curves: req/s, TTFT/TPOT p50/p99, `goodput_tok_s_per_chip` "
        "(SLO-met tokens per second per chip), and the sizing line "
        "(\"X replicas of config Y serve Z req/s within SLO\") plus "
        "`replicas_for_<N>_req_s` projections.",
    ]
    lines += ["",
              "## Op table",
              "",
              "| op | signature | doc |",
              "|---|---|---|"]
    for name in sorted(OP_REGISTRY):
        d = OP_REGISTRY[name]
        try:
            sig = str(inspect.signature(d.fn))
        except (TypeError, ValueError):
            sig = "(...)"
        doc = (d.doc or "").split("\n")[0].replace("|", "\\|")
        lines.append(f"| `{name}` | `{sig}` | {doc} |")
    text = "\n".join(lines) + "\n"
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        f.write(text)
    return out_path


if __name__ == "__main__":
    path = generate(sys.argv[1] if len(sys.argv) > 1 else "docs/OPS.md")
    print(f"wrote {path}")
