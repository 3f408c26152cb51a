"""Bench entry-point smoke (ISSUE 2 satellite): `python bench.py --<sec>`
must import and run one tiny step under JAX_PLATFORMS=cpu, so bench bit-rot
is caught by tier-1 instead of burning a driver round. Sections chosen for
CPU cost: llama (the headline path, smoke config compiles in seconds) and
input (the new pipeline section, sub-second). The heavy conv sections
(resnet/detect) compile for minutes on CPU and stay driver-only."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run_bench(*flags, timeout=420):
    env = {"JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin:/usr/local/bin"),
           "PYTHONPATH": REPO,
           "HOME": os.environ.get("HOME", "/tmp"),
           "BENCH_BUDGET_S": "3600"}   # never self-skip in the smoke run
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *flags],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    metrics = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(d, dict) and "metric" in d:
            metrics[d["metric"]] = d
    return metrics, proc


@pytest.fixture
def bench_main(monkeypatch, compile_cache_config_restored):
    """bench.main() in this process (already on the CPU platform; the
    compile-cache settings it switches on are restored afterwards)."""
    import bench

    def run(*argv):
        monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
        bench.main()

    return bench, run


def test_a_raising_section_exits_nonzero(bench_main, monkeypatch, capsys):
    """Sections stay isolated from each other, but a run in which one
    raised is a failed run — and a failed headline prints no value."""
    bench, run = bench_main

    def boom(*a, **k):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(bench, "_llama_point", boom)
    with pytest.raises(SystemExit) as exc:
        run("--llama", "--steps", "1")
    assert exc.value.code not in (0, None) and "llama" in str(exc.value.code)
    out = capsys.readouterr()
    assert "llama_train_mfu" not in out.out
    assert "kernel refused" in out.err        # the traceback is printed


def test_resolving_to_the_cpu_unasked_is_refused(bench_main, monkeypatch):
    """The toy preset runs only where the environment said
    JAX_PLATFORMS=cpu; a run that merely found no chip must not print
    numbers under the chip's metric names."""
    _, run = bench_main
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        run("--llama", "--steps", "1")
    assert "JAX_PLATFORMS=cpu was not set" in str(exc.value.code)


def test_unknown_device_kind_has_no_peak():
    import bench

    class Dev:
        platform, device_kind = "tpu", "TPU v9 experimental"

    with pytest.raises(RuntimeError, match="TPU v9 experimental"):
        bench._peak_tflops(Dev())
    Dev.device_kind = "TPU v5 lite"
    assert bench._peak_tflops(Dev()) == 197.0


def test_bench_llama_entry_point():
    """The headline section: one tiny fused+donated train step end to end,
    final stdout line is the llama_train_mfu re-emit the driver parses."""
    metrics, proc = _run_bench("--llama", "--steps", "1")
    assert "llama_train_mfu" in metrics, proc.stdout + proc.stderr
    last = [l for l in proc.stdout.splitlines() if l.strip()][-1]
    assert json.loads(last)["metric"] == "llama_train_mfu"


def test_bench_input_entry_point():
    """The input-pipeline section: H2D cost + prefetch overlap rows."""
    metrics, proc = _run_bench("--input", "--steps", "2")
    assert "input_h2d_ms_per_batch" in metrics, proc.stdout + proc.stderr
    assert "input_overlap_pct" in metrics
    assert metrics["input_h2d_ms_per_batch"]["value"] > 0
    assert 0.0 <= metrics["input_overlap_pct"]["value"] <= 100.0


def test_bench_serve_entry_point():
    """The serving section (ISSUE 4 + 5): continuous batching over the
    paged KV cache vs the static-batch baseline on one mixed-length trace,
    plus the shared-prefix trace (prefix cache on vs off) and the
    preemption-pressure trace (on-demand paging under a deliberately
    undersized pool). The section itself asserts the acceptance proofs
    (paged greedy bit-equal to the dense path, constant decode-executable
    count, pressure-row parity) before emitting, so a green run here pins
    them in tier-1; the smoke additionally checks the detail record and
    that the throughput rows landed."""
    metrics, proc = _run_bench("--serve")
    assert "serving_agg_tok_s" in metrics, proc.stdout + proc.stderr
    assert "serving_throughput_speedup" in metrics
    assert "serving_prefix_speedup" in metrics
    assert metrics["serving_agg_tok_s"]["value"] > 0
    detail = None
    for line in proc.stderr.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(d, dict) and "serve" in d:
                detail = d["serve"]
    assert detail is not None, proc.stderr
    assert detail["outputs_match"] is True
    assert detail["recompiles_constant"] is True
    assert detail["decode_traces"] == 1
    # shared-prefix row: hits actually happened and parity held
    assert detail["prefix_outputs_match"] is True
    assert detail["prefix_hit_tokens"] > 0
    # preemption-pressure row: the machinery fired and stayed bit-exact
    assert detail["preempt_outputs_match"] is True
    assert detail["preemptions"] >= 1
    assert detail["oom_truncated"] == 0
    # long-context row (ISSUE 10): the Pallas flash-decoding paged-
    # attention kernel (interpret mode on CPU — the REAL kernel path)
    # must emit token streams bit-equal to the gather fallback at every
    # context length with one decode executable per engine; the parity/
    # no-recompile asserts also live in-section
    assert detail["longctx_outputs_match"] is True
    assert detail["longctx_recompiles_constant"] is True
    assert any(k.startswith("longctx_kernel_tok_s") for k in detail)
    # KV capacity row (ISSUE 10 acceptance): at one fixed byte budget the
    # int8 pool admits >= 2x the fp pool's concurrent sequences, serves
    # the trace with exact length/EOS parity, and its pool actually fits
    # the budget
    assert detail["kv_capacity_ratio"] >= 2.0
    assert detail["kv_int8_peak_live"] >= 2 * detail["kv_fp_peak_live"]
    assert detail["kv_length_parity"] is True
    # True on the deterministic CPU trace (a fully-agreeing request
    # exists); None would mean the exactness check went vacuous
    assert detail["kv_eos_parity"] is not False
    assert detail["kv_token_agreement"] >= 0.6
    assert detail["kv_int8_pool_bytes"] <= detail["kv_budget_bytes"]
    # tensor-parallel row (ISSUE 12): at one fixed PER-DEVICE byte budget
    # the TP=2 engine (pool sharded on its kv-heads axis over the tp
    # mesh) must hold >= 2x the TP=1 engine's concurrent sequences,
    # serve the trace bit-identically (greedy + seeded sampling), compile
    # decode once per mesh shape, leak nothing, and its per-device pool
    # bytes must actually fit the budget. The parity/compile-once asserts
    # also live in-section; the smoke pins the detail record and the
    # serving_tp_capacity_ratio metric. bench provisions the 8-way host
    # platform itself (XLA_FLAGS before jax init), so tp_supported must
    # be True here.
    assert detail["tp_supported"] is True
    assert detail["tp_outputs_match"] is True
    assert detail["tp_capacity_ratio"] >= 2.0
    assert detail["tp2_concurrent"] >= 2 * detail["tp1_concurrent"]
    # measured, not just arithmetic: the live peak actually doubled
    assert detail["tp2_peak_live"] >= 2 * detail["tp1_peak_live"]
    assert detail["tp_decode_traces"] == 1
    assert detail["tp_leaked_blocks"] == 0
    assert detail["tp2_shard_bytes"] <= detail["tp_per_device_budget_bytes"]
    assert detail["tp_tok_s"] > 0
    assert "serving_tp_capacity_ratio" in metrics
    # spec-decode row (ISSUE 11): n-gram drafting + multi-query verify
    # across the acceptance sweep — bit-parity on BOTH traces, real
    # acceptance on the high trace, one verify executable, zero leaked
    # blocks after rollback, and the low-acceptance fall-through bound
    # are asserted in-section; the smoke pins the detail record
    assert detail["spec_outputs_match"] is True
    assert detail["spec_accepted"] > 0
    assert detail["spec_traces"] == 1
    assert detail["spec_leaked_blocks"] == 0
    assert detail["spec_low_accept_ratio"] >= 0.9
    assert "serving_spec_speedup" in metrics
    # overload row (ISSUE 6): 2x-capacity arrivals through FIFO vs EDF +
    # TTFT-SLO shedding — load was genuinely shed and every NON-shed
    # output stayed bit-identical to the dense oracle (timed-out partials
    # prefix-match). The EDF-beats-FIFO p99 comparison is asserted inside
    # the bench section itself (a regression fails this entry point via
    # the bench's nonzero exit).
    assert detail["overload_outputs_match"] is True
    assert detail["overload_shed"] > 0
    assert detail["overload_served"] > 0
    assert detail["overload_edf_decode_traces"] == 1
    # front-line row (ISSUE 7): a mini trace through the asyncio server
    # (in-process transport — port-free) with an engine crash injected
    # mid-trace, then a graceful drain. The bit-parity / restart /
    # zero-leak / scale-up proofs are asserted inside the section; the
    # smoke pins the detail record so the row can't silently vanish.
    assert detail["frontline_outputs_match"] is True
    assert detail["frontline_restarts"] >= 1
    assert detail["frontline_resubmitted"] >= 1
    assert detail["frontline_leaked_blocks"] == 0
    assert detail["frontline_tok_s"] > 0
    assert detail["autoscale_action"] == "scale_up"
    # fleet row (ISSUE 9): replica_kill mid-trace through the 2-replica
    # router — failover bit-parity, zero router-failed requests, zero
    # leaked blocks on EVERY replica, a rolling restart that rebuilds the
    # whole fleet under live traffic, and no recompile anywhere (shared
    # EnginePrograms). The asserts also live in-section; the smoke pins
    # the detail record so the row can't silently vanish.
    assert detail["router_outputs_match"] is True
    assert detail["router_failovers"] >= 1
    assert detail["router_failed"] == 0
    assert detail["router_leaked_blocks"] == 0
    assert detail["router_roll_outputs_match"] is True
    assert detail["router_roll_restarts"] >= detail["router_replicas"]
    assert detail["router_recompiles_constant"] is True
    assert detail["router_tok_s"] > 0
    # KV tiering row (ISSUE 16): device-pool churn with the host offload
    # tier on vs off — re-visit parity, real swap traffic, verified (zero
    # corrupt-drop) restores, zero recompute, and strictly more prefix
    # hits than the tier-off run whose chains died with the device pool.
    # The asserts also live in-section; the smoke pins the record + the
    # emitted metric.
    assert detail["tier_outputs_match"] is True
    assert detail["tier_swap_outs"] > 0
    assert detail["tier_swap_ins"] > 0
    assert detail["tier_hits"] > 0
    assert detail["tier_corrupt_drops"] == 0
    assert detail["tier_recomputed_tokens"] == 0
    assert detail["tier_prefix_hit_tokens"] > \
        detail["tier_off_prefix_hit_tokens"]
    assert detail["tier_hit_ttft_ratio"] > 0
    assert "serving_tier_hit_ttft_ratio" in metrics
    # migration row (ISSUE 16): scale-in drain with live KV migration —
    # every in-flight request moved (block chains + resolved state) to
    # the survivor and finished bit-identically with zero recompute,
    # zero failures and zero leaked blocks anywhere in the fleet
    assert detail["migration_outputs_match"] is True
    assert detail["migrations"] >= 1
    assert detail["migration_failed"] == 0
    assert detail["migration_recomputed_tokens"] == 0
    assert detail["migration_leaked_blocks"] == 0
    assert detail["migration_recompute_saved"] > 0
    assert "serving_migration_recompute_saved" in metrics
    # fleet-cache row (ISSUE 17): prefix families re-visited from the
    # NON-holder replica — the fleet directory pulls the chain's blocks
    # cross-replica (CRC-checked at both ends) where island caches
    # re-prefill. Parity / pulls / zero fallbacks / zero leaks are
    # asserted in-section; the smoke pins the record + the metric.
    assert detail["fleet_outputs_match"] is True
    assert detail["fleet_cache_pulls"] >= 1
    assert detail["fleet_pulled_blocks"] >= 3
    assert detail["fleet_pull_fallbacks"] == 0
    assert detail["fleet_prefix_hit_tokens"] > \
        detail["fleet_island_hit_tokens"]
    assert detail["fleet_leaked_blocks"] == 0
    assert detail["fleet_hit_ttft_ratio"] > 0
    assert "serving_fleet_cache_hit_ttft_ratio" in metrics
    # disaggregation row (ISSUE 17): long prompts prefill on a dedicated
    # replica and hand their finished chain to a decode replica via the
    # adopt path — parity, handoffs >= 1, recomputed_tokens == 0, zero
    # failed/leaks asserted in-section; the smoke pins the record + the
    # metric.
    assert detail["disagg_outputs_match"] is True
    assert detail["disagg_prefill_routed"] >= 1
    assert detail["disagg_prefill_handoffs"] >= 1
    assert detail["disagg_recomputed_tokens"] == 0
    assert detail["disagg_failed"] == 0
    assert detail["disagg_leaked_blocks"] == 0
    assert detail["disagg_tpot_ratio"] > 0
    assert "serving_disagg_tpot_ratio" in metrics
    # durability row (ISSUE 18): journal overhead < 5% on the mixed
    # trace, then kill -9 mid-flight + timed cold-restart recovery —
    # bit parity across the kill, ZERO lost requests and ZERO
    # re-delivered tokens are asserted in-section; the smoke pins the
    # detail record + the serving_recovery_ms metric so the row (and
    # its exactly-once proof) cannot silently vanish.
    assert detail["durable_outputs_match"] is True
    assert detail["durable_lost_requests"] == 0
    assert detail["durable_duplicated_tokens"] == 0
    assert detail["durable_journal_overhead_pct"] < 5.0
    assert detail["durable_recovery_ms"] > 0
    assert detail["durable_resubmitted"] >= 1
    assert detail["durable_recovered_records"] >= 1
    assert detail["durable_wal_bytes"] > 0
    assert detail["durable_leaked_blocks"] == 0
    assert "serving_recovery_ms" in metrics
    # multi-adapter LoRA row (ISSUE 19): 8 adapters served round-robin
    # from ONE paged pool vs the base-only engine — zero-adapter traffic
    # bit-identical, the mix adds zero decode executables, overhead
    # < 10%, zero leaked blocks; the smoke pins the detail record + both
    # metrics so the row cannot silently vanish.
    assert detail["lora_outputs_match"] is True
    assert detail["lora_adapter_overhead_pct"] < 10.0
    assert detail["lora_adapters"] == 8
    assert detail["lora_adapter_loads"] >= 8
    assert detail["lora_leaked_blocks"] == 0
    assert "serving_lora_adapter_overhead_pct" in metrics
    assert "serving_lora_adapters_per_replica" in metrics
    # mixed-batching row (ISSUE 20): chunked prefill fused into the
    # decode dispatch — mixed streams bit-equal to the two-phase AND
    # dense oracles, chat TPOT p99 under long-prompt admission strictly
    # better than two-phase, fewer dispatches per step, ONE mixed
    # executable across role churn, zero leaked blocks; the asserts also
    # live in-section, the smoke pins the record + both metrics so the
    # row cannot silently vanish.
    assert detail["mixed_outputs_match"] is True
    assert detail["mixed_tpot_p99_ratio"] > 1.0
    assert detail["mixed_dispatches_per_step"] < \
        detail["unmixed_dispatches_per_step"]
    assert detail["mixed_traces"] == 1
    assert detail["mixed_recompiles_constant"] is True
    assert detail["mixed_leaked_blocks"] == 0
    assert "serving_mixed_tpot_p99_ratio" in metrics
    assert "serving_mixed_dispatches_per_step" in metrics


def test_bench_health_entry_point():
    """The run-health section (ISSUE 3): sentinel overhead row on the
    tuned llama path plus the in-bench containment proof (a NaN-poisoned
    step must be flagged bad by the fused detector)."""
    metrics, proc = _run_bench("--health", "--steps", "1")
    assert "health_sentinel_overhead_pct" in metrics, \
        proc.stdout + proc.stderr
    detail = None
    for line in proc.stderr.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(d, dict) and "health" in d:
                detail = d["health"]
    assert detail is not None, proc.stderr
    assert detail["nan_step_flagged"] is True
    assert detail["nan_step_contained"] is True
