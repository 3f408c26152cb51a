"""Fleet-scale chaos replay + invariant auditor (ISSUE 13).

Three surfaces under test: the deterministic workload generator
(`inference.serving.workload`: the trace is a pure function of the spec,
the manifest reproduces it bit-exactly), the `InvariantAuditor` (one
registry of named serving invariants — each check must CATCH its seeded
corruption, not just pass on clean state), and `run_replay` (a generated
trace through a multi-replica router under a seeded chaos timeline with
the autoscaler actuating: zero violations, zero leaks, failed == 0, and
the same manifest replaying bit-identically — including onto a router
rebuilt from shared compiled programs). The 10k-request fleet replay
(the ISSUE 13 acceptance run) is marked slow + replay.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from paddle_tpu.models.llama import LlamaConfig, init_params
from paddle_tpu.testing import chaos

pytestmark = pytest.mark.replay


def tiny_cfg():
    return LlamaConfig(vocab_size=97, hidden_size=64, intermediate_size=96,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=64)


BASE = dict(block_size=4, max_slots=2, max_model_len=32, decode_chunk=2,
            queue_depth=4, prefill_chunk=None)


@pytest.fixture(scope="module")
def setup():
    """Params + a compiled-programs donor every router in the module
    shares (the same EnginePrograms sharing the fleet relies on)."""
    from paddle_tpu.inference.serving import ServingConfig, ServingRouter
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    donor = ServingRouter(params, cfg, ServingConfig(**BASE), replicas=1)
    p = np.arange(1, 8, dtype=np.int32)
    donor.run([p, p[:4]], max_new_tokens=[2, 2], eos_token_id=None)
    return cfg, params, donor._programs


def small_spec(**kw):
    from paddle_tpu.inference.serving import WorkloadSpec
    base = dict(requests=60, seed=5, prefix_len=8, tail_lens=(2, 3, 4),
                output_lens=(3, 4, 6), horizon_steps=36,
                autoscale_every=8, audit_every=4)
    base.update(kw)
    return WorkloadSpec(**base)


def serving_config(**kw):
    from paddle_tpu.inference.serving import ServingConfig
    sc = dict(BASE)
    sc.update(kw)
    return ServingConfig(**sc)


# ---------------------------------------------------------------------------
# workload generator: the trace is a pure function of the spec
# ---------------------------------------------------------------------------

class TestWorkloadGenerator:
    def test_trace_pure_function_of_spec(self):
        from paddle_tpu.inference.serving import generate_trace
        a = generate_trace(small_spec())
        b = generate_trace(small_spec())
        assert len(a) == len(b) == 60
        for x, y in zip(a, b):
            assert x.arrival_step == y.arrival_step
            assert x.tenant == y.tenant and x.family == y.family
            np.testing.assert_array_equal(x.prompt, y.prompt)
            assert (x.max_new_tokens, x.temperature, x.top_k, x.top_p,
                    x.seed, x.priority, x.deadline_steps, x.behavior,
                    x.behavior_at) == \
                   (y.max_new_tokens, y.temperature, y.top_k, y.top_p,
                    y.seed, y.priority, y.deadline_steps, y.behavior,
                    y.behavior_at)
        c = generate_trace(small_spec(seed=6))
        assert any(x.arrival_step != z.arrival_step
                   or not np.array_equal(x.prompt, z.prompt)
                   for x, z in zip(a, c))

    def test_trace_shape(self):
        """Zipf tenants (rank-1 tenant dominates), shared-prefix
        families actually share their prefix, arrivals sorted inside the
        horizon, and the sampled / deadline / misbehavior fractions all
        materialize."""
        from paddle_tpu.inference.serving import generate_trace
        spec = small_spec(requests=300, horizon_steps=100)
        tr = generate_trace(spec)
        steps = [t.arrival_step for t in tr]
        assert steps == sorted(steps)
        assert 0 <= min(steps) and max(steps) < spec.horizon
        counts = {}
        for t in tr:
            counts[t.tenant] = counts.get(t.tenant, 0) + 1
        assert counts["t0"] == max(counts.values())      # Zipf head
        fams = {}
        for t in tr:
            if t.family is not None:
                fams.setdefault(t.family, []).append(t.prompt)
        assert fams
        for members in fams.values():
            first = members[0][:spec.prefix_len]
            for p in members[1:]:
                np.testing.assert_array_equal(p[:spec.prefix_len], first)
        assert any(t.temperature > 0 for t in tr)
        assert any(t.deadline_steps is not None for t in tr)
        assert {t.behavior for t in tr} - {"normal"}

    def test_manifest_roundtrip_regenerates_trace(self):
        from paddle_tpu.inference.serving import (ReplayManifest,
                                                  generate_trace)
        spec = small_spec()
        tl = chaos.chaos_timeline(7, spec.horizon, events=4)
        m = ReplayManifest.capture(spec, tl)
        m2 = ReplayManifest.from_json(m.to_json())
        assert m2.workload().asdict() == spec.asdict()
        assert m2.timeline().spec() == tl.spec()
        assert m.tag == m2.tag
        a, b = generate_trace(spec), generate_trace(m2.workload())
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.prompt, y.prompt)
        assert "FLAGS_serving_queue_depth" in m.flags

    def test_chaos_timeline_seeded_and_step_indexed(self):
        tl = chaos.chaos_timeline(3, 100, events=6)
        tl2 = chaos.chaos_timeline(3, 100, events=6)
        assert tl.spec() == tl2.spec()
        assert {e.name for e in tl.events} == set(chaos.TIMELINE_INJECTORS)
        assert all(0 < e.step < 100 for e in tl.events)
        due = tl.due(100)
        assert len(due) == 6 and tl.remaining == 0


# ---------------------------------------------------------------------------
# invariant auditor: every check CATCHES its seeded corruption
# ---------------------------------------------------------------------------

class TestInvariantAuditor:
    def _engine(self, setup, **kw):
        from paddle_tpu.inference.serving import (ServingConfig,
                                                  ServingEngine)
        cfg, params, _ = setup
        sc = dict(BASE)
        sc.update(kw)
        return ServingEngine(params, cfg, ServingConfig(**sc))

    def test_registry_is_the_default_check_set(self):
        from paddle_tpu.inference.serving import (AUDIT_CHECKS,
                                                  InvariantAuditor)
        assert InvariantAuditor().checks == tuple(AUDIT_CHECKS)
        with pytest.raises(ValueError, match="unknown audit checks"):
            InvariantAuditor(checks=["nope"])

    def test_clean_engine_passes_every_step(self, setup):
        from paddle_tpu.inference.serving import InvariantAuditor
        eng = self._engine(setup)
        aud = InvariantAuditor()
        p = np.arange(1, 9, dtype=np.int32)
        rids = [eng.submit(p, max_new_tokens=4, eos_token_id=None)
                for _ in range(3)]
        while eng.pending:
            aud.observe(eng.step(1), lookup=eng._sched.find)
            aud.check(eng)
        aud.quiesce(eng)
        assert not aud.violations
        assert len(rids) == 3

    def test_partition_corruption_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        eng.cache.manager._free.pop()            # steal a block
        with pytest.raises(InvariantViolation) as e:
            InvariantAuditor(manifest="m-tag").check(eng)
        assert e.value.check == "block_partition"
        assert e.value.manifest == "m-tag"
        assert "m-tag" in str(e.value)

    def test_refcount_and_bijection_corruption_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        bm = eng.cache.manager
        b = bm.alloc(1)[0]
        bm._ref[b] = 0                           # live refcount < 1
        with pytest.raises(InvariantViolation) as e:
            InvariantAuditor().check(eng)
        assert e.value.check in ("block_partition", "block_consistency")
        bm._ref[b] = 1
        bm._block2hash[b] = 12345                # dangling reverse entry
        got = InvariantAuditor().check(eng, collect=True)
        assert any(v.check == "block_consistency" for v in got)

    def test_quiesce_leak_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        bm = eng.cache.manager
        bm.alloc(2)                              # held by nobody
        with pytest.raises(InvariantViolation) as e:
            InvariantAuditor().check(eng)
        assert e.value.check == "quiesce_leaks"

    def test_exactly_once_repeat_and_overrun_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        p = np.arange(1, 9, dtype=np.int32)
        rid = eng.submit(p, max_new_tokens=4, eos_token_id=None)
        aud = InvariantAuditor()
        first = eng.step(1)
        aud.observe(first, lookup=eng._sched.find)
        # replaying the same emission is a duplicate delivery: the
        # ledger diverges from the authoritative record immediately
        with pytest.raises(InvariantViolation) as e:
            aud.observe(first, lookup=eng._sched.find)
        assert e.value.check == "exactly_once"
        # and a terminal record must close against the ledger
        aud2 = InvariantAuditor()
        while eng.pending:
            aud2.observe(eng.step(1), lookup=eng._sched.find)
        rec = eng.request(rid)

        class Forged:
            state = rec.state
            tokens = list(rec.tokens) + [1]      # one token too many

        with pytest.raises(InvariantViolation):
            aud2.close_request(rid, Forged)

    def test_emission_after_terminal_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        aud = InvariantAuditor()

        class Rec:
            state = "finished"
            tokens = [5]
            max_new_tokens = 1
            eos_token_id = None

        aud.observe({7: [5]}, lookup=lambda rid: Rec)
        aud.close_request(7, Rec)
        with pytest.raises(InvariantViolation) as e:
            aud.observe({7: [9]}, lookup=lambda rid: Rec)
        assert e.value.check == "exactly_once"

    def test_lifecycle_forgery_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        p = np.arange(1, 9, dtype=np.int32)
        rid = eng.submit(p, max_new_tokens=3, eos_token_id=None)
        while eng.pending:
            eng.step()
        rec = eng._sched.finished[rid]
        rec.tokens.append(1)                     # past its budget
        with pytest.raises(InvariantViolation) as e:
            InvariantAuditor().check(eng)
        assert e.value.check == "lifecycle"

    def test_counter_regression_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        p = np.arange(1, 9, dtype=np.int32)
        eng.submit(p, max_new_tokens=2, eos_token_id=None)
        while eng.pending:
            eng.step()
        aud = InvariantAuditor()
        aud.check(eng)                           # baseline
        eng._sched.retired -= 1                  # counter goes backwards
        with pytest.raises(InvariantViolation) as e:
            aud.check(eng)
        assert e.value.check == "counters_monotonic"

    def test_tenant_closure_corruption_caught(self, setup):
        from paddle_tpu.inference.serving import (InvariantAuditor,
                                                  InvariantViolation)
        eng = self._engine(setup)
        p = np.arange(1, 9, dtype=np.int32)
        eng.submit(p, max_new_tokens=2, eos_token_id=None, tenant="a")
        while eng.pending:
            eng.step()
        eng._sched.tenants["a"]["submitted"] += 2
        with pytest.raises(InvariantViolation) as e:
            InvariantAuditor().check(eng)
        assert e.value.check == "tenant_closure"

    def test_router_audit_hook_and_flag(self, setup):
        """router.audit() is the production spelling (collects, never
        raises); FLAGS_serving_audit folds it into health_snapshot()."""
        import paddle_tpu
        from paddle_tpu.inference.serving import (RouterConfig,
                                                  ServingConfig,
                                                  ServingRouter)
        cfg, params, programs = setup
        r = ServingRouter(params, cfg, ServingConfig(**BASE),
                          router_config=RouterConfig(replicas=2,
                                                     hedge_ttft_mult=0.0),
                          programs=programs)
        verdict = r.audit()
        assert verdict["ok"] and verdict["violations"] == []
        snap = r.health_snapshot()
        assert snap["audit"] == {"enabled": False}   # flag off: no cost
        paddle_tpu.set_flags({"FLAGS_serving_audit": True})
        try:
            snap = r.health_snapshot()
            assert snap["audit"]["enabled"] is True
            assert snap["audit"]["ok"] is True
            json.dumps(snap["audit"])                # ops-serializable
        finally:
            paddle_tpu.set_flags({"FLAGS_serving_audit": False})
        # a corrupted replica surfaces (collected, not raised)
        rid0 = r.replicas[0]
        r._replicas[rid0].sup.engine.cache.manager._free.pop()
        verdict = r.audit()
        assert not verdict["ok"]
        assert any("block_partition" in v for v in verdict["violations"])


# ---------------------------------------------------------------------------
# 429/503 retry backoff (satellite): honoring converges, the storm sheds
# ---------------------------------------------------------------------------

class TestRetryBackoff:
    def _replay(self, setup, policy, **spec_kw):
        from paddle_tpu.inference.serving import run_replay
        cfg, params, programs = setup
        spec = small_spec(requests=40, horizon_steps=10, seed=9,
                          output_lens=(4, 6), misbehavior_frac=0.0,
                          deadline_frac=0.0, retry_policy=policy,
                          autoscale_every=0, audit_every=8, **spec_kw)
        return run_replay(params, cfg, spec=spec,
                          serving_config=serving_config(queue_depth=3),
                          replicas=1, chaos=None, programs=programs)

    def test_storm_sheds_honoring_converges(self, setup):
        """A burst over one tiny-queue replica: the client that ignores
        the 429's retry_after_s (the OLD workload-generator behavior)
        hammers the full queue and its shed count grows far past the
        honoring client's, while the client that backs off by the hint
        converges — every request eventually served, nothing given up."""
        import paddle_tpu
        storm = self._replay(setup, "storm")
        # honor the wall-clock hint; keep the cold-start hint small so
        # the test converges in seconds, restoring the flag after
        paddle_tpu.set_flags({"FLAGS_serving_retry_after_s": 0.05})
        try:
            honor = self._replay(setup, "hint")
        finally:
            paddle_tpu.set_flags({"FLAGS_serving_retry_after_s": 1.0})
        assert honor["gave_up"] == 0 and honor["failed"] == 0
        assert honor["completed"] == honor["requests"]
        assert storm["shed_submits"] >= 1.5 * max(honor["shed_submits"], 1)
        assert storm["retries"] > honor["retries"]
        # the deterministic fixed backoff converges too (the replay-
        # determinism setting)
        fixed = self._replay(setup, "fixed")
        assert fixed["gave_up"] == 0
        assert fixed["completed"] == fixed["requests"]
        assert fixed["shed_submits"] < storm["shed_submits"]


# ---------------------------------------------------------------------------
# replay determinism (satellite): manifest -> bit-identical everything
# ---------------------------------------------------------------------------

class TestReplayDeterminism:
    def test_same_manifest_bit_identical_incl_rebuilt_router(self, setup):
        """Two replays of ONE manifest — the second on a freshly built
        router sharing the first run's compiled programs — produce
        bit-identical per-request token streams, identical chaos event
        ordering, and an identical audit trail."""
        from paddle_tpu.inference.serving import (RouterConfig,
                                                  ServingConfig,
                                                  ServingRouter,
                                                  run_replay)
        cfg, params, programs = setup
        spec = small_spec()
        one = run_replay(params, cfg, spec=spec,
                         serving_config=serving_config(), replicas=2,
                         chaos_events=6, programs=programs,
                         record_streams=True)
        assert one["violations"] == [] and one["leaked_blocks"] == 0
        # resumed on a REBUILT router from the shared programs: spawning
        # the second fleet costs zero compiles (flat trace counter)
        traces0 = programs.stats["decode_traces"]
        rebuilt = ServingRouter(
            params, cfg, ServingConfig(**BASE),
            router_config=RouterConfig(replicas=2, breaker_cooldown_s=0.0,
                                       hedge_ttft_mult=0.0),
            programs=programs)
        two = run_replay(params, cfg, manifest=one["manifest"],
                         router=rebuilt, record_streams=True)
        assert programs.stats["decode_traces"] == traces0
        assert two["streams"] == one["streams"]
        assert two["chaos_fired"] == one["chaos_fired"]
        assert two["audit_trail"] == one["audit_trail"]
        assert two["audit"] == one["audit"]
        assert two["outcomes"] == one["outcomes"]
        rebuilt.close(0)

    def test_manifest_json_roundtrip_replays_identically(self, setup):
        from paddle_tpu.inference.serving import ReplayManifest, run_replay
        cfg, params, programs = setup
        spec = small_spec(requests=30, horizon_steps=20, seed=11)
        one = run_replay(params, cfg, spec=spec,
                         serving_config=serving_config(), replicas=2,
                         chaos_events=3, programs=programs,
                         record_streams=True)
        m = ReplayManifest.from_json(one["manifest_json"])
        two = run_replay(params, cfg, manifest=m,
                         serving_config=serving_config(), replicas=2,
                         programs=programs, record_streams=True)
        assert two["streams"] == one["streams"]
        assert two["audit"] == one["audit"]


# ---------------------------------------------------------------------------
# replay smoke: chaos + autoscale + audit, tier-1 sized
# ---------------------------------------------------------------------------

class TestReplaySmoke:
    def test_small_fleet_replay_clean(self, setup):
        """The tier-1 spelling of the acceptance run: a 3-replica fleet,
        every chaos kind armed, full audit — zero violations, zero
        leaks, failed == 0, and the capacity report emitted."""
        from paddle_tpu.inference.serving import run_replay
        cfg, params, programs = setup
        rep = run_replay(params, cfg, spec=small_spec(audit_every=2),
                         serving_config=serving_config(), replicas=3,
                         chaos_events=6, programs=programs)
        assert rep["violations"] == []
        assert rep["failed"] == 0 and rep["router_failed"] == 0
        assert rep["gave_up"] == 0
        assert rep["leaked_blocks"] == 0
        assert rep["completed"] >= rep["requests"] * 0.7
        assert len(rep["chaos_kinds"]) >= 4
        assert rep["goodput_tok_s_per_chip"] > 0
        cap = rep["capacity"]
        assert cap["layouts"]["fp_tp1"]["concurrent_seqs_per_chip"] > 0
        assert cap["layouts"]["int8_tp1"]["blocks_per_chip"] > \
            cap["layouts"]["fp_tp1"]["blocks_per_chip"]
        assert "tp2" in "".join(cap["layouts"])      # kv_heads=2 shards
        assert "sizing" in cap and "req/s" in cap["sizing"]
        assert rep["drain_report"]["leaked_blocks"] == 0

    def test_autoscale_actuates_and_improves_arrival_p99(self, setup):
        """The PR 7/9 loop closed with a measured effect: the SAME
        manifest served by the autoscaling fleet vs a fixed fleet — the
        autoscaled run spawns under the peak, drains in the trough, and
        its arrival->first-token p99 (which counts shed-retry waits) and
        makespan both beat the fixed fleet's. Step-indexed, so the
        comparison is deterministic and host-load-immune."""
        from paddle_tpu.inference.serving import run_replay
        cfg, params, programs = setup
        spec = small_spec(requests=90, horizon_steps=40,
                          output_lens=(3, 4, 6, 8))
        auto = run_replay(params, cfg, spec=spec,
                          serving_config=serving_config(), replicas=2,
                          chaos_events=6, programs=programs)
        fixed = run_replay(params, cfg,
                           spec=dataclasses.replace(spec,
                                                    autoscale_every=0),
                           serving_config=serving_config(), replicas=2,
                           chaos_events=6, programs=programs)
        assert auto["autoscale"]["spawns"] >= 1
        assert auto["autoscale"]["drains"] >= 1
        assert fixed["autoscale"]["spawns"] == 0
        assert auto["failed"] == 0 and fixed["failed"] == 0
        assert auto["arrival_ttft_steps_p99"] < \
            fixed["arrival_ttft_steps_p99"]
        assert auto["steps"] < fixed["steps"]


# ---------------------------------------------------------------------------
# the acceptance run: 10k requests, >= 3 replicas, >= 4 chaos kinds
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestFleetReplay10k:
    def test_10k_fleet_replay_and_bit_exact_rerun(self, setup):
        """ISSUE 13 acceptance: a seeded 10k-request replay through a
        >= 3-replica router with >= 4 distinct chaos injector firings
        and >= 1 autoscale spawn + >= 1 drain completes with zero
        InvariantViolations, failed == 0 and zero leaked blocks on every
        replica at quiesce, emits a capacity report + goodput metric —
        and the same manifest replayed twice produces bit-identical
        token streams and audit trails."""
        from paddle_tpu.inference.serving import run_replay
        cfg, params, programs = setup
        spec = small_spec(requests=10_000, horizon_steps=2000,
                          tenants=16, families=6,
                          output_lens=(2, 3, 4, 6, 8, 12),
                          audit_every=64, autoscale_every=32,
                          max_attempts=400)
        sc = serving_config(max_slots=4, queue_depth=16,
                            max_model_len=40)
        one = run_replay(params, cfg, spec=spec, serving_config=sc,
                         replicas=3, chaos_events=8, programs=None,
                         record_streams=True)
        assert one["violations"] == []
        assert one["failed"] == 0 and one["router_failed"] == 0
        assert one["gave_up"] == 0
        assert one["leaked_blocks"] == 0
        assert len(one["chaos_kinds"]) >= 4
        assert one["autoscale"]["spawns"] >= 1
        assert one["autoscale"]["drains"] >= 1
        assert one["goodput_tok_s_per_chip"] > 0
        assert one["capacity"]["sizing"]
        assert one["requests"] == 10_000
        two = run_replay(params, cfg, manifest=one["manifest"],
                         serving_config=sc, replicas=3,
                         record_streams=True)
        assert two["streams"] == one["streams"]
        assert two["chaos_fired"] == one["chaos_fired"]
        assert two["audit_trail"] == one["audit_trail"]
        assert two["audit"] == one["audit"]


# ---------------------------------------------------------------------------
# survivable-KV replay (ISSUE 16): tier + migration under chaos, audited
# ---------------------------------------------------------------------------

class TestSurvivableKVReplay:
    def test_tier_and_migration_replay_clean(self, setup):
        """A fleet with the host offload tier AND live migration on,
        chaos drawn from the full mix INCLUDING the tier pair
        (host_pressure, corrupt_offload_block) — the audit (now carrying
        tier_partition + migration_exactly_once) stays clean, nothing
        fails or leaks, and the capacity report grows the host-tier
        columns."""
        from paddle_tpu.inference.serving import RouterConfig, run_replay
        from paddle_tpu.testing.chaos import (TIER_INJECTORS,
                                              TIMELINE_INJECTORS,
                                              chaos_timeline)
        cfg, params, programs = setup
        spec = small_spec()
        timeline = chaos_timeline(
            spec.seed + 1, spec.horizon,
            kinds=TIMELINE_INJECTORS + TIER_INJECTORS, events=8)
        rep = run_replay(
            params, cfg, spec=spec,
            serving_config=serving_config(offload=True, offload_blocks=32),
            router_config=RouterConfig(replicas=3, migrate=True,
                                       breaker_cooldown_s=0.0,
                                       hedge_ttft_mult=0.0),
            chaos=timeline, programs=programs, host_gb=1.0)
        assert rep["violations"] == []
        assert rep["failed"] == 0 and rep["router_failed"] == 0
        assert rep["leaked_blocks"] == 0
        assert rep["drain_report"]["leaked_blocks"] == 0
        # the tier pair actually fired (scheduled kinds include them)
        fired = {name for _, name, _ in rep["chaos_fired"]} \
            if "chaos_fired" in rep else set(rep["chaos_kinds"])
        assert fired & set(TIER_INJECTORS)
        # host-tier capacity columns: an explicit host budget sizes the
        # tier, and host-extended cached tokens strictly beat HBM-only
        cap = rep["capacity"]
        assert cap["host_budget_bytes_per_chip"] == 1 << 30
        fp1 = cap["layouts"]["fp_tp1"]
        assert fp1["host_blocks_per_chip"] > 0
        assert fp1["cached_tokens_hbm_plus_host"] > \
            fp1["cached_tokens_hbm"]
        # int8 host blocks are cheaper: same budget, more cached tokens
        assert cap["layouts"]["int8_tp1"]["host_blocks_per_chip"] > \
            fp1["host_blocks_per_chip"]


# ---------------------------------------------------------------------------
# disaggregated-fleet replay (ISSUE 17): prefill pool + directory chaos
# ---------------------------------------------------------------------------

class TestDisaggReplay:
    def test_disagg_fleet_replay_clean_under_chaos(self, setup):
        """A fleet with a dedicated prefill replica and the cache
        directory on, chaos drawn from the full mix INCLUDING the disagg
        pair: ``kill_prefill_replica`` (mid-handoff prefill death — the
        staged requests land via failover recompute, zero failed) and
        ``stale_directory`` (a poisoned export fails the pull-side CRC
        and degrades to recompute, never wrong KV). The audit — carrying
        ``directory_coherence`` — stays clean every sample, nothing
        fails or leaks fleet-wide."""
        from paddle_tpu.inference.serving import RouterConfig, run_replay
        from paddle_tpu.testing.chaos import (DISAGG_INJECTORS,
                                              TIMELINE_INJECTORS,
                                              chaos_timeline)
        cfg, params, programs = setup
        # requests/horizon trimmed below small_spec defaults: 8 events
        # over 8 kinds still fire every injector once inside [0.1, 0.75)
        # of the horizon, and the fleet drains well before the cap
        spec = small_spec(requests=36, horizon_steps=28)
        timeline = chaos_timeline(
            spec.seed + 2, spec.horizon,
            kinds=TIMELINE_INJECTORS + DISAGG_INJECTORS, events=8)
        rep = run_replay(
            params, cfg, spec=spec, serving_config=serving_config(),
            router_config=RouterConfig(replicas=3, migrate=True,
                                       prefill_replicas=1,
                                       prefill_len_threshold=10,
                                       breaker_cooldown_s=0.0,
                                       hedge_ttft_mult=0.0),
            chaos=timeline, programs=programs)
        assert rep["violations"] == []
        assert rep["failed"] == 0 and rep["router_failed"] == 0
        assert rep["gave_up"] == 0
        assert rep["leaked_blocks"] == 0
        assert rep["drain_report"]["leaked_blocks"] == 0
        fired = {name for _, name, _ in rep["chaos_fired"]} \
            if "chaos_fired" in rep else set(rep["chaos_kinds"])
        assert fired & set(DISAGG_INJECTORS)


# ---------------------------------------------------------------------------
# mixed batching under chaos (ISSUE 20)
# ---------------------------------------------------------------------------

class TestMixedBatchReplay:
    def test_long_prompt_knob_gated_last(self):
        """long_prompt_frac=0 draws nothing: every previously generated
        seed keeps its byte-identical trace; >0 stretches that fraction
        of prompts toward long_prompt_len at the END (family prefixes —
        and the affinity keys hashed from them — stay intact)."""
        from paddle_tpu.inference.serving import generate_trace
        base = generate_trace(small_spec())
        again = generate_trace(small_spec(long_prompt_frac=0.0))
        for x, y in zip(base, again):
            np.testing.assert_array_equal(x.prompt, y.prompt)
        long = generate_trace(small_spec(long_prompt_frac=0.5,
                                         long_prompt_len=24))
        stretched = [y for y in long if len(y.prompt) == 24]
        assert len(stretched) >= len(base) // 4
        # arrivals are drawn before the per-request loop, so the knob
        # never reshapes the arrival curve
        for x, y in zip(base, long):
            assert x.arrival_step == y.arrival_step
        # extension lands at the END: stretched family rows still OPEN
        # with their family's shared prefix (the prefix-cache unit)
        by_fam = {}
        for y in stretched:
            if y.family is not None:
                by_fam.setdefault(y.family, []).append(y.prompt[:8])
        assert any(len(v) >= 2 for v in by_fam.values())
        for rows in by_fam.values():
            for p in rows[1:]:
                np.testing.assert_array_equal(rows[0], p)

    def test_mixed_fleet_replay_clean_under_chaos(self, setup):
        """The chaos timeline over a MIXED fleet: chunked long prompts
        riding the decode dispatch (prefill_chunk=4), every chaos kind
        armed, full audit — zero violations, zero leaks, failed == 0."""
        from paddle_tpu.inference.serving import run_replay
        cfg, params, programs = setup
        spec = small_spec(requests=40, horizon_steps=30,
                          long_prompt_frac=0.4, long_prompt_len=24,
                          output_lens=(3, 4, 6))
        rep = run_replay(params, cfg, spec=spec,
                         serving_config=serving_config(prefill_chunk=4),
                         replicas=2, chaos_events=6, programs=programs)
        assert rep["violations"] == []
        assert rep["failed"] == 0 and rep["router_failed"] == 0
        assert rep["gave_up"] == 0
        assert rep["leaked_blocks"] == 0
        assert rep["drain_report"]["leaked_blocks"] == 0
        assert rep["completed"] >= rep["requests"] * 0.7
