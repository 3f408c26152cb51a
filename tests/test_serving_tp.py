"""Tensor-parallel serving (ISSUE 12): the paged KV pool sharded on its
kv-heads axis over a "tp" mesh, prefill/chunked-prefill/decode/spec-verify
running under shard_map.

The oracle discipline mirrors every other serving tier: the TP=1 engine —
byte-for-byte the pre-TP code path — is the bit-parity reference, and the
TP>1 engine must reproduce its token streams EXACTLY (greedy and seeded
sampling, fp32 and int8 pools, kernel and gather attention paths). The
merge is an exact all_gather concatenation of per-shard attention heads
with the post-attention math replicated, so parity is structural, not
approximate (a row-parallel psum merge would break it — see
llama.serving_param_specs).

Runs on the conftest-provisioned 8-way virtual CPU mesh via the
``tp_platform`` fixture (@pytest.mark.tp skips on single-device
platforms).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import generation as G
from paddle_tpu.models import llama
from paddle_tpu.inference.serving import (EngineSupervisor, ServingConfig,
                                          ServingEngine)

pytestmark = pytest.mark.tp

CFG = llama.LlamaConfig(vocab_size=128, hidden_size=64,
                        intermediate_size=96, num_hidden_layers=2,
                        num_attention_heads=8, num_key_value_heads=4,
                        max_position_embeddings=128)

# base engine shape — every test reuses these knobs so engines can share
# compiled EnginePrograms (prefill_chunk/prefix_cache/num_blocks are not
# part of the program-shape key)
BASE = dict(block_size=8, max_slots=4, max_model_len=96, queue_depth=16,
            decode_chunk=4)


def mk(params, tp, programs=None, **kw):
    return ServingEngine(params, CFG,
                         ServingConfig(**{**BASE, **kw}, tp=tp),
                         programs=programs)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prompts():
    # all lengths inside ONE power-of-2 prefill bucket (8) and one wave
    # bucket: each engine compiles exactly one prefill executable, which
    # is what keeps this file's compile bill inside the tier-1 budget
    rng = np.random.default_rng(7)
    return [rng.integers(0, CFG.vocab_size, (int(s),)).astype(np.int32)
            for s in (5, 8, 6, 7)]


@pytest.fixture(scope="module")
def eng1(tp_platform, params, prompts):
    """TP=1 oracle engine (fp pool, gather path) — module-scoped so its
    compiled programs amortize across the file. Depends on tp_platform so
    a single-device platform SKIPS here instead of erroring in setup."""
    return mk(params, 1)


@pytest.fixture(scope="module")
def eng2(tp_platform, params):
    """TP=2 engine sharing the base shape (its own programs: a different
    mesh shape must never share executables)."""
    return mk(params, 2)


@pytest.fixture(scope="module")
def oracle(eng1, prompts):
    return [np.asarray(o) for o in
            eng1.run(prompts, max_new_tokens=10, eos_token_id=None)]


def _parity(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


class TestTPBitParity:
    def test_greedy_gather(self, tp_platform, eng2, oracle, prompts):
        """TP=2 greedy token streams are bit-identical to TP=1 on the fp
        pool through the gather path; the decode program compiles ONCE
        and a second trace adds zero executables."""
        outs = eng2.run(prompts, max_new_tokens=10, eos_token_id=None)
        assert _parity(outs, oracle)
        st = eng2.stats()
        assert st["decode_traces"] == 1
        assert st["tp_degree"] == 2
        # second run warms the prefix-HIT path (the mixed program first
        # traces here, exactly as at TP=1); the third run must then
        # add zero executables anywhere
        outs2 = eng2.run(prompts, max_new_tokens=10, eos_token_id=None)
        assert _parity(outs2, oracle)
        before = dict(eng2.stats())
        outs3 = eng2.run(prompts, max_new_tokens=10, eos_token_id=None)
        assert _parity(outs3, oracle)
        after = eng2.stats()
        for k in ("decode_traces", "prefill_traces", "mixed_traces",
                  "sample_traces", "spec_traces"):
            assert after[k] == before[k], k

    def test_greedy_kernel(self, tp_platform, params, prompts):
        """Same parity through the Pallas flash-decoding kernel (interpret
        mode on CPU — the REAL kernel code path): each shard executes the
        unmodified kernel on its kv-head slice of the pool."""
        o1 = mk(params, 1, paged_kernel="on").run(
            prompts, max_new_tokens=10, eos_token_id=None)
        e2 = mk(params, 2, paged_kernel="on")
        o2 = e2.run(prompts, max_new_tokens=10, eos_token_id=None)
        assert _parity(o1, o2)
        assert e2.stats()["decode_traces"] == 1

    def test_int8_pool(self, tp_platform, params, prompts):
        """int8 pools shard k/v AND their scale planes identically: TP=2
        is bit-identical to TP=1 on the quantized pool through both
        attention paths."""
        for kernel in ("off", "on"):
            o1 = mk(params, 1, kv_quant="int8", paged_kernel=kernel).run(
                prompts, max_new_tokens=10, eos_token_id=None)
            e2 = mk(params, 2, kv_quant="int8", paged_kernel=kernel)
            o2 = e2.run(prompts, max_new_tokens=10, eos_token_id=None)
            assert _parity(o1, o2), f"kernel={kernel}"
            # the scale leaves actually split with the kv heads (dim 2 of
            # both head-major layouts)
            assert e2.cache.pool["k_scale"].sharding.spec[2] == "tp"
            assert e2.cache.pool["k"].sharding.spec[2] == "tp"

    def test_seeded_sampling(self, tp_platform, eng1, eng2, prompts):
        """Sampled streams (per-request temperature/top-k/top-p/seed)
        reproduce bit-exactly across mesh sizes: the sampler runs on the
        REPLICATED merged logits, so the per-token-index PRNG contract is
        untouched by sharding. The wave mixes greedy and sampled rows."""
        def run(eng):
            rids = []
            for i, p in enumerate(prompts):
                kw = ({} if i % 3 == 0 else
                      dict(temperature=0.8 + 0.1 * i, top_k=17,
                           top_p=0.9, seed=100 + i))
                rids.append(eng.submit(p, max_new_tokens=10,
                                       eos_token_id=None, **kw))
            while eng.pending:
                eng.step()
            return [eng.request(r).output() for r in rids]

        assert _parity(run(eng1), run(eng2))

    def test_tp4(self, tp_platform, params, prompts, oracle):
        """Mesh degree 4 (8 query heads / 4 kv heads -> 1 kv head per
        shard) stays bit-identical too."""
        if tp_platform < 4:
            pytest.skip("needs 4 devices")
        e4 = mk(params, 4)
        assert _parity(e4.run(prompts, max_new_tokens=10,
                              eos_token_id=None), oracle)
        assert e4.stats()["decode_traces"] == 1


class TestTPSchedulerComposition:
    """The host-side machinery — chunked prefill, prefix cache,
    preemption, spec decode — is device-count-agnostic: block tables and
    slot operands replicate, only pool bytes split."""

    def test_chunked_prefill_and_prefix_cache(self, tp_platform, params,
                                              eng1, eng2):
        rng = np.random.default_rng(3)
        pre = rng.integers(0, CFG.vocab_size, (24,)).astype(np.int32)
        shared = [np.concatenate(
            [pre, rng.integers(0, CFG.vocab_size, (6,)).astype(np.int32)])
            for _ in range(5)]
        e1 = mk(params, 1, prefill_chunk=8, programs=eng1.programs)
        e2 = mk(params, 2, prefill_chunk=8, programs=eng2.programs)
        o1 = e1.run(shared, max_new_tokens=8, eos_token_id=None)
        o2 = e2.run(shared, max_new_tokens=8, eos_token_id=None)
        assert _parity(o1, o2)
        assert e2.stats()["prefix_hit_tokens"] > 0
        assert e2.stats()["prefix_hit_tokens"] == \
            e1.stats()["prefix_hit_tokens"]

    def test_preemption_pressure(self, tp_platform, params, eng1, eng2,
                                 prompts):
        """An undersized pool forces preempt-and-recompute; outputs stay
        bit-identical across mesh sizes and no block leaks on either."""
        # short prompts (one prefill bucket — no extra executables), long
        # outputs and a 9-block pool: pressure comes from decode GROWTH,
        # so extension runs dry mid-flight and preemption must fire
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
                   for _ in range(6)]
        e1 = mk(params, 1, num_blocks=9, prefix_cache=None,
                programs=eng1.programs)
        e2 = mk(params, 2, num_blocks=9, prefix_cache=None,
                programs=eng2.programs)
        o1 = e1.run(prompts, max_new_tokens=24, eos_token_id=None)
        o2 = e2.run(prompts, max_new_tokens=24, eos_token_id=None)
        assert _parity(o1, o2)
        assert e2.stats()["preemptions"] >= 1
        assert e1.cache.manager.blocks_in_use == 0
        assert e2.cache.manager.blocks_in_use == 0

    def test_spec_decode(self, tp_platform, params, eng1):
        """Speculative verify (the multi-query kernel entry point) under
        shard_map: drafts fire, acceptance is real, and spec output is
        bit-identical both to the TP=1 spec engine and to plain decode.
        Seeds screened for self-continuation cycles on THIS config (the
        acceptance assert re-verifies them every run)."""
        prompts = []
        for s in (21, 24):
            base = np.random.default_rng(s).integers(
                0, CFG.vocab_size, (8,)).astype(np.int32)
            long = np.asarray(G.generate(params, jnp.asarray(base[None]),
                                         CFG, max_new_tokens=40))[0]
            prompts.append(np.concatenate([base, long[:24]]))
        plain = mk(params, 1, programs=eng1.programs).run(
            prompts, max_new_tokens=16, eos_token_id=None)
        s1 = mk(params, 1, spec_decode=4, spec_ngram=2)
        s2 = mk(params, 2, spec_decode=4, spec_ngram=2)
        o1 = s1.run(prompts, max_new_tokens=16, eos_token_id=None)
        o2 = s2.run(prompts, max_new_tokens=16, eos_token_id=None)
        assert _parity(o1, o2)
        assert _parity(o2, plain)
        assert s2.stats()["spec_traces"] == 1
        assert s2.stats()["spec_accepted"] > 0
        assert s2.stats()["spec_accepted"] == s1.stats()["spec_accepted"]
        assert s2.cache.manager.blocks_in_use == 0


class TestTPPrograms:
    """EnginePrograms keying across mesh shapes (ISSUE 12 satellite)."""

    def test_same_shape_shares(self, tp_platform, params, eng2, prompts,
                               oracle):
        # jit is lazy — make sure the shared programs have actually traced
        # before snapshotting the flat counter
        eng2.run(prompts[:2], max_new_tokens=4, eos_token_id=None)
        traces = eng2.stats()["decode_traces"]
        assert traces >= 1
        twin = mk(params, 2, programs=eng2.programs)
        assert _parity(twin.run(prompts, max_new_tokens=10,
                                eos_token_id=None), oracle)
        # the shared flat counter proves the twin never retraced
        assert twin.stats()["decode_traces"] == traces

    def test_different_mesh_never_shares(self, tp_platform, params, eng1,
                                         eng2):
        with pytest.raises(ValueError, match="different engine shape"):
            mk(params, 1, programs=eng2.programs)
        with pytest.raises(ValueError, match="different engine shape"):
            mk(params, 2, programs=eng1.programs)

    def test_supervisor_rebuild_reuses_tp_programs(self, tp_platform,
                                                   params, prompts,
                                                   oracle, eng2):
        """A crashed TP replica rebuilds from the dead engine's programs:
        recovery is bit-exact and the flat decode_traces counter proves
        no recompile (the supervisor itself spawned from eng2's shared
        programs — zero compiles in this test)."""
        from paddle_tpu.testing.chaos import engine_crash
        # warm the shared programs at THIS pool shape, then pin the flat
        # counter: the crash rebuild must add zero decode executables
        eng2.run(prompts[:2], max_new_tokens=4, eos_token_id=None)
        before = eng2.programs.stats["decode_traces"]
        sup = EngineSupervisor(params, CFG,
                               ServingConfig(**BASE, tp=2),
                               programs=eng2.programs)
        rids = [sup.submit(p, max_new_tokens=10, eos_token_id=None)
                for p in prompts]
        # at_step=1: the short trace can drain in a single dispatch, so
        # the crash must land on the FIRST step to be guaranteed to fire
        engine_crash(sup, at_step=1)
        while sup.pending:
            sup.step()
        outs = [np.asarray(sup.result(r)) for r in rids]
        assert _parity(outs, oracle)
        assert sup.restarts == 1
        assert sup.engine.stats()["decode_traces"] == before
        assert sup.engine.stats()["tp_degree"] == 2


class TestTPFleet:
    def test_router_of_tp_replicas(self, tp_platform, params, prompts,
                                   oracle, eng2):
        """A PR 9 router fronts a fleet of TP replicas unchanged: both
        replicas spawn from ONE shared program set (zero new compiles —
        flat decode_traces) and serve bit-identically to the TP=1
        oracle."""
        from paddle_tpu.inference.serving import ServingRouter
        eng2.run(prompts[:2], max_new_tokens=4, eos_token_id=None)  # warm
        before = eng2.programs.stats["decode_traces"]
        router = ServingRouter(params, CFG, ServingConfig(**BASE, tp=2),
                               replicas=2, programs=eng2.programs)
        rids = [router.submit(p, max_new_tokens=10, eos_token_id=None)
                for p in prompts]
        while router.pending:
            router.step()
        outs = [np.asarray(router.result(r)) for r in rids]
        assert _parity(outs, oracle)
        assert eng2.programs.stats["decode_traces"] == before
        snap = router.health_snapshot()
        assert snap["counters"]["failed"] == 0
        for part in router.block_partitions().values():
            assert part["in_use"] == 0


class TestTPCapacityAndObservability:
    def test_pool_actually_sharded(self, tp_platform, eng2):
        """Each device holds Hk/tp heads of every block: addressable
        shard bytes are half the global pool, per-chip capacity per
        sequence halves -> the TP capacity multiplier is real, not
        bookkeeping."""
        for leaf in eng2.cache.pool.values():
            shards = leaf.addressable_shards
            assert len(shards) == 2
            assert shards[0].data.shape[2] * 2 == leaf.shape[2]

    def test_block_bytes_arithmetic(self, tp_platform):
        full = G.paged_pool_block_bytes(CFG, 8)
        assert G.paged_pool_block_bytes(CFG, 8, tp=2) * 2 == full
        assert G.paged_pool_block_bytes(CFG, 8, kv_quant="int8", tp=2) * 2 \
            == G.paged_pool_block_bytes(CFG, 8, kv_quant="int8")

    def test_kv_bytes_per_shard(self, tp_platform, eng1, eng2):
        assert eng2.cache.kv_bytes() == \
            eng2.cache.kv_bytes(per_shard=True) * 2
        assert eng1.cache.kv_bytes() == eng1.cache.kv_bytes(per_shard=True)

    def test_snapshot_fields_registered(self, tp_platform, eng2):
        from paddle_tpu.inference.serving import HEALTH_SNAPSHOT_FIELDS
        snap = eng2.health_snapshot()
        st = eng2.stats()
        for payload in (snap, st):
            assert payload["tp_degree"] == 2
            assert payload["kv_pool_shard_bytes"] * 2 == \
                payload["kv_pool_bytes"]
        for field in ("tp_degree", "kv_pool_shard_bytes"):
            assert field in HEALTH_SNAPSHOT_FIELDS
        import json
        json.dumps(snap)     # ops payload stays serializable


class TestTPStructuredErrors:
    def test_indivisible_kv_heads(self, tp_platform, params):
        with pytest.raises(ValueError) as e:
            mk(params, 3)
        assert "num_kv_heads" in str(e.value)
        assert "tp=3" in str(e.value)

    def test_not_enough_devices(self, tp_platform, params):
        # Hk = 4 divides 4... ask for more devices than the platform has
        # while keeping divisibility impossible to blame
        too_many = jax.device_count() + 8
        with pytest.raises(ValueError) as e:
            mk(params, too_many)
        msg = str(e.value)
        assert "devices" in msg or "num_kv_heads" in msg

    def test_config_rejects_nonpositive(self, tp_platform):
        with pytest.raises(ValueError, match=">= 1"):
            ServingConfig(**BASE, tp=0)

    def test_shard_dim_spec_structured(self, tp_platform):
        """The sharding-helper satellite: an indivisible dim raises a
        structured error naming the tensor and the mesh axis instead of
        failing inside device_put; the heuristic _shard_spec still SKIPS
        indivisible dims."""
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.sharding import (_shard_spec,
                                                     shard_dim_spec)
        from paddle_tpu.distributed.topology import tp_mesh
        mesh = tp_mesh(2)
        with pytest.raises(ValueError) as e:
            shard_dim_spec((4, 7), mesh, "tp", dim=1, name="pool.k")
        msg = str(e.value)
        assert "pool.k" in msg and "'tp'" in msg and "7" in msg
        # out-of-range dim raises too (the likeliest layout mistake must
        # not silently shard a different axis)
        with pytest.raises(ValueError, match="out of range"):
            shard_dim_spec((4, 8), mesh, "tp", dim=5, name="pool.k_scale")
        # explicit-dim spelling through _shard_spec raises the same way
        with pytest.raises(ValueError, match="pool.k"):
            _shard_spec((4, 7), mesh, "tp", dim=1, name="pool.k")
        # heuristic mode: skip the indivisible dim, shard the next
        assert _shard_spec((7, 4), mesh, "tp") == P(None, "tp")
        assert _shard_spec((7, 7), mesh, "tp") == P()

    def test_pool_specs_structured(self, tp_platform):
        from paddle_tpu.distributed.topology import tp_mesh
        if tp_platform < 4:
            pytest.skip("needs 4 devices")
        mesh = tp_mesh(4)
        bad = llama.LlamaConfig(vocab_size=128, hidden_size=64,
                                intermediate_size=96, num_hidden_layers=1,
                                num_attention_heads=6,
                                num_key_value_heads=6)
        pool = G.init_paged_pool(bad, 4, 8)
        with pytest.raises(ValueError, match="paged_pool.k"):
            G.paged_pool_specs(pool, mesh)
