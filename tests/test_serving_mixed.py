"""Stall-free mixed batching (ISSUE 20): chunked prefill fused into the
decode dispatch as extra query rows of ONE mixed multi-query step.

Oracle discipline: the two-phase engine (``mixed_batch=False`` — byte-
for-byte the pre-ISSUE-20 path) is the bit-parity reference. The mixed
engine must reproduce its token streams EXACTLY across
{fp32, int8 KV} x {kernel, gather} x {greedy, seeded} (TP2 rides
test_serving_tp's mesh via the tp-marked class here), including prefix
hits, preemption recompute, crash resubmit/recovery, and adapters —
with ``recomputed_tokens`` / leak counters unchanged. On top of parity:
spec-decode precedence (a step with drafts dispatches verify, never
mixed), compile-once across admission churn (``decode_traces`` /
``mixed_traces`` flat), and the stall removal itself (decoding slots
advance in the SAME step a new prompt prefills).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import generation as G
from paddle_tpu.models.llama import LlamaConfig, init_params
from paddle_tpu.models.lora import lora_init_params
from paddle_tpu.inference.serving import (EngineSupervisor, ServingConfig,
                                          ServingEngine)
from paddle_tpu.testing import chaos


def tiny_cfg(**kw):
    base = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=96)
    base.update(kw)
    return LlamaConfig(**base)


# chunked prefill armed everywhere: long prompts MUST cross chunk
# boundaries for the mixed path to carry mid-flight prefill rows
BASE = dict(block_size=4, max_slots=3, max_model_len=64, decode_chunk=2,
            queue_depth=16, prefill_chunk=4)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 97, (8,)).astype(np.int32)
    # mixed lengths with several prompts long enough to chunk (> 4),
    # sharing a block-aligned family prefix so prefix hits engage
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 97, (s,)).astype(np.int32)])
               for s in [2, 13, 5, 21, 9, 3]]
    outs = [6, 4, 8, 3, 6, 5]
    return cfg, params, prompts, outs


# donor-programs cache: engines with an identical shape surface share
# one compiled EnginePrograms (the supervisor/fleet sharing path — and
# mixed_batch is deliberately NOT in the program key, so both sides of
# a parity pair share too). Cuts the module's compile bill to one per
# distinct shape key; per-engine parity counters (preemptions, prefix
# hits, ...) live on the scheduler, not the shared stats, so parity
# comparisons are unaffected.
_DONORS = {}


def mk(params, cfg, mixed, **kw):
    sc = dict(BASE)
    sc.update(kw)
    key = tuple(sorted(sc.items()))
    eng = ServingEngine(params, cfg, ServingConfig(mixed_batch=mixed, **sc),
                        programs=_DONORS.get(key))
    _DONORS.setdefault(key, eng.programs)
    return eng


def drain_streams(eng, prompts, outs, max_iters=None, **submit_kw):
    """Submit a wave and drain step-by-step, returning per-rid streams
    plus the stats record (the parity payload)."""
    rids = [eng.submit(p, max_new_tokens=int(n), eos_token_id=None,
                       **submit_kw) for p, n in zip(prompts, outs)]
    acc = {r: [] for r in rids}
    while eng.pending:
        for rid, toks in eng.step(max_iters).items():
            acc[rid].append(toks)
    return [sum(acc[r], []) for r in rids], eng.stats()


PARITY_COUNTERS = ("preemptions", "recomputed_tokens", "prefix_hit_tokens",
                   "oom_truncated", "retired")


class TestMixedParityMatrix:
    """Token streams bit-identical to the two-phase oracle, counters
    unchanged, across the quant x attention-path x sampling matrix."""

    @pytest.mark.parametrize("quantize", [None, "int8"])
    @pytest.mark.parametrize("paged_kernel", [False, True])
    def test_greedy_parity(self, setup, quantize, paged_kernel):
        cfg, params, prompts, outs = setup
        kw = dict(quantize=quantize, paged_kernel=paged_kernel)
        a, sa = drain_streams(mk(params, cfg, False, **kw), prompts, outs)
        b, sb = drain_streams(mk(params, cfg, True, **kw), prompts, outs)
        assert a == b
        assert sb["mixed_dispatches"] > 0      # the path actually ran
        for k in PARITY_COUNTERS:
            assert sa[k] == sb[k], k

    @pytest.mark.parametrize("paged_kernel", [False, True])
    def test_seeded_parity(self, setup, paged_kernel):
        cfg, params, prompts, outs = setup
        kw = dict(temperature=0.8, top_k=25, top_p=0.9, seed=123)
        a, sa = drain_streams(mk(params, cfg, False,
                                 paged_kernel=paged_kernel),
                              prompts, outs, **kw)
        b, sb = drain_streams(mk(params, cfg, True,
                                 paged_kernel=paged_kernel),
                              prompts, outs, **kw)
        assert a == b
        assert sb["mixed_dispatches"] > 0
        for k in PARITY_COUNTERS:
            assert sa[k] == sb[k], k

    def test_prefix_hit_parity(self, setup):
        """A second identical wave prefix-hits: suffixes enter mid-offset
        chunked prefill — exactly the rows the mixed dispatch carries —
        and streams still match the oracle's second wave."""
        cfg, params, prompts, outs = setup
        ea, eb = mk(params, cfg, False), mk(params, cfg, True)
        a1, _ = drain_streams(ea, prompts, outs)
        a2, sa = drain_streams(ea, prompts, outs)
        b1, _ = drain_streams(eb, prompts, outs)
        b2, sb = drain_streams(eb, prompts, outs)
        assert (a1, a2) == (b1, b2)
        assert sa["prefix_hit_tokens"] == sb["prefix_hit_tokens"] > 0

    def test_preemption_recompute_parity(self, setup):
        """An undersized pool forces preempt-and-recompute in BOTH modes:
        streams stay bit-identical and the recompute counters match
        exactly. Driven at step(1) so both modes advance decode one
        iteration per step — the per-step KV state evolves identically,
        so the planner/preemption ladder (shared code) fires at the SAME
        instants with the SAME victims."""
        cfg, params, prompts, outs = setup
        kw = dict(num_blocks=14, prefix_cache=None)
        a, sa = drain_streams(mk(params, cfg, False, **kw), prompts, outs,
                              max_iters=1)
        b, sb = drain_streams(mk(params, cfg, True, **kw), prompts, outs,
                              max_iters=1)
        assert a == b
        assert sa["preemptions"] == sb["preemptions"] >= 1
        assert sa["recomputed_tokens"] == sb["recomputed_tokens"] > 0
        for eng_mode, st in (("unmixed", sa), ("mixed", sb)):
            assert st["free_blocks"] == 13, eng_mode   # zero leaked

    def test_adapter_parity(self, setup):
        cfg, params, prompts, outs = setup
        adapters = {f"a{i}": lora_init_params(cfg, 4, seed=i, scale=0.5)
                    for i in range(2)}
        ids = ["a0", None, "a1", "a0", None, "a1"]
        streams = {}
        for mixed in (False, True):
            eng = mk(params, cfg, mixed, lora_rank=4, lora_slots=2,
                     lora_pool=8)
            for name, ap in adapters.items():
                eng.register_adapter(name, ap)
            rids = [eng.submit(p, max_new_tokens=int(n),
                               eos_token_id=None, adapter_id=a)
                    for p, n, a in zip(prompts, outs, ids)]
            while eng.pending:
                eng.step()
            streams[mixed] = [list(eng.request(r).output()) for r in rids]
            if mixed:
                assert eng.stats()["mixed_dispatches"] > 0
        assert streams[False] == streams[True]

    def test_crash_resubmit_recovery_parity(self, setup):
        """Crash mid-trace under a supervisor in BOTH modes: the rebuilt
        engine's resubmit/recompute path must land every stream on the
        same tokens (and mixed-mode recovery re-chunks mid-prefill
        prompts through the mixed dispatch)."""
        cfg, params, prompts, outs = setup
        streams = {}
        for mixed in (False, True):
            sup = EngineSupervisor(params, cfg,
                                   ServingConfig(mixed_batch=mixed,
                                                 **BASE))
            srids = [sup.submit(p, max_new_tokens=int(n),
                                eos_token_id=None)
                     for p, n in zip(prompts, outs)]
            assert sup.step(2) is not None and sup.pending
            chaos.engine_crash(sup, at_step=1)
            assert sup.step(2) == {}        # the crashed iteration
            assert sup.restarts == 1
            while sup.pending:
                sup.step(2)
            streams[mixed] = [list(sup.result(s)) for s in srids]
            if mixed:
                assert sup.engine.stats()["mixed_dispatches"] > 0
        assert streams[False] == streams[True]


@pytest.mark.tp
class TestMixedParityTP:
    def test_tp2_parity(self, setup, tp_platform):
        cfg = tiny_cfg(num_attention_heads=4, num_key_value_heads=2)
        params = init_params(cfg, jax.random.PRNGKey(3))
        _, _, prompts, outs = setup
        streams = {}
        for mixed in (False, True):
            for tp in (1, 2):
                eng = mk(params, cfg, mixed, tp=tp)
                got, st = drain_streams(eng, prompts, outs)
                streams[(mixed, tp)] = got
                if mixed:
                    assert st["mixed_dispatches"] > 0
        assert len({tuple(map(tuple, v)) for v in streams.values()}) == 1


class TestMixedDispatchShape:
    def test_spec_decode_precedence(self, setup):
        """A step whose decode rows carry drafts dispatches VERIFY, never
        mixed+verify in one step — and with a prompt mid-prefill the
        draft-less steps dispatch mixed. The two counters never move
        together within one step."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg, True, spec_decode=3, spec_ngram=2,
                 max_model_len=256, prefill_chunk=16)
        # a prompt in which prompt lookup MUST find a draft, whatever the
        # model emits: "c 0 c 1 c 2 ... c V-1 c" holds the bigram (c, x)
        # for every token x, so the context's tail (c, first token) has
        # occurred before, with a continuation to draft from. (A prompt
        # seeded with the toy model's own greedy stream only drafts if
        # that stream happens to repeat an n-gram.)
        c = 5
        rep = np.full((2 * cfg.vocab_size + 1,), c, np.int32)
        rep[1::2] = np.arange(cfg.vocab_size)
        eng.submit(rep, max_new_tokens=8, eos_token_id=None)
        while eng.pending:
            before = eng.stats()
            eng.step()
            after = eng.stats()
            d_spec = after["spec_dispatches"] - before["spec_dispatches"]
            d_mixed = after["mixed_dispatches"] - before["mixed_dispatches"]
            assert d_spec + d_mixed <= 1      # never both in one step
        st = eng.stats()
        assert st["spec_dispatches"] > 0      # drafts did fire
        # now a long prompt mid-prefill alongside the draft-capable row:
        # steps with drafts verify, steps without carry the chunk mixed
        eng.submit(rep, max_new_tokens=8, eos_token_id=None)
        eng.submit(prompts[3], max_new_tokens=4, eos_token_id=None)
        saw_mixed = saw_spec = False
        while eng.pending:
            before = eng.stats()
            eng.step()
            after = eng.stats()
            d_spec = after["spec_dispatches"] - before["spec_dispatches"]
            d_mixed = after["mixed_dispatches"] - before["mixed_dispatches"]
            assert d_spec + d_mixed <= 1
            saw_mixed |= d_mixed > 0
            saw_spec |= d_spec > 0
        assert saw_mixed and saw_spec

    def test_compile_once_across_admission_churn(self, setup):
        """Role churn (slots flipping prefill <-> decode as prompts admit
        and retire) never retraces: per-row start/q_len are device
        operands, so one trace per Q bucket serves every mix. Chunk
        sizes here stay inside ONE bucket (prefill_chunk=4 -> Q=8), so
        both trace counters go exactly flat after the first wave."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg, True)
        drain_streams(eng, prompts, outs)
        st = eng.stats()
        assert st["mixed_traces"] == 1
        d0, m0 = st["decode_traces"], st["mixed_traces"]
        # staggered second wave: admissions land while others decode
        rids = []
        for i, (p, n) in enumerate(zip(prompts, outs)):
            rids.append(eng.submit(p, max_new_tokens=int(n),
                                   eos_token_id=None))
            eng.step()
        while eng.pending:
            eng.step()
        st = eng.stats()
        assert st["decode_traces"] == d0
        assert st["mixed_traces"] == m0 == 1

    def test_decode_advances_while_prompt_prefills(self, setup):
        """The stall this PR removes, pinned directly: in the SAME
        engine step that a newly admitted long prompt advances its
        prefill chunk, an already-decoding slot emits its next token
        (two-phase mode stalls the decoder behind the chunk dispatches
        and the decode_chunk clamp instead)."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg, True)
        r0 = eng.submit(prompts[0], max_new_tokens=12, eos_token_id=None)
        eng.step()                             # r0 admits
        req0 = next(r for r in eng._sched.live if r.rid == r0)
        while req0.prefilling:                 # chunk through its prompt
            eng.step()
        assert req0.tokens                     # decoding now
        long_p = prompts[3]                    # 29 tokens: many chunks
        r1 = eng.submit(long_p, max_new_tokens=2, eos_token_id=None)
        eng.step()                             # r1 admits (queue -> slot)
        req1 = next(r for r in eng._sched.live if r.rid == r1)
        saw_same_step = 0
        while req1.prefilling:
            before = len(req0.tokens)
            computed = req1.num_computed
            em = eng.step()
            if req1.num_computed > computed and len(req0.tokens) > before:
                saw_same_step += 1
                assert em.get(r0)              # and it was delivered
        assert saw_same_step >= 2
        st = eng.stats()
        assert st["mixed_dispatches"] >= saw_same_step

    def test_flag_default_and_override(self):
        assert ServingConfig(**BASE).mixed_batch is True
        assert ServingConfig(mixed_batch=False, **BASE).mixed_batch \
            is False

    def test_programs_shared_across_flag_values(self, setup):
        """EnginePrograms carry jmixed keyed like the others: a two-phase
        engine's programs rebuild a mixed engine (and vice versa) with
        zero new traces — the supervisor/router shared-program contract."""
        cfg, params, prompts, outs = setup
        donor = mk(params, cfg, False)
        a, _ = drain_streams(donor, prompts, outs)
        eng = ServingEngine(params, cfg,
                            ServingConfig(mixed_batch=True, **BASE),
                            programs=donor.programs)
        b, st = drain_streams(eng, prompts, outs)
        assert a == b
        assert st["mixed_dispatches"] > 0
        assert st["mixed_traces"] == 1         # first mixed use traces it
