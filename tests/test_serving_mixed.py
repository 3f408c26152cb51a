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


# ---------------------------------------------------------------------------
# the packed step itself (ISSUE 28), against the plain form kept HERE
# ---------------------------------------------------------------------------

def _plain_mixed_step(params, cfg, tokens, starts, q_lens, block_tables,
                      pool, active, use_kernel=False, lora=None):
    """``paged_mixed_step`` as it stood before it was packed: every
    matmul over all ``[M, Q]`` lanes. The oracle of the packed step."""
    from paddle_tpu.models.llama import _masked_sdpa, _mm, _rms_norm, _rope
    from paddle_tpu.models.lora import lora_delta
    M, Q = tokens.shape
    H, Hk = G._local_heads(cfg, pool)
    D, dt = cfg.head_dim, cfg.dtype
    bs, W = pool["k"].shape[2], block_tables.shape[1]
    C = W * bs
    draft_lens = jnp.maximum(q_lens - 1, 0)
    qi = jnp.arange(Q)
    pos = starts[:, None] + qi[None, :]
    cos, sin = G._row_tables(cfg, pos)
    valid_q = (qi[None, :] <= draft_lens[:, None]) & active[:, None]
    widx = jnp.minimum(pos // bs, W - 1)
    phys = jnp.where(valid_q,
                     jnp.take_along_axis(block_tables, widx, axis=1), 0)
    off = pos % bs
    qcap = jnp.minimum(qi[None, :], draft_lens[:, None])
    kv_mask = jnp.arange(C)[None, None, :] <= (starts[:, None] +
                                               qcap)[:, :, None]
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)

    def body(h, xs):
        lp, pz, ll = G._lora_unpack(xs)
        hh = _rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps,
                       cfg.use_fused_norm)
        q, k, v = (_mm(hh, lp, n, dt) for n in ("wq", "wk", "wv"))
        if ll is not None:
            lids = lora["ids"]
            q = q + lora_delta(hh, ll["qA"], ll["qB"], lids, dt)
            k = k + lora_delta(hh, ll["kA"], ll["kB"], lids, dt)
            v = v + lora_delta(hh, ll["vA"], ll["vB"], lids, dt)
        q = _rope(q.reshape(M, Q, H, D), cos, sin, False)
        k = _rope(k.reshape(M, Q, Hk, D), cos, sin, False)
        pz, _, _ = G._kv_store(pz, phys, off, k, v.reshape(M, Q, Hk, D))
        if use_kernel:
            from paddle_tpu.kernels.paged_attention import paged_attention
            o = paged_attention(q, pz["k"], pz["v"], block_tables, starts,
                                draft_lens=draft_lens,
                                k_scale=pz.get("k_scale"),
                                v_scale=pz.get("v_scale"))
        else:
            kk, vv = G._kv_gather(pz, block_tables, M, C, Hk, D)
            o = _masked_sdpa(q, kk, vv, kv_mask)
        m = G._merge_heads(o, cfg).astype(dt)
        d = _mm(m, lp, "wo", dt)
        if ll is not None:
            d = d + lora_delta(m, ll["oA"], ll["oB"], lora["ids"], dt)
        return G._ffn_tail(lp, h + d, cfg)[0], pz

    x, pool = jax.lax.scan(body, x, G._lora_xs(params, pool, lora))
    last = jnp.take_along_axis(x, draft_lens[:, None, None], axis=1)
    return G._lm_head(params, cfg, last), pool


# M = 3 slots x Q = 32: a wave is min(96, 16 x 3) = 48 lanes, two at most.
# name -> (starts, q_lens, active, adapter ids or None, waves)
PACKED_CASES = {
    "all_rows_decoding": ([5, 17, 30], [1, 1, 1], [1, 1, 1], None, 1),
    "one_chunk_beside_decoding_rows":
        ([9, 8, 3], [1, 20, 1], [1, 1, 1], None, 1),
    "more_real_lanes_than_one_wave":          # 49: the cut between rows
        ([0, 4, 7], [32, 16, 1], [1, 1, 1], None, 2),
    "a_chunk_cut_by_a_waves_edge":            # row 1: 16 lanes | 4 lanes
        ([8, 12, 40], [32, 20, 1], [1, 1, 1], None, 2),
    "an_inactive_row_and_full_chunks":        # row 2: 16 lanes | 16 lanes
        ([0, 3, 16], [32, 5, 32], [1, 0, 1], None, 2),
    "an_adapter_a_row": ([2, 11, 6], [20, 1, 32], [1, 1, 1], [1, 0, 2], 2),
}
_PACKED_M, _PACKED_Q, _PACKED_BS, _PACKED_W = 3, 32, 4, 18
_PACKED_FNS = {}


# pool kind -> (the model's dtype, the pool's quantisation, what the two
# forms may differ by on the logits and on the pool's entries). A matmul
# over other rows rounds its sums in another order (1e-6 in float32, a
# last bit of bfloat16 that the layers carry on); an int8 entry next to a
# rounding edge may then land one step away.
PACKED_POOLS = {"f32": (jnp.float32, None, 3e-5, 3e-5),
                "bf16": (jnp.bfloat16, None, 0.1, 0.1),
                "int8": (jnp.float32, "int8", 5e-3, 1)}


def _packed_fixture(pool_kind, use_kernel, with_lora):
    """The model, a pool full of history, and both jitted forms, built
    once a (pool kind, attention path, adapters or none)."""
    key = (pool_kind, use_kernel, with_lora)
    if key in _PACKED_FNS:
        return _PACKED_FNS[key]
    dtype, kv_quant = PACKED_POOLS[pool_kind][:2]
    cfg = tiny_cfg(dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(5))
    M, bs, W = _PACKED_M, _PACKED_BS, _PACKED_W
    pool = G.init_paged_pool(cfg, 1 + M * W, bs, kv_quant=kv_quant)
    rng = np.random.default_rng(7)
    # every block holds finite history, as after earlier dispatches
    pool = {n: (jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
                if a.dtype == jnp.int8 else
                jnp.asarray(rng.uniform(0.01, 0.03, a.shape), a.dtype)
                if n.endswith("_scale") else
                jnp.asarray(rng.standard_normal(a.shape), a.dtype))
            for n, a in pool.items()}
    tables = jnp.asarray(1 + np.arange(M * W).reshape(M, W), jnp.int32)
    layers = None
    if with_lora:
        layers = {}
        for n, s in lora_init_params(cfg, 4).items():
            stack = rng.standard_normal((s.shape[0], 3) + s.shape[1:]) * 0.3
            stack[:, 0] = 0                   # slot 0: the base adapter
            layers[n] = jnp.asarray(stack, jnp.float32)

    def run(step):
        def fn(tokens, starts, q_lens, active, ids):
            lora = {"ids": ids, "layers": layers} if with_lora else None
            return step(params, cfg, tokens, starts, q_lens, tables, pool,
                        active, use_kernel=use_kernel, lora=lora)
        return jax.jit(fn)

    _PACKED_FNS[key] = (cfg, run(_plain_mixed_step),
                        run(G.paged_mixed_step))
    return _PACKED_FNS[key]


class TestPackedMixedStep:
    @pytest.mark.parametrize("pool_kind", sorted(PACKED_POOLS))
    @pytest.mark.parametrize("use_kernel", [False, True])
    @pytest.mark.parametrize("case", sorted(PACKED_CASES))
    def test_logits_and_pool_equal_the_unpacked_forwards(
            self, case, use_kernel, pool_kind):
        starts, q_lens, active, ids, waves = PACKED_CASES[case]
        cfg, plain, packed = _packed_fixture(pool_kind, use_kernel,
                                             ids is not None)
        logit_tol, pool_tol = PACKED_POOLS[pool_kind][2:]
        M, Q = _PACKED_M, _PACKED_Q
        rng = np.random.default_rng(len(case))
        ops = (jnp.asarray(rng.integers(0, cfg.vocab_size, (M, Q)),
                           jnp.int32),
               jnp.asarray(starts, jnp.int32), jnp.asarray(q_lens, jnp.int32),
               jnp.asarray(active, bool),
               jnp.asarray(ids if ids is not None else [0] * M, jnp.int32))
        want, want_pool = plain(*ops)
        got, got_pool, counts = packed(*ops)
        rows = np.flatnonzero(active)
        np.testing.assert_allclose(np.asarray(got)[rows],
                                   np.asarray(want)[rows], rtol=0,
                                   atol=logit_tol)
        # the null block (0) takes the pad lanes' writes: any lane's
        for n in want_pool:
            np.testing.assert_allclose(
                np.asarray(got_pool[n][:, 1:], np.float32),
                np.asarray(want_pool[n][:, 1:], np.float32), rtol=1e-5,
                atol=1e-5 if n.endswith("_scale") else pool_tol, err_msg=n)
        Tw = min(M * Q, G._WAVE_ROWS * M)
        assert dict(zip(G.PAGED_COUNTERS, np.asarray(counts).tolist())) == {
            "lanes_computed": waves * Tw, "mixed_waves": waves}

    def test_engine_counts_the_lanes_its_waves_ran(self):
        """M = 2, chunks of 32: a wave is 32 lanes. Two prompts of whole
        chunks keep every mixed step in the Q = 32 bucket; while both
        prefill a step holds 64 real lanes, two waves. Token streams equal
        the two-phase engine's."""
        cfg = tiny_cfg(max_position_embeddings=160)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (96, 64, 64)]
        outs = [5, 3, 4]
        kw = dict(max_slots=2, max_model_len=128, prefill_chunk=32,
                  prefix_cache=None)
        want, _ = drain_streams(mk(params, cfg, False, **kw), prompts, outs)
        eng = mk(params, cfg, True, **kw)
        got, st = drain_streams(eng, prompts, outs)
        assert got == want
        c = st["spans"]["counters"]
        assert c["mixed_lanes_total"] == 32 * c["mixed_waves"]
        assert c["mixed_waves"] > st["mixed_dispatches"] > 0
        assert 0 < c["mixed_lanes_real"] <= c["mixed_lanes_total"]
        # every dispatch's lanes: the mixed steps' and the decode loop's
        assert c["lanes_computed"] == (c["mixed_lanes_total"] +
                                       2 * c["decode_iterations"])
        assert eng.health_snapshot()["family"] == {
            "lanes_computed": c["lanes_computed"],
            "mixed_waves": c["mixed_waves"]}
