"""The mixed step (ISSUE 20): chunked prefill rides the decode dispatch
as extra query rows of ONE multi-query step. It is the only way the
engine steps, so its oracles are outside the engine:

1. dense ``generate()`` (``dense_rows``) for greedy float32 streams, on
   the gather path and the kernel: code that shares no paged function;
2. **the request served alone** (``served_alone``): the same
   ``ServingConfig``, the request submitted by itself to a fresh engine
   (cold cache, no neighbour, never preempted). A reply does not depend
   on who shares the step, so its stream in the wave is its stream
   alone, bit for bit: int8 KV, seeded sampling, adapters, a second wave
   that prefix-hits, a preempted and recomputed request, one resubmitted
   after a crash, TP2;
3. counters against what the scenario itself implies.

On top of those: spec-decode precedence (a step with drafts dispatches
verify, never mixed), compile-once across admission churn
(``decode_traces`` / ``mixed_traces`` flat), decoding slots advancing in
the SAME step a new prompt prefills, and the decode loop never sized
with a row mid-prefill.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import generation as G
from paddle_tpu.models.llama import LlamaConfig, init_params, quantize_params
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
PREFIX = 8          # tokens every prompt of the trace shares: two blocks


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 97, (PREFIX,)).astype(np.int32)
    # mixed lengths, every prompt long enough to chunk (> 4), sharing a
    # block-aligned family prefix so prefix hits engage
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 97, (s,)).astype(np.int32)])
               for s in [2, 13, 5, 21, 9, 3]]
    outs = [6, 4, 8, 3, 6, 5]
    return cfg, params, prompts, outs


def dense_rows(params, cfg, prompts, outs):
    return [np.asarray(G.generate(params, jnp.asarray(p[None]), cfg,
                                  max_new_tokens=int(n)))[0].tolist()
            for p, n in zip(prompts, outs)]


@pytest.fixture(scope="module")
def dense(setup):
    """The trace's greedy float32 streams by ``generate()`` on a dense
    cache, one request a call."""
    cfg, params, prompts, outs = setup
    return dense_rows(params, cfg, prompts, outs)


# donor-programs cache: engines with an identical shape surface share
# one compiled EnginePrograms (the supervisor/fleet sharing path). Cuts
# the module's compile bill to one per distinct shape key; a wave's
# engine and its requests' served-alone engines share too. Per-engine
# counters (preemptions, prefix hits, ...) live on the scheduler, not
# the shared stats.
_DONORS = {}


def mk(params, cfg, **kw):
    sc = dict(BASE)
    sc.update(kw)
    key = tuple(sorted(sc.items()))
    eng = ServingEngine(params, cfg, ServingConfig(**sc),
                        programs=_DONORS.get(key))
    _DONORS.setdefault(key, eng.programs)
    return eng


def drain_streams(eng, prompts, outs, max_iters=None, adapters=None,
                  **submit_kw):
    """Submit a wave and drain step-by-step, returning per-rid streams
    plus the stats record."""
    adapters = adapters or [None] * len(prompts)
    rids = [eng.submit(p, max_new_tokens=int(n), eos_token_id=None,
                       adapter_id=a, **submit_kw)
            for p, n, a in zip(prompts, outs, adapters)]
    acc = {r: [] for r in rids}
    while eng.pending:
        for rid, toks in eng.step(max_iters).items():
            acc[rid].append(toks)
    return [sum(acc[r], []) for r in rids], eng.stats()


def served_alone(make, prompts, outs, adapters=None, **submit_kw):
    """Each request's stream when it is the only one its engine ever
    sees: ``make()`` builds a fresh engine of the wave's own
    ``ServingConfig`` (cold cache, no neighbour; one request never
    preempts itself)."""
    adapters = adapters or [None] * len(prompts)
    streams = []
    for p, n, a in zip(prompts, outs, adapters):
        (got,), st = drain_streams(make(), [p], [n], adapters=[a],
                                   **submit_kw)
        assert (st["preemptions"], st["prefix_hit_tokens"]) == (0, 0)
        streams.append(got)
    return streams


def later_admissions_hits(prompts, slots):
    """Prefix-hit tokens of ONE cold wave on ``slots`` slots: the first
    ``slots`` requests admit together against an empty cache; each later
    one admits into a slot a finished request freed, so the shared prefix
    is registered by then and it hits all of it."""
    return PREFIX * (len(prompts) - slots)


class TestMixedParityMatrix:
    """Token streams of a wave on the mixed step, across the quant x
    attention-path x sampling matrix and the paging scenarios, against
    dense ``generate()`` and the request served alone; counters against
    what the scenario implies."""

    @pytest.mark.parametrize("quantize", [None, "int8"])
    @pytest.mark.parametrize("paged_kernel", [False, True])
    def test_greedy_parity(self, setup, dense, quantize, paged_kernel):
        cfg, params, prompts, outs = setup
        kw = dict(quantize=quantize, paged_kernel=paged_kernel)
        got, st = drain_streams(mk(params, cfg, **kw), prompts, outs)
        assert got == (dense_rows(quantize_params(params), cfg, prompts, outs)
                       if quantize else dense)
        assert st["mixed_dispatches"] > 0      # the path actually ran
        assert st["retired"] == len(prompts)
        assert st["prefix_hit_tokens"] == later_admissions_hits(prompts, 3)
        assert (st["preemptions"], st["recomputed_tokens"],
                st["oom_truncated"]) == (0, 0, 0)

    @pytest.mark.parametrize("paged_kernel", [False, True])
    def test_seeded_parity(self, setup, paged_kernel):
        cfg, params, prompts, outs = setup
        kw = dict(temperature=0.8, top_k=25, top_p=0.9, seed=123)

        def make():
            return mk(params, cfg, paged_kernel=paged_kernel)
        got, st = drain_streams(make(), prompts, outs, **kw)
        assert got == served_alone(make, prompts, outs, **kw)
        assert st["mixed_dispatches"] > 0
        assert st["retired"] == len(prompts)
        assert st["prefix_hit_tokens"] == later_admissions_hits(prompts, 3)
        assert (st["preemptions"], st["recomputed_tokens"],
                st["oom_truncated"]) == (0, 0, 0)

    def test_prefix_hit_parity(self, setup, dense):
        """A second identical wave prefix-hits: suffixes enter mid-offset
        chunked prefill — exactly the rows the mixed dispatch carries —
        and both waves' streams are the dense ones and the requests'
        own, served alone on a cold cache."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg)
        first, s1 = drain_streams(eng, prompts, outs)
        second, s2 = drain_streams(eng, prompts, outs)
        assert first == second == dense
        assert second == served_alone(lambda: mk(params, cfg), prompts,
                                      outs)
        # the second wave finds every prompt of the first in the cache
        # (the pool holds them all): each request hits its own prompt's
        # whole blocks, short of the last token, which it must compute
        bs = BASE["block_size"]
        assert s1["prefix_hit_tokens"] == later_admissions_hits(prompts, 3)
        assert s2["prefix_hit_tokens"] - s1["prefix_hit_tokens"] == sum(
            (len(p) - 1) // bs * bs for p in prompts)
        assert s2["retired"] == 2 * len(prompts)

    def test_preemption_recompute_parity(self, setup, dense):
        """An undersized pool forces preempt-and-recompute: every stream,
        the victims' too, is the dense one and the one served alone, and
        ``recomputed_tokens`` is the KV the victims held when they were
        preempted (no prefix cache here, so a readmission recomputes all
        of it)."""
        cfg, params, prompts, outs = setup
        kw = dict(num_blocks=14, prefix_cache=None)
        eng = mk(params, cfg, **kw)
        held = []                      # KV entries of each victim
        preempt = eng._preempt

        def spy(req):
            held.append(req.num_computed if req.prefilling
                        else int(eng._seq_lens[req.slot]))
            preempt(req)
        eng._preempt = spy
        got, st = drain_streams(eng, prompts, outs, max_iters=1)
        assert got == dense
        assert got == served_alone(lambda: mk(params, cfg, **kw), prompts,
                                   outs)
        assert st["preemptions"] == len(held) >= 1
        assert st["recomputed_tokens"] == sum(held) > 0
        assert st["retired"] == len(prompts)
        assert (st["prefix_hit_tokens"], st["oom_truncated"]) == (0, 0)
        assert st["free_blocks"] == 13             # zero leaked

    def test_adapter_parity(self, setup, dense):
        """A wave of mixed adapters: each reply is the one its request
        gets alone with its adapter (the merged-weights dense oracle of
        the adapters themselves is tests/test_lora.py's)."""
        cfg, params, prompts, outs = setup
        adapters = {f"a{i}": lora_init_params(cfg, 4, seed=i, scale=0.5)
                    for i in range(2)}
        ids = ["a0", None, "a1", "a0", None, "a1"]

        def make():
            eng = mk(params, cfg, lora_rank=4, lora_slots=2, lora_pool=8)
            for name, ap in adapters.items():
                eng.register_adapter(name, ap)
            return eng
        got, st = drain_streams(make(), prompts, outs, adapters=ids)
        assert st["mixed_dispatches"] > 0
        assert got == served_alone(make, prompts, outs, adapters=ids)
        for g, w, a in zip(got, dense, ids):
            assert (g == w) == (a is None)         # adapters did bite

    def test_crash_resubmit_recovery_parity(self, setup, dense):
        """Crash mid-trace under a supervisor: the rebuilt engine's
        resubmit/recompute path (which re-chunks mid-prefill prompts
        through the mixed dispatch) lands every stream on the dense
        tokens, which are the requests' own served alone."""
        cfg, params, prompts, outs = setup
        sup = EngineSupervisor(params, cfg, ServingConfig(**BASE))
        srids = [sup.submit(p, max_new_tokens=int(n), eos_token_id=None)
                 for p, n in zip(prompts, outs)]
        assert sup.step(2) is not None and sup.pending
        chaos.engine_crash(sup, at_step=1)
        assert sup.step(2) == {}        # the crashed iteration
        assert sup.restarts == 1
        while sup.pending:
            sup.step(2)
        got = [list(sup.result(s)) for s in srids]
        assert sup.engine.stats()["mixed_dispatches"] > 0
        assert got == dense
        assert got == served_alone(lambda: mk(params, cfg), prompts, outs)


class TestServedAlone:
    @pytest.mark.parametrize("sampling", ["greedy", "seeded"])
    @pytest.mark.parametrize("paged_kernel", [False, True],
                             ids=["gather", "kernel"])
    @pytest.mark.parametrize("kv_quant", [None, "int8"],
                             ids=["float32", "int8kv"])
    def test_a_reply_does_not_depend_on_who_shares_the_step(
            self, setup, dense, kv_quant, paged_kernel, sampling):
        """Each request of a wave (three slots, six requests, chunks
        beside decoding rows, later admissions on a warm prefix) streams
        what it streams alone on a cold engine: a neighbour's rows, keys
        or cache leaking into a reply changes it. Seeds differ a request,
        so a key drawn from the wrong row shows. The greedy float32 cases
        are held to dense ``generate()`` as well."""
        cfg, params, prompts, outs = setup

        def make():
            return mk(params, cfg, kv_quant=kv_quant,
                      paged_kernel=paged_kernel)
        eng = make()
        rids, want = [], []
        for i, (p, n) in enumerate(zip(prompts, outs)):
            kw = (dict(temperature=0.9, top_k=30, top_p=0.95, seed=1000 + i)
                  if sampling == "seeded" else {})
            rids.append(eng.submit(p, max_new_tokens=int(n),
                                   eos_token_id=None, **kw))
            want += served_alone(make, [p], [n], **kw)
        while eng.pending:
            eng.step()
        got = [list(eng.request(r).output()) for r in rids]
        assert got == want
        st = eng.stats()
        assert st["mixed_dispatches"] > 0 and st["decode_dispatches"] > 0
        assert st["prefix_hit_tokens"] == later_admissions_hits(prompts, 3)
        if sampling == "greedy" and kv_quant is None:
            assert got == dense
        elif sampling == "seeded":
            assert got != dense


@pytest.mark.tp
class TestMixedParityTP:
    def test_tp2_parity(self, setup, tp_platform):
        """The wave at tp=1 and on the two-way mesh: dense ``generate()``
        streams both, and at tp=2 each request's own served alone."""
        cfg = tiny_cfg(num_attention_heads=4, num_key_value_heads=2)
        params = init_params(cfg, jax.random.PRNGKey(3))
        _, _, prompts, outs = setup
        want = dense_rows(params, cfg, prompts, outs)
        for tp in (1, 2):
            got, st = drain_streams(mk(params, cfg, tp=tp), prompts, outs)
            assert st["mixed_dispatches"] > 0
            assert got == want, tp
        assert got == served_alone(lambda: mk(params, cfg, tp=2), prompts,
                                   outs)


class TestMixedDispatchShape:
    def test_spec_decode_precedence(self, setup):
        """A step whose decode rows carry drafts dispatches VERIFY, never
        mixed+verify in one step — and with a prompt mid-prefill the
        draft-less steps dispatch mixed. The two counters never move
        together within one step."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg, spec_decode=3, spec_ngram=2,
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
        eng = mk(params, cfg)
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
        """In the SAME engine step that a newly admitted long prompt
        advances its prefill chunk, an already-decoding slot emits its
        next token: a long admission stalls nobody."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg)
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

    @pytest.mark.parametrize("kw, max_iters", [
        (dict(), None), (dict(), 1),
        (dict(num_blocks=14, prefix_cache=None), 1),
        (dict(spec_decode=3, spec_ngram=2), None),
        (dict(preempt=False), None)],
        ids=["drain", "stream", "preempting", "spec", "reserved"])
    def test_decode_loop_is_sized_with_no_row_prefilling(self, setup, kw,
                                                         max_iters):
        """``_limit`` sizes the decode loop from the decoding rows and the
        queue alone: a step with a row mid-prefill dispatches the mixed
        step (or a verify) and never asks it. Held on a drained wave and
        on a staggered one with EOS on, whose admissions land while
        others decode."""
        cfg, params, prompts, outs = setup
        eng = mk(params, cfg, **kw)
        seen = []
        limit = eng._limit

        def spy(decoding, mi):
            seen.append(any(r.prefilling for r in eng._sched.live))
            return limit(decoding, mi)
        eng._limit = spy
        drain_streams(eng, prompts, outs, max_iters=max_iters)
        for p, n in zip(prompts, outs):
            eng.submit(p, max_new_tokens=int(n), eos_token_id=5)
            eng.step(max_iters)
        while eng.pending:
            eng.step(max_iters)
        assert seen and not any(seen)

    def test_there_is_one_way_to_step(self):
        """No field of ``ServingConfig`` and no flag selects a scheduler:
        23 fields, none of them about mixing, and the name the switch
        had is an unknown argument, like any other."""
        from paddle_tpu.flags import get_flags
        names = {f.name for f in dataclasses.fields(ServingConfig)}
        assert len(names) == 23
        assert not [n for n in [*names, *get_flags()] if "mixed" in n]
        with pytest.raises(TypeError):
            ServingConfig(**{"mixed" + "_batch": True}, **BASE)


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

    def store(p, k, v):
        """One layer's slab ``[N, bs, Hk, D]`` with the new entries in."""
        out = dict(p)
        if "k_scale" in p:
            (k, sk), (v, sv) = G._kv_quantize(k), G._kv_quantize(v)
            out["k_scale"] = p["k_scale"].at[phys, off].set(sk)
            out["v_scale"] = p["v_scale"].at[phys, off].set(sv)
        out["k"] = p["k"].at[phys, off].set(k.astype(p["k"].dtype))
        out["v"] = p["v"].at[phys, off].set(v.astype(p["v"].dtype))
        return out

    def body(h, xs):
        # the pool a layer through the scan's xs and ys, as every paged
        # program of this family carried it until ISSUE 30
        lp, pz, ll = xs
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
        pz = store(pz, k, v.reshape(M, Q, Hk, D))
        if use_kernel:
            from paddle_tpu.kernels.paged_attention import paged_attention
            o = paged_attention(q, pz["k"], pz["v"], block_tables, starts,
                                draft_lens=draft_lens,
                                k_scale=pz.get("k_scale"),
                                v_scale=pz.get("v_scale"))
        else:
            kk, vv = G._kv_gather({n: a[None] for n, a in pz.items()}, 0,
                                  block_tables, M, C, Hk, D)
            o = _masked_sdpa(q, kk, vv, kv_mask)
        m = G._merge_heads(o, cfg).astype(dt)
        d = _mm(m, lp, "wo", dt)
        if ll is not None:
            d = d + lora_delta(m, ll["oA"], ll["oB"], lora["ids"], dt)
        return G._ffn_tail(lp, h + d, cfg)[0], pz

    x, pool = jax.lax.scan(body, x, (
        params["layers"], pool, None if lora is None else lora["layers"]))
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
        prefill a step holds 64 real lanes, two waves. Token streams are
        dense ``generate()``'s."""
        cfg = tiny_cfg(max_position_embeddings=160)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
                   for n in (96, 64, 64)]
        outs = [5, 3, 4]
        kw = dict(max_slots=2, max_model_len=128, prefill_chunk=32,
                  prefix_cache=None)
        eng = mk(params, cfg, **kw)
        got, st = drain_streams(eng, prompts, outs)
        assert got == dense_rows(params, cfg, prompts, outs)
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


# ---------------------------------------------------------------------------
# the pool is loop state of ONE buffer, never a scanned operand (ISSUE 30)
# ---------------------------------------------------------------------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, those inside loops, branches
    and calls too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _pool_program(name, pool_kind, use_kernel):
    """(a paged program of the family as ``fn(pool)``, the pool), toy size."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    M, Q, bs, W = 3, 8, 4, 6
    pool = G.init_paged_pool(cfg, 1 + M * W, bs, kv_quant=pool_kind)
    tables = jnp.asarray(1 + np.arange(M * W).reshape(M, W), jnp.int32)
    tokens = jnp.ones((M, Q), jnp.int32)
    lens = jnp.asarray([3, 1, 5], jnp.int32)
    active = jnp.ones((M,), bool)
    programs = {
        "paged_prefill": lambda pool: G.paged_prefill(
            params, cfg, tokens, lens, tables, pool, active,
            use_kernel=use_kernel),
        "paged_decode_step": lambda pool: G.paged_decode_step(
            params, cfg, tokens[:, 0], lens, tables, pool, active,
            use_kernel=use_kernel),
        "paged_mixed_step": lambda pool: G.paged_mixed_step(
            params, cfg, tokens, lens, lens, tables, pool, active,
            use_kernel=use_kernel),
        "paged_spec_step": lambda pool: G.paged_spec_step(
            params, cfg, tokens, lens, lens, tables, pool, active,
            use_kernel=use_kernel)}
    return programs[name], pool


class TestPoolStaysOutOfTheLayerScan:
    @pytest.mark.parametrize("pool_kind", [None, "int8"])
    @pytest.mark.parametrize("use_kernel", [False, True])
    @pytest.mark.parametrize("name", ["paged_prefill", "paged_decode_step",
                                      "paged_mixed_step", "paged_spec_step"])
    def test_the_pool_is_carried_whole(self, name, use_kernel, pool_kind):
        """In the program's jaxpr no scan has an ``xs`` or a ``ys`` of a
        pool leaf's shape (a scanned operand is another
        buffer than the argument: a layer's slab sliced out and written
        back, the stack copied whole once a loop trip; ``PERF.md`` §6, PR
        30), and every leaf of the pool is among the layer scan's
        carries."""
        fn, pool = _pool_program(name, pool_kind, use_kernel)
        leaves = sorted({a.shape for a in pool.values()})
        shape = lambda v: tuple(v.aval.shape)
        holders = []
        for eqn in _scans(jax.make_jaxpr(fn)(pool).jaxpr):
            p = eqn.params                # the kernel's loops are scans too
            n_in, n_out = p["num_consts"] + p["num_carry"], p["num_carry"]
            scanned = [shape(v) for v in (*eqn.invars[n_in:],
                                          *eqn.outvars[n_out:])]
            assert not [s for s in scanned if s in leaves], scanned
            carries = [shape(v) for v in eqn.outvars[:n_out]]
            if all(s in carries for s in leaves):
                holders.append((p["length"], (p["length"],) in scanned))
        # the layer scan, and no other: the stack's length, a layer's index
        assert holders == [(tiny_cfg().num_hidden_layers, True)]
