"""The ``pangu_ultra_moe`` family at a toy size on the CPU, float32: the
program against the benchmark's plain reference, the paged path through
the latent cache, the absorbed form against the expanded one, the shares
of an expert-parallel layer adding up to the uncut layer, dropless
routing, the latent kernel against a dense gather, and the counters by
hand."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness                                   # noqa: E402
from paddle_tpu.inference.serving import (ServingConfig,        # noqa: E402
                                          ServingEngine)
from paddle_tpu.kernels import (grouped_matmul,                 # noqa: E402
                                paged_attention_latent)
from paddle_tpu.models import (generation, paged_family,        # noqa: E402
                               pangu_ultra_moe as P)
from paddle_tpu.models.llama import LlamaConfig                 # noqa: E402

ROOTS = [os.path.join(REPO, "benchmark")]
model = harness.load_by_name("models", "pangu_ultra_moe", ROOTS)
ref = harness.load_by_name("reference", "pangu_ultra_moe", ROOTS)

# the configuration-file keys of the toy: every mechanism, no real width
TOY = {"vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "num_attention_heads": 4,
       "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "n_routed_experts": 16, "n_shared_experts": 1,
       "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
       "rms_norm_eps": 1e-5, "rope_theta": 25600000,
       "max_position_embeddings": 4096}


def toy(**over):
    config = {**TOY, **over}
    cfg = model.program_config(config, dtype="float32",
                               param_dtype="float32")
    return config, cfg, model.make_weights(cfg, 2 ** 31 + 5)


def ids_of(n, seed=0, rows=None):
    shape = (n,) if rows is None else (rows, n)
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"],
                                                shape).astype(np.int32)


def test_a_program_forward_is_the_references_on_logits():
    config, cfg, params = toy()
    ids = ids_of(21, rows=2)
    got = P.forward(params, jnp.asarray(ids), cfg)
    for row, out in zip(ids, got):
        want = ref.forward(params, jnp.asarray(row), config)
        np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_b_paged_prefill_mixed_chunks_and_decode_are_the_references_forward(
        kernel):
    """A short prompt through the batched prefill, a long one in chunks
    through the mixed step beside a decoding slot, then decode: the logits
    at every position a token is emitted from are the reference's."""
    config, cfg, params = toy()
    bs, W, M = 4, 10, 3
    pool = P.init_paged_pool(cfg, 1 + M * W, bs)
    tables = jnp.asarray(1 + np.arange(M * W).reshape(M, W), jnp.int32)
    short, long_ = ids_of(7, 1), ids_of(19, 2)
    want_s = ref.forward(params, jnp.asarray(np.concatenate(
        [short, ids_of(3, 3)])), config)
    want_l = ref.forward(params, jnp.asarray(long_), config)
    # row 0: the short prompt, cold, batched prefill (bucket 8, 1 row pad)
    ids = np.zeros((2, 8), np.int32)
    ids[0, :7] = short
    lg, pool, counts = P.paged_prefill(
        params, cfg, jnp.asarray(ids), jnp.asarray([7, 1]), tables[:2],
        pool, jnp.asarray([True, False]), use_kernel=kernel)
    np.testing.assert_allclose(lg[0], want_s[6], atol=2e-4, rtol=2e-4)
    assert counts.shape == (len(P.PAGED_COUNTERS),)
    # row 1: the long prompt in chunks of 8 through the mixed step, while
    # row 0 decodes the tokens the reference's sequence holds
    tail = ids_of(3, 3)
    done = 0
    for step in range(3):
        n = min(8, 19 - done)
        toks = np.zeros((M, 8), np.int32)
        toks[0, :] = tail[step]
        toks[1, :n] = long_[done:done + n]
        toks[1, n:] = long_[done + n - 1]
        lg, pool, _ = P.paged_mixed_step(
            params, cfg, jnp.asarray(toks),
            jnp.asarray([7 + step, done, 0]), jnp.asarray([1, n, 1]),
            tables, pool, jnp.asarray([True, True, False]),
            use_kernel=kernel)
        done += n
        np.testing.assert_allclose(lg[0], want_s[7 + step], atol=2e-4,
                                   rtol=2e-4)
        np.testing.assert_allclose(lg[1], want_l[done - 1], atol=2e-4,
                                   rtol=2e-4)
    # decode: feed row 1 the reference's own continuation
    more = ids_of(4, 4)
    want = ref.forward(params, jnp.asarray(np.concatenate([long_, more])),
                       config)
    for i, t in enumerate(more):
        lg, pool, _ = P.paged_decode_step(
            params, cfg, jnp.asarray([0, t, 0], jnp.int32),
            jnp.asarray([10, 19 + i, 0]), tables, pool,
            jnp.asarray([False, True, False]), use_kernel=kernel)
        np.testing.assert_allclose(lg[1], want[19 + i], atol=2e-4,
                                   rtol=2e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_b_a_packed_mixed_step_runs_its_real_lanes_in_waves(kernel):
    """Three slots x 8 lanes pack into waves of 12: two prompts in prefill
    (8 and 7 real lanes) beside a decoding slot are 16 real lanes, two
    waves, and the second prompt's chunk is split between them. Logits
    after each row's last token are the reference's, and the counters say
    24 lanes were computed for 16 real ones."""
    config, cfg, params = toy()
    bs, W, M = 4, 10, 3
    pool = P.init_paged_pool(cfg, 1 + M * W, bs)
    tables = jnp.asarray(1 + np.arange(M * W).reshape(M, W), jnp.int32)
    a, b, c = ids_of(8, 31), ids_of(15, 32), ids_of(6, 33)
    # row 2 holds 5 tokens of c already (a prefill of its own)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = c[:5]
    _, pool, _ = P.paged_prefill(params, cfg, jnp.asarray(ids),
                                 jnp.asarray([5]), tables[2:], pool,
                                 jnp.asarray([True]), use_kernel=kernel)
    # row 1 holds 8 tokens of b already
    _, pool, _ = P.paged_prefill(params, cfg, jnp.asarray(b[None, :8]),
                                 jnp.asarray([8]), tables[1:2], pool,
                                 jnp.asarray([True]), use_kernel=kernel)
    toks = np.zeros((M, 8), np.int32)
    toks[0] = a
    toks[1, :7], toks[1, 7] = b[8:], b[-1]
    toks[2, :] = c[5]
    lg, pool, counts = P.paged_mixed_step(
        params, cfg, jnp.asarray(toks), jnp.asarray([0, 8, 5]),
        jnp.asarray([8, 7, 1]), tables, pool, jnp.asarray([True] * 3),
        use_kernel=kernel)
    for row, seq in enumerate((a, b, c)):
        want = ref.forward(params, jnp.asarray(seq), config)[-1]
        np.testing.assert_allclose(lg[row], want, atol=2e-4, rtol=2e-4)
    named = dict(zip(P.PAGED_COUNTERS, counts.tolist()))
    assert named["lanes_computed"] == 24
    assert named["moe_pairs_total"] == 16 * 4 * 2      # 2 expert layers
    # every real lane read its own prefix, in each of 3 layers
    assert named["latent_tokens_read"] == 3 * (
        sum(range(1, 9)) + sum(range(9, 16)) + 6)


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_b_engine_serves_the_references_argmax(kernel):
    """The same through ``ServingEngine``: chunked prompts, mixed steps,
    decode dispatches; every emitted token is the reference's argmax."""
    config, cfg, params = toy()
    eng = ServingEngine(params, cfg, ServingConfig(
        block_size=4, max_slots=3, max_model_len=48, prefill_chunk=8,
        decode_chunk=2, paged_kernel=kernel))
    prompts = [ids_of(n, 10 + n) for n in (5, 19, 8, 27, 3)]
    outs = eng.run(prompts, max_new_tokens=5, eos_token_id=None)
    for p, o in zip(prompts, outs):
        lg = np.asarray(ref.forward(params, jnp.asarray(
            np.concatenate([p, o])), config))
        for i, tok in enumerate(o):
            row = lg[len(p) - 1 + i]
            assert row.max() - row[tok] < 1e-4
    st = eng.stats()
    assert st["mixed_dispatches"] > 0 and st["decode_dispatches"] > 0
    assert st["model"]["family"] == "pangu_ultra_moe"
    c = st["spans"]["counters"]
    assert c["decode_tokens"] + len(prompts) == sum(len(o) for o in outs)
    assert c["prefill_tokens"] == sum(len(p) for p in prompts)
    # every expert is held here: every pair of a real token is local
    assert c["moe_pairs_local"] == c["moe_pairs_total"] == 2 * 4 * (
        c["decode_tokens"] + c["prefill_tokens"])
    assert eng.health_snapshot()["family"]["local_pair_pct"] == 100.0
    assert eng.cache.manager.blocks_in_use == 0


def test_c_absorbed_form_is_the_expanded_form():
    _, cfg, params = toy()
    ids = jnp.asarray(ids_of(17, 5, rows=2))
    np.testing.assert_allclose(P.forward(params, ids, cfg, absorbed=True),
                               P.forward(params, ids, cfg, absorbed=False),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged", "kernel"])
def test_d_the_shares_add_up_to_the_uncut_layer(kernel):
    """Four shares of four experts each: the routed parts of all shares,
    with the shared expert counted once, sum to what the uncut layer (and
    the reference given all sixteen) computes."""
    config, cfg, params = toy()
    lp = {k: v[0] for k, v in params["runs"][1].items()}
    m = jnp.asarray(np.random.default_rng(7).normal(size=(13, 64)),
                    jnp.float32)
    real = jnp.ones((13,), bool)
    whole, counts = P._ffn(lp, m, real, cfg, kernel)
    assert int(counts[1]) == int(counts[0]) == 13 * 4
    parts = jnp.zeros_like(whole)
    local_pairs = 0
    for rank in range(4):
        share = dataclasses.replace(cfg, n_local_experts=4,
                                    expert_offset=4 * rank)
        mine = dict(lp, w_gu=lp["w_gu"][4 * rank:4 * rank + 4],
                    w_down=lp["w_down"][4 * rank:4 * rank + 4])
        routed, c = P._routed_experts(mine, m, real, share, kernel)
        parts = parts + routed
        local_pairs += int(c[1])
        # the reference given the same share computes the same share
        with jax.default_matmul_precision("highest"):
            want = ref.expert_ffn(m, mine, dict(config,
                                                expert_offset=4 * rank))
        shared = P._dense_ffn(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                              jnp.float32)
        np.testing.assert_allclose(shared + routed, want, atol=1e-4,
                                   rtol=1e-4)
    assert local_pairs == 13 * 4          # every pair is some share's
    shared = P._dense_ffn(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                          jnp.float32)
    np.testing.assert_allclose(shared + parts, whole, atol=1e-4, rtol=1e-4)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(whole, ref.expert_ffn(m, lp, config),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged", "kernel"])
def test_e_dropless_every_token_on_one_expert(kernel):
    """A router that sends every token to expert 3 first: all T pairs on
    it are computed (a capacity-routed layer would drop most)."""
    _, cfg, params = toy()
    lp = {k: v[0] for k, v in params["runs"][1].items()}
    lp["router"] = lp["router"].at[:, 3].set(0.0)
    m = jnp.abs(jnp.asarray(np.random.default_rng(8).normal(size=(40, 64)),
                            jnp.float32))
    lp["router"] = lp["router"].at[:, 3].add(1.0)   # m >= 0: score ~ 1
    ids, w = P.route(lp, m, cfg)
    assert (ids[:, 0] == 3).all()
    share = dataclasses.replace(cfg, n_local_experts=4, expert_offset=0)
    mine = dict(lp, w_gu=lp["w_gu"][:4], w_down=lp["w_down"][:4])
    routed, counts = P._routed_experts(mine, m, jnp.ones((40,), bool),
                                       share, kernel)
    assert int(counts[3]) == 40           # moe_rows_max: all on expert 3
    want = np.zeros((40, 64), np.float32)
    for e in range(4):
        coef = np.where(np.asarray(ids) == e, np.asarray(w), 0).sum(-1)
        gu = lp["w_gu"][e]
        want += coef[:, None] * np.asarray(P._dense_ffn(
            m, gu[:, :32], gu[:, 32:], lp["w_down"][e], jnp.float32))
    np.testing.assert_allclose(routed, want, atol=1e-4, rtol=1e-4)


def test_e_a_tokens_logits_do_not_depend_on_its_batch():
    _, cfg, params = toy()
    a, b = ids_of(15, 20), ids_of(15, 21)
    alone = P.forward(params, jnp.asarray(a[None]), cfg)[0]
    beside = P.forward(params, jnp.asarray(np.stack([b, a, b])), cfg)[1]
    np.testing.assert_allclose(alone, beside, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("lanes", ["decode", "chunk"])
def test_f_latent_kernel_is_the_dense_gather(lanes):
    """Interpret mode against a gather of each lane's table row: one lane
    a slot (a decode step), and several lanes a slot at growing lengths (a
    chunk), with a lane of length 0 that must read zeros."""
    rng = np.random.default_rng(3)
    L, N, bs, R, dr, H, W, D = 2, 14, 4, 16, 8, 4, 6, 128
    pool = jnp.asarray(rng.normal(size=(L, N, bs, D)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, N, size=(3, W)), jnp.int32)
    if lanes == "decode":
        slot, lens = [0, 1, 2], [1, 9, 24]
    else:
        slot, lens = [1] * 6 + [2, 0], [5, 6, 7, 8, 9, 10, 0, 17]
    T = len(slot)
    ql = jnp.asarray(rng.normal(size=(T, H, R)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(T, H, dr)), jnp.float32)
    slot, lens = jnp.asarray(slot, jnp.int32), jnp.asarray(lens, jnp.int32)
    got = paged_attention_latent(ql, qr, pool, jnp.int32(1), tbl, slot, lens,
                                 0.2)
    kv = pool[1][tbl].reshape(3, W * bs, D)[slot]
    live = jnp.arange(W * bs)[None, None, :] < lens[:, None, None]
    s = (jnp.einsum("thr,tcr->thc", ql, kv[..., :R]) +
         jnp.einsum("thr,tcr->thc", qr, kv[..., R:R + dr])) * 0.2
    p = jnp.where(live, jax.nn.softmax(jnp.where(live, s, -1e30), -1), 0)
    want = jnp.einsum("thc,tcr->thr", p, kv[..., :R])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if lanes == "chunk":
        assert not np.asarray(got[6]).any()


def test_f_latent_kernel_contains_poison_beyond_a_lanes_length():
    rng = np.random.default_rng(4)
    pool = jnp.asarray(rng.normal(size=(1, 5, 4, 128)), jnp.float32)
    tbl = jnp.asarray([[1, 2, 3]], jnp.int32)
    ql = jnp.asarray(rng.normal(size=(1, 2, 16)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(1, 2, 8)), jnp.float32)
    args = (jnp.int32(0), tbl, jnp.zeros((1,), jnp.int32),
            jnp.asarray([6], jnp.int32), 0.2)
    clean = paged_attention_latent(ql, qr, pool, *args)
    dirty = pool.at[0, 2, 2:].set(jnp.nan).at[0, 3].set(jnp.nan)
    np.testing.assert_array_equal(
        clean, paged_attention_latent(ql, qr, dirty, *args))


@pytest.mark.parametrize("sizes", [[3, 0, 5, 1], [0, 0, 0, 0],
                                   [300, 0, 0, 0], [70, 90, 60, 80],
                                   [1, 130, 2, 129]])
@pytest.mark.parametrize("gated", [False, True])
def test_grouped_matmul_kernel_is_the_sum_by_hand(sizes, gated):
    """Interpret mode, several row tiles, groups that share a tile, empty
    groups, rows of no group: zeros there, the product elsewhere."""
    rng = np.random.default_rng(5)
    R, K, N = 320, 32, 48
    x = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, K, N)), jnp.float32)
    want = np.zeros((R, N), np.float32)
    o = 0
    for g, n in enumerate(sizes):
        want[o:o + n] = np.asarray(x[o:o + n]) @ np.asarray(w[g])
        o += n
    if gated:
        want = np.asarray(jax.nn.silu(want[:, :24])) * want[:, 24:]
    stack = jnp.stack([w * 0 + 7.0, w, w * 0 - 3.0])   # layer 1 is ours
    for kernel in (False, True):
        got = grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                             gated=gated, use_kernel=kernel)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
        # the same out of every layer's experts stacked, read in place
        got = jax.jit(lambda layer: grouped_matmul(
            x, stack, jnp.asarray(sizes, jnp.int32), layer=layer,
            gated=gated, use_kernel=kernel))(jnp.int32(1))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_g_counters_by_hand_on_a_fixed_routing():
    """Six lanes, five real, experts 4..7 held. The router is rigged so
    that lane t's picks are experts t, t+1, t+2, t+3 (mod 16)."""
    _, cfg, params = toy()
    share = dataclasses.replace(cfg, n_local_experts=4, expert_offset=4)
    lp = {k: v[0] for k, v in params["runs"][1].items()}
    router = np.zeros((64, 16), np.float32)
    m = np.zeros((6, 64), np.float32)
    for t in range(6):
        m[t, t] = 1.0
        for j in range(4):
            router[t, (t + j) % 16] = 5.0 - j
    lp = dict(lp, router=jnp.asarray(router), w_gu=lp["w_gu"][4:8],
              w_down=lp["w_down"][4:8])
    real = jnp.asarray([True, True, True, True, True, False])
    ids, _ = P.route(lp, jnp.asarray(m), share)
    assert [sorted(r) for r in np.asarray(ids).tolist()] == [
        [t, t + 1, t + 2, t + 3] for t in range(6)]
    _, counts = P._routed_experts(lp, jnp.asarray(m), real, share, False)
    # real lanes 0..4 pick {0..3},{1..4},{2..5},{3..6},{4..7}: on experts
    # 4,5,6,7 that is 1+2+3+4 = 10 pairs; expert 4 has the most rows (4:
    # lanes 1,2,3,4), every held expert has a row
    assert counts.tolist() == [5 * 4, 10, 4, 4]


def test_the_engine_finds_a_family_through_the_config_object():
    assert paged_family(LlamaConfig()) is generation
    _, cfg, params = toy()
    assert paged_family(cfg) is P
    for knob in ({"kv_quant": "int8"}, {"spec_decode": 2}, {"tp": 2},
                 {"quantize": "int8"}, {"lora_slots": 2, "lora_pool": 2}):
        with pytest.raises(ValueError, match="pangu_ultra_moe"):
            ServingEngine(params, cfg, ServingConfig(
                block_size=4, max_slots=2, max_model_len=16, **knob))


def _dense_toy():
    from paddle_tpu.models.llama import init_params
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("family", ["dense", "pangu_ultra_moe"])
def test_a_family_is_its_documented_entry_points_and_serves_in_chunks(
        family):
    """A family's contract is ``paged_prefill``, ``paged_decode_step``,
    ``paged_mixed_step`` (and ``paged_spec_step`` where it has a verify
    step) beside the pool's functions and the four names the engine
    reads: exactly these, and an engine built on them serves a prompt
    longer than ``prefill_chunk``, every chunk through the mixed step."""
    cfg, params = _dense_toy() if family == "dense" else toy()[1:]
    F = paged_family(cfg)
    steps = {"paged_prefill", "paged_decode_step", "paged_mixed_step"}
    if family == "dense":
        steps.add("paged_spec_step")
    assert {n for n in F.__all__ if n.startswith("paged_")
            and not n.startswith("paged_pool")} == steps
    assert {n for n in dir(F) if n.startswith("paged_")
            and callable(getattr(F, n))} - steps <= {
                "paged_pool_block_bytes", "paged_pool_specs"}
    for name in ("init_paged_pool", "paged_pool_block_bytes",
                 "PAGED_COUNTERS", "validate_serving", "describe", "health"):
        assert name in F.__all__, name
    eng = ServingEngine(params, cfg, ServingConfig(
        block_size=4, max_slots=2, max_model_len=48, prefill_chunk=8,
        decode_chunk=2))
    prompt = ids_of(27, 3)
    (out,) = eng.run([prompt], max_new_tokens=4, eos_token_id=None)
    assert len(out) == 4
    st = eng.stats()
    assert st["mixed_dispatches"] == 4          # ceil(27 / 8) chunks
    assert st["prefill_dispatches"] == 0 and st["decode_dispatches"] >= 1
    assert st["spans"]["counters"]["prefill_tokens"] == 27
    assert eng.cache.manager.blocks_in_use == 0


def test_counts_of_the_published_configuration_by_hand():
    """The issue's arithmetic from the row's widths: 196.6 M of attention
    a layer, 47.19 M an expert, 4.92 B parameters held, 5760 B of cache a
    token, and one call of each kernel."""
    with open(os.path.join(
            REPO, "benchmark/configs/openpangu-ultra-moe-718b-ep16-d5.json"
            )) as f:
        import json
        config = json.load(f)
    assert model.attention_params(config) == (
        7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 +
        16384 * 7680) == 196_575_232
    assert model.expert_params(config) == 47_185_920
    assert model.router_width(config) == 256
    assert model.held_experts(config) == 16
    held = (196_575_232 + 3 * 7680 * 18432 +
            4 * (196_575_232 + 7680 * 256 + 17 * 47_185_920) +
            2 * 19200 * 7680)
    norms = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert model.total_params(config) == held + norms
    assert abs(model.total_params(config) * 2 / 1e9 - 9.84) < 0.01
    assert model.cache_bytes_per_token(config) == 5760
    assert model.pool_bytes_per_token(config) == 6400
    # a decode token at a cache of 600: its picks that fall here are 8/16
    per_token = model.serve_flops_per_token(config, 600)
    by_hand = 2 * (5 * 196_575_232 + 3 * 7680 * 18432 +
                   4 * (7680 * 256 + 1.5 * 47_185_920) + 19200 * 7680) + \
        5 * 600 * 2 * 128 * (2 * 512 + 64)
    assert per_token == by_hand
    g = model.grouped_matmul_counts(config, rows=32, expert_calls=16)
    assert g["flops"] == 32 * 6 * 7680 * 2048
    assert g["bytes"] == 16 * 3 * 7680 * 2048 * 2 + 32 * (
        2 * 7680 + 2 * 2048) * 2
    a = model.latent_attention_counts(config, tokens_read=1000)
    assert a == {"flops": 1000 * 2 * 128 * 1088, "bytes": 1000 * 1152}
    # the program agrees on what is stored
    cfg = model.program_config(config, **config["program"])
    assert P.num_params(cfg) == model.total_params(config)
