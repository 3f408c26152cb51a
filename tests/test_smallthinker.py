"""SmallThinker through the window/full family at a toy size on the CPU,
float32: the program against the benchmark's plain reference (NoPE full
layers, rotary window layers, a router on the layer's input, ReGLU
experts held whole), the paged path through rings that wrap several
times, a packed mixed step against an unpacked one, the ReGLU grouped
kernel against ``ragged_dot``, three planted faults that each fail the
comparison, what the engine reports of the family, and the published
configuration's counts by hand."""

import dataclasses
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness                                   # noqa: E402
from paddle_tpu.inference.serving import (InvariantAuditor,     # noqa: E402
                                          ServingConfig, ServingEngine)
from paddle_tpu.kernels.grouped_matmul import grouped_matmul    # noqa: E402
from paddle_tpu.models import laguna as L, paged_family         # noqa: E402
from paddle_tpu.models import pangu_ultra_moe as P              # noqa: E402

ROOTS = [os.path.join(REPO, "benchmark")]
model = harness.load_by_name("models", "smallthinker", ROOTS)
ref = harness.load_by_name("reference", "smallthinker", ROOTS)

# the configuration-file keys of the toy, under the published names: a
# group of 7 query heads a kv head, a window of 8 shorter than the
# prompts, 16 experts held whole; the layout lists as long as published
LAYOUT = [0, 1, 1, 1] * 13
TOY = {
    "vocab_size": 96, "hidden_size": 64, "moe_ffn_hidden_size": 32,
    "num_hidden_layers": 8, "num_attention_heads": 14,
    "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window_layout": LAYOUT, "rope_layout": LAYOUT,
    "sliding_window_size": 8, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 4,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_scaling": None,
    "engine": {"prefill_chunk": 8}}
BS = 4
RING = 5            # ceil((8 + 8) / 4) + 1 table entries a window group


def toy():
    cfg = model.program_config(TOY, dtype="float32", param_dtype="float32")
    return cfg, model.make_weights(cfg, 2 ** 31 + 5)


def ids_of(n, seed=0, rows=None):
    shape = (n,) if rows is None else (rows, n)
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"],
                                                shape).astype(np.int32)


def serving(**over):
    kw = dict(block_size=BS, max_slots=3, max_model_len=64, prefill_chunk=8,
              decode_chunk=2, prefix_cache=False, num_blocks=80)
    kw.update(over)
    return ServingConfig(**kw)


def test_the_toy_is_the_architecture():
    """Layer 0 is sparse like every other, no layer holds a gate or a
    shared expert, the norms were drawn away from one, the full layers
    rotate nothing."""
    cfg, params = toy()
    assert (cfg.n_full, cfg.n_sliding, cfg.window_groups) == (2, 6, 3)
    assert (cfg.n_periods, cfg.n_tail, cfg.n_sparse) == (1, 3, 8)
    assert cfg.heads_full == cfg.heads_sliding == 14
    assert set(params["first"]) == {"ln_in", "ln_post", "wq", "wkv", "wo",
                                    "router", "w_gu", "w_down"}
    assert set(params["periods"]["slide"]) == set(params["first"])
    assert float(jnp.abs(params["first"]["ln_in"] - 1).max()) > 0.1
    assert L.rope_inv_freq(cfg, L.FULL) is None
    assert L._rope_tables(cfg, L.FULL, jnp.arange(3)) is None
    inv, factor = L.rope_inv_freq(cfg, L.SLIDING)
    np.testing.assert_allclose(
        inv, 1500000.0 ** (-2.0 * np.arange(8) / 16), rtol=1e-6)
    assert factor == 1.0


def test_a_program_forward_is_the_references_on_logits():
    cfg, params = toy()
    ids = ids_of(27, rows=2)
    got = L.forward(params, jnp.asarray(ids), cfg)
    for row, out in zip(ids, got):
        want = ref.forward(params, jnp.asarray(row), TOY)
        np.testing.assert_allclose(out, want, atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_b_engine_prefill_then_decode_past_several_windows(kernel):
    """Prompts of 5, 21 and 30 tokens (one prefill, two chunked across the
    mixed step), decoded to 45-54 tokens: every emitted token is the
    argmax of the reference's full forward although each ring of 5
    entries (20 positions) wrapped two to three times."""
    cfg, params = toy()
    eng = ServingEngine(params, cfg, serving(paged_kernel=kernel))
    assert eng.cache.rings == (None, RING, RING, RING)
    assert eng.cache.blocks_per_seq == 16 + 3 * RING
    prompts = [ids_of(n, n) for n in (5, 21, 30)]
    outs = eng.run(prompts, max_new_tokens=[40, 24, 24], eos_token_id=None)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        seq = np.concatenate([p, o])
        assert len(seq) >= 45
        want = np.asarray(ref.forward(params, jnp.asarray(seq), TOY))
        at = want[len(p) - 1:len(seq) - 1]
        gap = at.max(-1) - at[np.arange(len(o)), o]
        assert gap.max() < 1e-4, gap.max()
    assert eng.cache.manager.blocks_in_use == 0
    assert InvariantAuditor().quiesce(eng, collect=True) == []
    st = eng.stats()
    assert st["mixed_dispatches"] >= 1 and st["prefill_dispatches"] >= 1
    fam = eng.health_snapshot()["family"]
    assert fam["local_pair_pct"] == 100.0
    assert fam["window_tokens_read_a_lane"] <= 8
    assert fam["full_tokens_read_a_lane"] > 8


def _mixed_case():
    """Three rows of a mixed step: a decoding slot at position 33, a
    7-token chunk from 12 on, a full 8-token chunk from 25 on; tables
    laid out as the engine lays them, [full | ring 1 | ring 2 | ring 3]."""
    cfg, params = toy()
    M, Q, W = 3, 8, 16
    width = W + 3 * RING
    pool = L.init_paged_pool(cfg, 1 + M * width, BS)
    rng = np.random.default_rng(3)
    pool = {k: jnp.asarray(rng.standard_normal(v.shape), jnp.float32)
            for k, v in pool.items()}
    tables = jnp.asarray(1 + np.arange(M * width).reshape(M, width),
                         jnp.int32)
    return (cfg, params, jnp.asarray(ids_of(Q, 4, rows=M)),
            jnp.asarray([33, 12, 25], jnp.int32),
            jnp.asarray([1, 7, 8], jnp.int32), tables, pool)


@pytest.mark.parametrize("wave_rows", [32, 1], ids=["one-wave", "waves"])
def test_c_a_packed_mixed_step_is_the_unpacked_one(wave_rows, monkeypatch):
    """The mixed step packs its 16 real lanes into waves of ``_WAVE_ROWS x
    M`` lanes (one wave as served, or six of 3 lanes); the same rows run
    in place, unpacked, give the same logits and leave the same pool."""
    monkeypatch.setattr(L, "_WAVE_ROWS", wave_rows)
    cfg, params, tokens, starts, q_lens, tables, pool = _mixed_case()
    active = jnp.ones((3,), bool)
    packed, pool_p, c_p = L.paged_mixed_step(params, cfg, tokens, starts,
                                            q_lens, tables, pool, active)
    x, pool_u, c_u = L._paged_rows(params, cfg, tokens, starts, q_lens,
                                   tables, pool, False)
    np.testing.assert_allclose(packed, L._head(params, x, cfg), atol=2e-5,
                               rtol=2e-5)
    for k in pool:       # block 0 is the null block pad lanes write to
        np.testing.assert_allclose(pool_p[k][:, 1:], pool_u[k][:, 1:],
                                   atol=2e-5, rtol=0)
    c_p, c_u = (dict(zip(L.PAGED_COUNTERS, np.asarray(c).tolist()))
                for c in (c_p, c_u))
    for name in ("moe_pairs_total", "moe_pairs_local", "full_tokens_read",
                 "window_tokens_read"):
        assert c_p[name] == c_u[name], name
    assert c_p["moe_pairs_total"] == 16 * 4 * 8     # every lane, 8 layers
    waves = -(-16 // (wave_rows * 3))
    assert c_p["lanes_computed"] == waves * min(24, wave_rows * 3)


@pytest.mark.parametrize("stacked", [False, True], ids=["one", "layer"])
def test_d_the_reglu_grouped_kernel_is_ragged_dot(stacked):
    """``relu(x w_gate) * (x w_up)`` in the kernel's epilogue (interpret
    mode here) against the ``ragged_dot`` path, empty experts and rows of
    no group among them; the SiLU epilogue is another result."""
    rng = np.random.default_rng(7)
    R, K, N, G = 45, 64, 2 * 32, 5
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, G, K, N)) / 8, jnp.float32)
    sizes = jnp.asarray([7, 0, 19, 11, 0], jnp.int32)      # 37 of 45 rows
    kw = dict(layer=jnp.int32(2)) if stacked else {}
    ww = w if stacked else w[2]
    got = grouped_matmul(x, ww, sizes, gated=True, activation="relu",
                         use_kernel=True, **kw)
    want = grouped_matmul(x, ww, sizes, gated=True, activation="relu",
                          use_kernel=False, **kw)
    assert got.shape == (R, 32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[37:].any()
    by_hand = np.maximum(np.asarray(x[:7] @ w[2, 0, :, :32]), 0) * \
        np.asarray(x[:7] @ w[2, 0, :, 32:])
    np.testing.assert_allclose(got[:7], by_hand, atol=2e-5, rtol=2e-5)
    silu = grouped_matmul(x, ww, sizes, gated=True, use_kernel=True, **kw)
    assert float(jnp.abs(silu - got)[:37].max()) > 0.1


def _rotary_on_full_layers(monkeypatch):
    sound = L._rope_tables
    monkeypatch.setattr(L, "_rope_tables", lambda cfg, kind, pos: sound(
        cfg, L.SLIDING, pos))


def _router_fed_the_normed_stream(monkeypatch):
    sound = P.route
    monkeypatch.setattr(P, "route", lambda lp, r, cfg: sound(
        lp, L._norm(r, lp["ln_in"], cfg), cfg))


FAULTS = {"rotary on a full layer": _rotary_on_full_layers,
          "the router fed N_in(x)": _router_fed_the_normed_stream,
          "SiLU in place of ReLU": None}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_e_a_planted_fault_fails_the_comparison(fault, monkeypatch):
    """Each fault changes what the program computes and nothing else; the
    reference, which computes the architecture by itself, tells it apart
    by far more than the sound program's gap (a few 1e-6)."""
    cfg, params = toy()
    if FAULTS[fault] is None:
        cfg = dataclasses.replace(cfg, expert_activation="silu")
    else:
        FAULTS[fault](monkeypatch)
    ids = ids_of(27, 9)
    got = np.asarray(L.forward(params, jnp.asarray(ids)[None], cfg)[0])
    want = np.asarray(ref.forward(params, jnp.asarray(ids), TOY))
    assert np.abs(got - want).max() > 1e-2, fault


def test_f_the_engine_describes_the_family_under_the_published_names():
    """``describe`` names the family the benchmark loads its counts by and
    gives the family's own field names; the counts file maps them to the
    published keys it reads, and its counts read the same number from the
    file and from the engine."""
    cfg, params = toy()
    assert paged_family(cfg) is L
    eng = ServingEngine(params, cfg, serving())
    shown = eng.stats()["model"]
    assert shown["family"] == "smallthinker"
    assert "moe_ffn_hidden_size" not in shown
    named = model.published(shown)
    assert model.published(TOY) is TOY
    for key in ("hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window_size",
                "moe_num_primary_experts", "moe_num_active_primary_experts",
                "vocab_size", "num_hidden_layers"):
        assert named[key] == TOY[key], key
    assert named["sliding_window_layout"] == LAYOUT[:8]
    assert named["rope_layout"] == LAYOUT[:8]
    assert model.serve_flops_per_token(shown, 100) == \
        model.serve_flops_per_token(TOY, 100)
    assert model.grouped_matmul_counts(shown, 96, 64) == \
        model.grouped_matmul_counts(TOY, 96, 64)
    assert model.full_attention_counts(shown, 1000, 100) == \
        model.full_attention_counts(TOY, 1000, 100)
    assert L.paged_pool_block_bytes(cfg, BS) == 2 * BS * 2 * 2 * 16 * 4
    assert L.describe(L.LagunaConfig())["family"] == "laguna"
    with pytest.raises(ValueError, match="laguna"):
        ServingEngine(params, cfg, serving(prefix_cache=True))


def test_g_the_routing_reads_the_layers_input_at_its_start():
    """The router of a sparse layer reads ``router_input`` (the layer's
    input ``x``, un-normed) while the experts read ``m``: the FFN is the
    reference's picks of ``x``, softmax-weighed, applied to ``m``, and
    routing on ``m`` instead is another result. The forward hands the
    router ``x``: a router whose input changes nothing but the routing
    tells the two apart (test_e)."""
    cfg, params = toy()
    lp = params["first"]
    rng = np.random.default_rng(2)
    x, m = (jnp.asarray(rng.standard_normal((6, 64)), jnp.float32)
            for _ in range(2))
    real = jnp.ones((6,), bool)
    got, counts = P._ffn(lp, m, real, cfg, False, router_input=x)
    ids, w = ref.route(x, lp, {"k": 4})
    want = np.asarray(ref.expert_ffn(m, ids, w, lp))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert int(counts[1]) == 24
    on_m, _ = P._ffn(lp, m, real, cfg, False)
    assert np.abs(np.asarray(on_m) - want).max() > 1e-2


def test_counts_of_the_published_configuration_by_hand():
    """The arithmetic of the cut, from the configuration file."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker-21ba3b-d8.json")) as f:
        c = json.load(f)
    assert model.attention_params(c) == 20_971_520
    assert model.expert_params(c) == 5_898_240
    assert model.layer_matmul_params(c) == 398_622_720
    assert model.total_params(c) == 3_966_937_600
    whole = {**c, "num_hidden_layers": 52}
    assert model.total_params(whole) == 21_506_562_560
    cfg = model.program_config(c, **c["program"])
    assert L.num_params(cfg) == model.total_params(c)
    assert (cfg.n_full, cfg.n_sliding, cfg.window_groups) == (2, 6, 3)
    assert cfg.ring_blocks(16) == 265
    assert model.cache_bytes_per_token(c) == 4_096
    assert model.window_cache_bytes_per_sequence(c, 128, 16) == \
        6 * 2048 * 4240
    assert L.paged_pool_block_bytes(cfg, 16) == 65_536
    flops = model.serve_flops_per_token(c, 0)
    assert 1.6e9 < flops < 1.75e9
    # at 8k a window layer reads 4096, a full one 8192
    assert model.serve_flops_per_token(c, 8192) - flops == \
        4 * 28 * 128 * (2 * 8192 + 6 * 4096)
    got = model.full_attention_counts(c, 1000, 100)
    assert got == {"flops": 1000 * 4 * 28 * 128, "bytes": 100 * 2048}
    assert model.window_attention_counts(c, 10)["flops"] == 10 * 4 * 28 * 128
    assert model.grouped_matmul_counts(c, 96, 64) == {
        "flops": 96 * 6.0 * 2560 * 768,
        "bytes": 64 * 3.0 * 2560 * 768 * 2 + 96 * (2 * 2560 + 2 * 768) * 2}
