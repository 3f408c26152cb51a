"""The ``laguna`` family at a toy size on the CPU, float32: the program
against the benchmark's plain reference, the paged path through a cache
whose window groups are rings, the window as a bound on what is READ, the
shares of an expert-parallel layer adding up, the window-bounded kernel
against a gather, the allocator over groups, the counters by hand, the two
rotary tables, and what the family refuses to serve."""

import dataclasses
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness                                   # noqa: E402
from paddle_tpu.inference.serving import (InvariantAuditor,     # noqa: E402
                                          ServingConfig, ServingEngine)
from paddle_tpu.inference.serving.paged_cache import PagedKVCache  # noqa: E402
from paddle_tpu.kernels import paged_attention                  # noqa: E402
from paddle_tpu.models import laguna as L, paged_family         # noqa: E402
from paddle_tpu.models.llama import LlamaConfig                 # noqa: E402
from paddle_tpu.models.pangu_ultra_moe import (                 # noqa: E402
    PanguUltraMoEConfig, _ffn)

ROOTS = [os.path.join(REPO, "benchmark")]
model = harness.load_by_name("models", "laguna", ROOTS)
ref = harness.load_by_name("reference", "laguna", ROOTS)

LAYERS = 9          # 1 dense + 8 sparse, in the published pattern
# the configuration-file keys of the toy: every mechanism, no real width;
# the per-layer lists as long as published, of which the first 9 are held
TOY = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": LAYERS, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3 +
    (["full_attention"] + ["sliding_attention"] * 3) * 3,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 4,
    "mlp_only_layers": [0], "decoder_sparse_step": 1,
    "sliding_window": 8, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "engine": {"prefill_chunk": 8}}
BS = 4
RING = 5            # ceil((8 + 8) / 4) + 1 table entries a window group


def toy(**over):
    config = {**TOY, **over}
    cfg = model.program_config(config, dtype="float32",
                               param_dtype="float32")
    return config, cfg, model.make_weights(cfg, 2 ** 31 + 5)


def ids_of(n, seed=0, rows=None):
    shape = (n,) if rows is None else (rows, n)
    return np.random.default_rng(seed).integers(0, TOY["vocab_size"],
                                                shape).astype(np.int32)


def serving(**over):
    kw = dict(block_size=BS, max_slots=3, max_model_len=64, prefill_chunk=8,
              decode_chunk=2, prefix_cache=False, num_blocks=80)
    kw.update(over)
    return ServingConfig(**kw)


# ---------------------------------------------------------------------------
# (a) the program is the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("share", [(16, 0), (8, 4)], ids=["whole", "share"])
def test_a_program_forward_is_the_references_on_logits(share):
    held, offset = share
    config, cfg, params = toy(
        num_experts=held, expert_offset=offset,
        published_counts={"num_experts": 16})
    ids = ids_of(27, rows=2)
    got = L.forward(params, jnp.asarray(ids), cfg)
    for row, out in zip(ids, got):
        want = ref.forward(params, jnp.asarray(row), config)
        np.testing.assert_allclose(out, want, atol=3e-4, rtol=3e-4)


def test_a_the_stack_is_layer_0_whole_periods_and_a_tail():
    """Depth 12 of the pattern is layer 0, two whole periods and a tail of
    three sliding layers; a depth whose sliding layers do not split into
    groups of the full layers' count is refused."""
    kinds = tuple(TOY["layer_types"])
    base = toy()[1]
    cfg = dataclasses.replace(
        base, num_hidden_layers=12, layer_types=kinds[:12],
        num_attention_heads_per_layer=tuple(
            TOY["num_attention_heads_per_layer"][:12]))
    assert (cfg.n_periods, cfg.slides_per_period, cfg.n_tail) == (2, 3, 3)
    assert (cfg.n_full, cfg.window_groups) == (3, 3)
    with pytest.raises(ValueError, match="cache groups"):
        dataclasses.replace(
            base, num_hidden_layers=13, layer_types=kinds[:13],
            num_attention_heads_per_layer=tuple(
                TOY["num_attention_heads_per_layer"][:13]))
    # 16 layers: 4 full, 12 sliding = 3 groups of 4, a tail of 3
    cfg = dataclasses.replace(
        base, num_hidden_layers=16, layer_types=kinds,
        num_attention_heads_per_layer=tuple(
            TOY["num_attention_heads_per_layer"]))
    assert (cfg.n_periods, cfg.n_tail, cfg.window_groups) == (3, 3, 3)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    assert ref.n_blocks(params) == 16
    config = {**TOY, "num_hidden_layers": 16}
    ids = ids_of(19, 7)
    np.testing.assert_allclose(
        L.forward(params, jnp.asarray(ids)[None], cfg)[0],
        ref.forward(params, jnp.asarray(ids), config), atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# (b) the paged path: prefill, chunks across the mixed step, decode to six
# windows' length, the rings wrapped several times
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["off", "on"])
def test_b_engine_serves_the_references_argmax_past_six_windows(kernel):
    config, cfg, params = toy()
    eng = ServingEngine(params, cfg, serving(paged_kernel=kernel))
    assert eng.cache.rings == (None, RING, RING)
    prompts = [ids_of(n, n) for n in (5, 21, 30)]        # 1 prefill, 2 chunked
    outs = eng.run(prompts, max_new_tokens=[52, 24, 30], eos_token_id=None)
    for p, o in zip(prompts, outs):
        o = np.asarray(o)
        seq = np.concatenate([p, o])
        assert len(seq) >= 45            # well past 8-token windows, ring 20
        want = np.asarray(ref.forward(params, jnp.asarray(seq), config))
        at = want[len(p) - 1:len(seq) - 1]
        gap = at.max(-1) - at[np.arange(len(o)), o]
        assert gap.max() < 1e-4, gap.max()
    assert eng.cache.manager.blocks_in_use == 0
    assert InvariantAuditor().quiesce(eng, collect=True) == []
    st = eng.stats()
    assert st["mixed_dispatches"] >= 1 and st["prefill_dispatches"] >= 1
    fam = eng.health_snapshot()["family"]
    assert fam["window_tokens_read_a_lane"] <= TOY["sliding_window"]
    assert fam["full_tokens_read_a_lane"] > TOY["sliding_window"]


# ---------------------------------------------------------------------------
# (c) the window is a bound on what is READ
# ---------------------------------------------------------------------------

def _rows_case(seed, M, Q, H, starts, dls, R=None, W=12):
    """Random queries and a pool whose table rows are filled up to each
    row's last position; a ring where ``R`` is given."""
    rng = np.random.default_rng(seed)
    Hk, D, Lg = 2, 16, 3
    n_tbl = R or W
    N = 1 + M * n_tbl
    pool = {k: jnp.asarray(rng.standard_normal((Lg, N, Hk, BS, D)),
                           jnp.float32) for k in ("k", "v")}
    tbl = jnp.asarray(1 + np.arange(M * n_tbl).reshape(M, n_tbl), jnp.int32)
    q = jnp.asarray(rng.standard_normal((M, Q, H, D)), jnp.float32)
    return (q, pool, tbl, jnp.asarray(starts, jnp.int32),
            jnp.asarray(dls, jnp.int32))


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_c_a_sliding_layer_never_reads_behind_its_window(kernel):
    """Overwrite every cached value before ``pos - window + 1`` with NaN:
    a sliding layer's output does not change, a full layer's does."""
    window, R, pos = 8, RING, 37
    q, pool, tbl, start, dl = _rows_case(3, 1, 1, 6, [pos], [0], R=R)
    ring_pos = np.asarray(L._ring_positions(start + dl, R, BS))[0]
    behind = ring_pos < pos - window + 1                  # [R * BS] cells
    cells = np.asarray(tbl)[0][:, None] * BS + np.arange(BS)[None, :]
    bad = {name: np.array(arr) for name, arr in pool.items()}
    for name in bad:
        # the head-major pool by cell: [layers, N * BS, Hk, D]
        flat = np.swapaxes(bad[name], 2, 3).reshape(3, -1, 2, 16)
        flat[:, cells.reshape(-1)[behind]] = np.nan
        flat[:, :BS] = np.nan                              # the null block
        bad[name] = jnp.asarray(np.swapaxes(
            flat.reshape(3, -1, BS, 2, 16), 2, 3))
    assert behind.sum() >= 8
    clean = L._attend_rows(q, pool, 1, tbl, start, dl, window, kernel)
    dirty = L._attend_rows(q, bad, 1, tbl, start, dl, window, kernel)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    # the same poison under a full layer's read of the same table
    q, pool, tbl, start, dl = _rows_case(3, 1, 1, 4, [pos], [0], W=12)
    bad = {name: arr.at[:, 1:3].set(jnp.nan) for name, arr in pool.items()}
    full = L._attend_rows(q, bad, 1, tbl, start, dl, None, kernel)
    assert not np.isfinite(np.asarray(full)).all()


# ---------------------------------------------------------------------------
# (d) the shares add up
# ---------------------------------------------------------------------------

def test_d_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of a sparse layer, with the
    shared expert counted once, sum to the layer that holds every expert."""
    _, whole, params = toy(num_experts=16)
    lp = {k: v[0, 0] for k, v in params["periods"]["slide"].items()}
    m = jnp.asarray(np.random.default_rng(5).standard_normal((11, 64)),
                    jnp.float32)
    real = jnp.ones((11,), bool)
    uncut, counts = _ffn(lp, m, real, whole, False)
    shared = _ffn({**lp, "w_gu": lp["w_gu"][:4], "w_down": lp["w_down"][:4]},
                  m, jnp.zeros((11,), bool),
                  dataclasses.replace(whole, n_local_experts=4), False)[0]
    total, pairs = shared, 0
    for offset in (0, 4, 8, 12):
        cfg = dataclasses.replace(whole, n_local_experts=4,
                                  expert_offset=offset)
        part, c = _ffn({**lp, "w_gu": lp["w_gu"][offset:offset + 4],
                        "w_down": lp["w_down"][offset:offset + 4]},
                       m, real, cfg, False)
        total = total + (part - shared)
        pairs += int(c[1])
    assert pairs == int(counts[1]) == 11 * 4
    np.testing.assert_allclose(total, uncut, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# (e) the window-bounded kernel is the gathered oracle
# ---------------------------------------------------------------------------

WINDOW_CASES = {
    # name: (Q, starts, draft lens): window 8, pages of 4, a ring of 5
    "q1-window-starts-mid-page": (1, [14, 22, 9], [0, 0, 0]),
    "q1-before-the-first-window-fills": (1, [0, 5, 3], [0, 0, 0]),
    "q1-ring-wrapped-six-times": (1, [121, 63, 40], [0, 0, 0]),
    "mq-chunk-straddles-the-rings-seam": (8, [17, 36, 14], [7, 7, 5]),
    "mq-before-the-first-window-fills": (8, [0, 2, 0], [7, 3, 0]),
    "mq-one-decoding-row-among-chunks": (8, [30, 57, 12], [0, 7, 2]),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_e_window_kernel_is_the_gathered_oracle(case):
    Q, starts, dls = WINDOW_CASES[case]
    q, pool, tbl, start, dl = _rows_case(11, 3, Q, 6, starts, dls, R=RING)
    want = np.asarray(L._attend_rows(q, pool, 2, tbl, start, dl, 8, False))
    got = np.asarray(L._attend_rows(q, pool, 2, tbl, start, dl, 8, True))
    for m in range(3):
        n = int(dl[m]) + 1
        np.testing.assert_allclose(got[m, :n], want[m, :n], rtol=3e-5,
                                   atol=3e-6)
        assert not got[m, n:].any()      # rows past the draft: zeros
    # the oracle itself, against attention over the positions in order
    m, i = 1, int(dl[1])
    pos = int(start[1]) + i
    ring_pos = np.asarray(L._ring_positions(start + dl, RING, BS))[1]
    cells = (np.asarray(tbl)[1][:, None] * BS + np.arange(BS)).reshape(-1)
    live = [(p, c) for p, c in zip(ring_pos, cells)
            if max(pos - 8, -1) < p <= pos]
    assert sorted(p for p, _ in live) == list(range(max(0, pos - 7), pos + 1))
    k, v = (np.swapaxes(np.asarray(pool[n][2]), 1, 2).reshape(-1, 2, 16)[
        [c for _, c in live]] for n in ("k", "v"))
    for h in range(6):
        s = k[:, h // 3] @ np.asarray(q[m, i, h]) / 4.0
        p = np.exp(s - s.max())
        np.testing.assert_allclose(want[m, i, h], (p / p.sum()) @ v[:, h // 3],
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("Q", [1, 8])
def test_e_query_groups_of_6_and_9_through_the_full_kernel(group, Q):
    """Head counts that neither fill nor divide a sublane tile, through
    the kernel the dense family calls, a whole pool and a layer index."""
    H = 2 * group
    q, pool, tbl, start, dl = _rows_case(group, 3, Q, H, [13, 40, 2],
                                         [Q - 1, 0, (Q - 1) // 2], W=12)
    want = np.asarray(L._attend_rows(q, pool, 1, tbl, start, dl, None, False))
    got = np.asarray(L._attend_rows(q, pool, 1, tbl, start, dl, None, True))
    for m in range(3):
        n = int(dl[m]) + 1
        np.testing.assert_allclose(got[m, :n], want[m, :n], rtol=3e-5,
                                   atol=3e-6)


def test_e_the_window_form_reads_fp_pools_only():
    q, pool, tbl, start, dl = _rows_case(1, 2, 1, 4, [3, 9], [0, 0], R=RING)
    scale = jnp.ones(pool["k"].shape[:-1], jnp.float32)
    with pytest.raises(ValueError, match="fp pools only"):
        paged_attention(q[:, 0], pool["k"].astype(jnp.int8),
                        pool["v"].astype(jnp.int8), tbl, start,
                        k_scale=scale, v_scale=scale, layer=0, window=8)


# ---------------------------------------------------------------------------
# (f) the allocator over groups
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 4, 5, 19, 20, 21, 40, 64])
def test_f_a_sequence_holds_its_pages_and_two_bounded_rings(n):
    _, cfg, _ = toy()
    cache = PagedKVCache(cfg, max_slots=2, max_model_len=64, block_size=BS,
                         num_blocks=80, prefix_cache=False)
    pages = math.ceil(n / BS)
    want = pages + 2 * min(pages, RING)
    assert cache.blocks_for(n) == want
    assert cache.blocks_per_seq == 16 + 2 * RING
    blocks, _, _ = cache.admit(np.zeros((n,), np.int32))
    assert len(blocks) == want == cache.manager.blocks_in_use
    assert cache._pages_held(want) == pages
    assert cache.window_blocks(want) == 2 * min(pages, RING)
    cache.assign(1, blocks)
    row = cache.tables[1]
    assert (row[:pages] > 0).all() and not row[pages:16].any()
    for g in (0, 1):
        ring = row[16 + g * RING:16 + (g + 1) * RING]
        assert (ring[:min(pages, RING)] > 0).all()
        assert not ring[min(pages, RING):].any()
    assert sorted(row[row > 0]) == sorted(blocks)
    # grown a token at a time to 64, the table only ever gains entries
    for t in range(n + 1, 65):
        before = cache.tables[1].copy()
        assert cache.extend(1, blocks, t) is not None
        assert len(blocks) == cache.blocks_for(t)
        kept = before > 0
        assert (cache.tables[1][kept] == before[kept]).all()
    assert len(blocks) == 16 + 2 * RING
    cache.release(1, blocks)
    assert cache.manager.blocks_in_use == 0 and not cache.tables.any()


@pytest.mark.parametrize("family", ["dense", "pangu_ultra_moe"])
@pytest.mark.parametrize("tokens", [1, 16, 17, 4096])
def test_f_blocks_for_of_the_existing_families_is_unchanged(family, tokens):
    cfg = (LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=2)
           if family == "dense" else PanguUltraMoEConfig(
        vocab_size=64, hidden_size=32, intermediate_size=32,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=8,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_routed_experts=4, n_local_experts=4,
        num_experts_per_tok=2))
    cache = PagedKVCache(cfg, max_slots=2, max_model_len=4096, block_size=16,
                         num_blocks=300, prefix_cache=False)
    assert cache.uniform and cache.rings == (None,)
    assert cache.blocks_per_seq == 256
    assert cache.blocks_for(tokens) == cache.manager.blocks_for(tokens) \
        == max(1, math.ceil(tokens / 16))
    assert cache.window_blocks(cache.blocks_for(tokens)) == 0
    blocks, _, _ = cache.admit(np.zeros((tokens,), np.int32))
    cache.assign(0, blocks)
    assert list(cache.tables[0][:len(blocks)]) == blocks
    assert cache.extend(0, blocks, min(tokens + 40, 4096)) is not None
    assert list(cache.tables[0][:len(blocks)]) == blocks


@pytest.mark.parametrize("end", ["finished", "preempted", "timed_out"])
def test_f_every_end_gives_every_block_of_every_group_back(end):
    _, cfg, params = toy()
    blocks = {"finished": 80, "preempted": 40, "timed_out": 80}[end]
    eng = ServingEngine(params, cfg, serving(num_blocks=blocks))
    auditor = InvariantAuditor()
    rids = [eng.submit(ids_of(n, n), max_new_tokens=24, eos_token_id=None,
                       timeout_s=0.5 if end == "timed_out" and n == 30
                       else None) for n in (30, 21, 9)]
    held = 0
    while eng.pending:
        eng.step(max_iters=1)
        auditor.check(eng)
        held = max(held, eng.cache.manager.blocks_in_use)
        if end == "timed_out":
            time.sleep(0.05)
    st = eng.stats()
    assert held > 0 and eng.cache.manager.blocks_in_use == 0
    assert auditor.quiesce(eng, collect=True) == []
    if end == "preempted":
        assert st["preemptions"] >= 1
        for rid, n in zip(rids, (30, 21, 9)):
            alone = ServingEngine(params, cfg, serving(),
                                  programs=None).run(
                [ids_of(n, n)], max_new_tokens=24, eos_token_id=None)[0]
            np.testing.assert_array_equal(eng.request(rid).tokens, alone)
    if end == "timed_out":
        assert st["timed_out"] >= 1
    counters = st["spans"]["counters"]
    assert 0 < counters["kv_window_blocks_in_use_sum"] \
        < counters["kv_blocks_in_use_sum"]


# ---------------------------------------------------------------------------
# (g) the counters by hand
# ---------------------------------------------------------------------------

def test_g_counters_by_hand_on_fixed_lengths_and_a_fixed_routing():
    """Two rows in a mixed step, a decoding slot at position 21 and a
    6-token chunk from 10 on; the routers zeroed so that every token picks
    experts 0-3, of which this share holds 2 and 3."""
    _, cfg, params = toy(num_experts=8, expert_offset=2,
                         published_counts={"num_experts": 16})

    params = {**params, "periods": {
        k: {**v, "router": jnp.zeros_like(v["router"])}
        for k, v in params["periods"].items()}}
    M, Q, W = 2, 8, 16
    pool = L.init_paged_pool(cfg, 1 + M * (W + 2 * RING), BS)
    tables = jnp.asarray(1 + np.arange(M * (W + 2 * RING)).reshape(M, -1),
                         jnp.int32)
    tokens = jnp.asarray(ids_of(Q, 4, rows=M))
    _, _, c = L.paged_mixed_step(
        params, cfg, tokens, jnp.asarray([21, 10], jnp.int32),
        jnp.asarray([1, 6], jnp.int32), tables, pool,
        jnp.asarray([True, True]))
    c = dict(zip(L.PAGED_COUNTERS, np.asarray(c).tolist()))
    lanes, sparse = 7, 8
    # a zero router scores every expert 0.5: top_k takes ids 0-3, and this
    # share holds ids 2-9, so two picks of every token are local
    assert c["moe_pairs_total"] == lanes * 4 * sparse
    assert c["moe_pairs_local"] == lanes * 2 * sparse
    assert c["moe_expert_calls"] == 2 * sparse
    assert c["moe_rows_max"] == lanes * sparse
    positions = [21] + list(range(10, 16))
    assert c["full_tokens_read"] == 3 * sum(p + 1 for p in positions)
    assert c["window_tokens_read"] == 6 * sum(min(p + 1, 8)
                                              for p in positions)
    # whole pages a row's call copies: the full layers pages 0..5 and
    # 0..3; the window form from the page of the first query's lowest
    # position (21 - 7 = 14 -> page 3; 10 - 7 = 3 -> page 0)
    assert c["full_tokens_copied"] == 3 * (6 + 4) * BS
    assert c["window_tokens_copied"] == 6 * ((6 - 3) + (4 - 0)) * BS
    assert c["lanes_computed"] == M * Q          # one wave of min(16, 16)


# ---------------------------------------------------------------------------
# (h) the two rotary tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", [L.FULL, L.SLIDING])
def test_h_rotary_tables_are_the_formulas_beyond_the_original_context(kind):
    """At the published widths: YaRN on 64 of 128 lanes of a full layer
    (theta 5e5, factor 128, 8192 original positions, 32 and 1 turns),
    plain on all lanes of a sliding one, at positions beyond 8192."""
    cfg = L.LagunaConfig()
    pos = np.array([0, 1, 8191, 8192, 20000, 1048575])
    cos, sin = L._rope_tables(cfg, kind, jnp.asarray(pos))
    if kind == L.SLIDING:
        r, factor = 128, 1.0
        inv = 10000.0 ** (-2.0 * np.arange(64) / 128)
    else:
        r, factor = 64, 0.1 * math.log(128) + 1
        i = np.arange(32)
        extra = 500000.0 ** (-2.0 * i / 64)

        def dim(n):
            return 64 * math.log(8192 / (2 * math.pi * n)) / (
                2 * math.log(500000.0))
        low, high = math.floor(dim(32)), math.ceil(dim(1))
        assert (low, high) == (9, 18)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        inv = extra / 128 * ramp + extra * (1 - ramp)
        assert abs(factor - cfg.yarn_attention_factor) < 1e-12
    assert cos.shape == (6, r // 2)
    ang = pos[:, None].astype(np.float64) * inv[None, :]
    # float32 angles at a million positions: a few 1e-2 of a radian
    np.testing.assert_allclose(cos, np.cos(ang) * factor, atol=0.08)
    np.testing.assert_allclose(np.asarray(cos)[:4], (np.cos(ang) * factor)[:4],
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin)[:4], (np.sin(ang) * factor)[:4],
                               atol=2e-3)
    # and the reference's own table, written apart from the program's
    if kind == L.FULL:
        np.testing.assert_allclose(
            ref.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0), inv,
            rtol=1e-5)
        np.testing.assert_allclose(L.rope_inv_freq(cfg, kind)[0], inv,
                                   rtol=1e-5)
    # lanes past r pass through
    x = jnp.asarray(np.random.default_rng(0).standard_normal((6, 2, 128)),
                    jnp.float32)
    np.testing.assert_array_equal(L._rope(x, cos, sin)[..., r:], x[..., r:])


# ---------------------------------------------------------------------------
# (i) what the family does not serve raises
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("field,value", [
    ("lora_slots", 2), ("kv_quant", "int8"), ("quantize", "int8"),
    ("tp", 2), ("spec_decode", 2), ("prefix_cache", True),
    ("offload", True), ("prefill_chunk", None), ("prefill_chunk", 16)])
def test_i_validate_serving_raises_for_what_is_not_served(field, value):
    _, cfg, params = toy()
    kw = {field: value}
    if field == "offload":
        kw.update(prefix_cache=True, offload_blocks=8)
    if field == "lora_slots":
        kw.update(lora_rank=4, lora_pool=4)
    with pytest.raises(ValueError, match="laguna"):
        ServingEngine(params, cfg, serving(**kw))


@pytest.mark.parametrize("budget", ["one_wave", "none"])
def test_j_a_mixed_step_is_kept_to_one_wave_oldest_prompt_first(
        budget, monkeypatch):
    """The family names the lanes of one wave; the engine gives a mixed
    step the decode rows and the oldest prompts' chunks that fit beside
    them. With no budget (a family that names none) every prompt in
    prefill rides every step. The tokens are the same either way."""
    monkeypatch.setattr(L, "_WAVE_ROWS", 4)     # 4 x 3 slots = 12 lanes
    _, cfg, params = toy()
    eng = ServingEngine(params, cfg, serving())
    assert eng._lane_budget == L.mixed_lane_budget(cfg, 3) == 12
    if budget == "none":
        eng._lane_budget = None
    steps, choices = [], []
    inner, choose = eng._mixed_dispatch, eng._within_lane_budget

    def mixed(prefills, include_decode, emitted):
        steps.append([r.rid for r in prefills])
        return inner(prefills, include_decode, emitted)

    def within(prefills, decode_rows):
        kept = choose(prefills, decode_rows)
        choices.append(([r.rid for r in prefills], [r.rid for r in kept]))
        return kept

    eng._mixed_dispatch, eng._within_lane_budget = mixed, within
    # the first request ends early and the fourth takes its slot, slot 0,
    # while the third is still in prefill in slot 2
    prompts = [ids_of(n, n) for n in (30, 21, 30, 25)]
    outs = eng.run(prompts, max_new_tokens=[2, 30, 30, 12],
                   eos_token_id=None)
    if budget == "one_wave":
        # a chunk is 8 lanes of 12: one prompt a step beside the decode
        # rows, the oldest of those in prefill, whatever its slot
        assert all(kept == [min(rids)] for rids, kept in choices)
        assert steps == [kept for _, kept in choices]
        assert ([3, 2], [2]) in choices       # slot order is not age
    else:
        assert not choices and max(len(rids) for rids in steps) == 3
    alone = ServingEngine(params, cfg, serving())
    alone._lane_budget = None
    for p, o, n in zip(prompts, outs, (2, 30, 30, 12)):
        want = alone.run([p], max_new_tokens=n, eos_token_id=None)[0]
        np.testing.assert_array_equal(o, want)
    assert eng.cache.manager.blocks_in_use == 0
    assert InvariantAuditor().quiesce(eng, collect=True) == []
    dense = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_hidden_layers=2, num_attention_heads=2)
    assert getattr(paged_family(dense), "mixed_lane_budget", None) is None


def test_the_engine_finds_the_family_and_describes_it():
    _, cfg, params = toy()
    assert paged_family(cfg) is L
    eng = ServingEngine(params, cfg, serving())
    shown = eng.stats()["model"]
    assert shown["family"] == "laguna" and shown["n_local_experts"] == 16
    assert shown["num_attention_heads_per_layer"] == [4, 6, 6, 6, 4, 6, 6,
                                                      6, 4]
    assert L.paged_pool_block_bytes(cfg, BS) == 3 * BS * 2 * 2 * 16 * 4
    assert eng.cache.kv_bytes() == 80 * L.paged_pool_block_bytes(cfg, BS)


def test_counts_of_the_published_configuration_by_hand():
    """ISSUE 33's arithmetic from the configuration file."""
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-s-2.1-ep8-d9.json")) as f:
        c = json.load(f)
    assert model.attention_params(c, 48) == 44_187_648
    assert model.attention_params(c, 72) == 63_135_744
    assert model.expert_params(c) == 9_437_184
    assert model.total_params(c) == 3_199_460_352
    cfg = model.program_config(c, **c["program"])
    assert L.num_params(cfg) == model.total_params(c)
    assert (cfg.n_full, cfg.n_sliding, cfg.window_groups) == (3, 6, 2)
    assert cfg.ring_blocks(16) == 41
    assert model.cache_bytes_per_token(c) == 12_288
    assert model.window_cache_bytes_per_sequence(c, 128, 16) == \
        6 * 4096 * 656
    assert L.paged_pool_block_bytes(cfg, 16) == 196_608
    flops = model.serve_flops_per_token(c, 0)
    assert 1.6e9 < flops < 1.75e9
    assert model.serve_flops_per_token(c, 6000) > 1.2 * flops
    got = model.full_attention_counts(c, 1000, 100)
    assert got == {"flops": 1000 * 4 * 48 * 128, "bytes": 100 * 4096}
    assert model.window_attention_counts(c, 10)["flops"] == 10 * 4 * 72 * 128
