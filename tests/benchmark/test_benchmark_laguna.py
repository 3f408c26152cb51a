"""The family with window and full attention layers through the benchmark
on the CPU: the ``tiny-laguna.sat`` cell of a rehearsal tree of its own
(``rehearsal_laguna/``: new files only) runs a chip's share of the toy
through ``runners/serve.py`` and the sixteen readers the real cell
reports; a sliding layer that reads one position beyond its window, or a
picked local expert left out, turns ``correct`` false; the four readers
the configuration brought are held to a count by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, run as bench_run  # noqa: E402

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_laguna")
ROOTS = [os.path.join(REPO, "benchmark")]
CELL, REAL = "tiny-laguna.sat", "laguna118b-serve-mixedlen-sat"
NEW = ["window_attention_roofline_pct.sat",
       "full_attention_roofline_pct.sat", "kv_pool_used_pct.sat",
       "preemptions.sat"]
SHARED = ["live_slots_mean.sat", "mixed_dispatches_per_req.sat",
          "device_idle_pct.sat", "compiles_in_window.sat",
          "frontline_host_ms.sat", "step_host_ms.sat",
          "mixed_real_lane_pct.sat",
          "paged_attn_device_pct.sat", "moe_ffn_device_pct.sat",
          "moe_grouped_roofline_pct.sat", "moe_load_max_over_mean.sat",
          "mfu_pct.sat"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, compile_cache_config_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    yield


def run_cell(capsys, *argv):
    rc = bench_run.main(["--root", REHEARSAL, "--workload", CELL, *argv])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_share_runs_through_the_serving_runner(capsys, cache_dir,
                                                   runs_seen):
    rc, lines = run_cell(capsys, "--seed", str(2 ** 31 + 11), "--seconds",
                         "1.5", "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"out_tokens_per_s", "setup_s"}
    notes = {k: v for ln in lines[:-1] for k, v in ln.items()}
    assert notes["oracle"]["ok"] and len(notes["oracle"]["checked"]) == 4
    assert notes["compiles"]["in_window"] == 0
    assert notes["health"]["blocks_in_use"] == 0
    assert notes["health"]["audit_violations"] == 0
    # every prompt over a chunk crossed the mixed step, and a lane of a
    # sliding layer read at most its window while a full layer's read on
    (run,) = runs_seen
    a, b = (run[k]["spans"]["counters"] for k in ("stats_before",
                                                  "stats_after"))
    d = {k: b[k] - a.get(k, 0) for k in b}
    lanes = d["moe_pairs_total"] / 4 / 8          # top-4, 8 sparse layers
    assert lanes > 0
    assert d["window_tokens_read"] / (6 * lanes) <= 8
    assert d["full_tokens_read"] / (3 * lanes) > 8
    assert 0 < d["kv_window_blocks_in_use_sum"] < d["kv_blocks_in_use_sum"]


def test_the_traced_run_reads_the_cells_layer_metrics(capsys, cache_dir):
    rc, lines = run_cell(capsys, "--seed", "6", "--seconds", "1.5",
                         "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    got = lines[-1]["metrics"]
    # what needs a device plane or a chip's peaks reads nothing on a CPU
    # and is left out of the line; the counters' readers read
    assert {"moe_load_max_over_mean.sat", "step_host_ms.sat", "frontline_host_ms.sat",
            "live_slots_mean.sat", "mixed_real_lane_pct.sat",
            "mixed_dispatches_per_req.sat", "compiles_in_window.sat",
            "kv_pool_used_pct.sat", "preemptions.sat"} <= set(got)
    assert not {"moe_grouped_roofline_pct.sat", "mfu_pct.sat",
                "window_attention_roofline_pct.sat",
                "full_attention_roofline_pct.sat",
                "device_idle_pct.sat"} & set(got)
    assert 0 < got["kv_pool_used_pct.sat"]["value"] <= 100
    assert got["preemptions.sat"]["value"] >= 0


def test_a_sliding_layer_reading_one_position_beyond_its_window_is_not_correct(
        capsys, cache_dir, monkeypatch):
    """The program's sliding layers attend ``window + 1`` positions (the
    ring still holds that one): the engine serves on, healthy, and the
    reference, which masks at the window, sees it."""
    from paddle_tpu.models import laguna as L
    sound = L._attend_rows

    def one_beyond(q, pool, idx, tbl, start, dl, window, use_kernel):
        return sound(q, pool, idx, tbl, start, dl,
                     None if window is None else window + 1, use_kernel)

    monkeypatch.setattr(L, "_attend_rows", one_beyond)
    rc, lines = run_cell(capsys, "--seed", "31", "--seconds", "1.5",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    gap, faults = last["compared"]
    assert gap["name"] == "oracle_worst_gap" and gap["value"] > gap["limit"]
    assert faults == {"name": "health_faults", "value": 0.0, "limit": 0.0}


def test_a_picked_local_expert_left_out_is_not_correct(capsys, cache_dir,
                                                       monkeypatch):
    """The program drops each token's first pick; the reference routes by
    itself and keeps it."""
    from paddle_tpu.models import pangu_ultra_moe as P
    sound = P.route

    def first_pick_left_out(lp, m, cfg):
        ids, w = sound(lp, m, cfg)
        return ids, w.at[:, 0].set(0.0)

    monkeypatch.setattr(P, "route", first_pick_left_out)
    rc, lines = run_cell(capsys, "--seed", "23", "--seconds", "1.5",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    gap, faults = last["compared"]
    assert gap["name"] == "oracle_worst_gap" and gap["value"] > gap["limit"]
    assert faults == {"name": "health_faults", "value": 0.0, "limit": 0.0}


# What a family may hold of the repo's ``BENCHMARK.json``: what it BROUGHT,
# where it put it. Not what is last in ``per_layer`` and not the whole of a
# reader's ``workloads``: the next configuration appends its readers after
# these and its cell's name to the lists of the readers it reads in
# (``full_attention_roofline_pct.sat``, ``kv_pool_used_pct.sat`` and
# ``preemptions.sat`` read in any family with full layers over a block
# pool). The assertions are a ``check_*(bench)`` function, so the rehearsal
# of that PR (``test_benchmark_contract.py``) finds them and calls them on
# the dictionary it will leave; the ``test_`` hands them the repo's file.

def _listed(bench, cell):
    return [m for m in bench["per_layer"] if cell in m.get("workloads", ())]


def check_which_readers_list_the_cell(bench):
    """The real cell reads the twelve readers it shares with the cells
    before it and the four it brought, which stand where they were
    appended (right after ``mfu_pct.sat``, the last entry there was) and
    name this cell first; the rehearsal's entries are their twins. (Not
    ``decode_iter_wall_ms.sat``: in this cell nearly every step is a mixed
    step, and a window with no decode dispatch leaves that reader nothing
    to read.) ``bench``: the repo's ``BENCHMARK.json``, or a dictionary a
    test made of it."""
    from test_benchmark_spans import in_place_after
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        ours = _listed(json.load(f), CELL)
    assert [m["name"] for m in _listed(bench, REAL)] == SHARED + NEW
    assert in_place_after([m["name"] for m in bench["per_layer"]],
                          "mfu_pct.sat", NEW)
    assert [m["name"] for m in ours] == SHARED + NEW
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in ours:
        assert {k: m[k] for k in m if k != "workloads"} == {
            k: by_name[m["name"]][k] for k in m if k != "workloads"}
        src = open(os.path.join(REPO, "benchmark", "layer_metrics",
                                m["name"] + ".py")).read()
        assert f'LAYER = "{m["layer"]}"' in src
        assert f'MOVES = "{m["moves"]}"' in src
        assert f'UNIT = "{m["unit"]}"' in src
    for name in NEW:      # written for this cell; a later cell appends
        assert by_name[name]["workloads"].index(REAL) == 0
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["out_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixedlen-sat"
    assert cell["config"] == "laguna-s-2.1-ep8-d9"


def test_which_readers_list_the_cell(bench):
    check_which_readers_list_the_cell(bench)


@pytest.mark.parametrize("tree", ["toy", "real"])
def test_the_cut_keeps_the_guides_floors_and_the_lists_whole(tree):
    """The contract's own rules (``check_cut``) on the toy configuration
    of this tree, which the contract's cases do not walk, and what they
    mean for the real one: every published key unchanged but the three
    counts, the per-layer lists at their published length."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "contract", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "test_benchmark_contract.py"))
    contract = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(contract)
    base = (os.path.join(REHEARSAL, "bench") if tree == "toy"
            else os.path.join(REPO, "benchmark"))
    names = (("tiny-laguna-serve", "tiny-laguna") if tree == "toy"
             else ("laguna-s-2.1-ep8-d9", "laguna-s-2.1"))
    with open(os.path.join(base, "configs", names[0] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(base, "published", names[1] + ".json")) as f:
        published = json.load(f)["config"]
    contract.check_cut(cfg, published)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(cfg[key]) == published["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 9 and cfg["period"] == 4
    assert cfg["engine"]["prefix_cache"] is False
    with pytest.raises(AssertionError, match="at least 8 routed experts"):
        contract.check_cut({**cfg, "num_experts": 4}, published)
    with pytest.raises(AssertionError, match="differs from the published"):
        contract.check_cut({**cfg, "layer_types": cfg["layer_types"][:9]},
                           published)


def test_the_traffic_file_holds_the_issues_parameters():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "mixedlen-sat.json")) as f:
        mix = json.load(f)
    want = {"kind": "serve", "loop": "closed", "clients": 64,
            "requests": 2000, "ramp_s": 20.0, "edge_s": 10.0, "drain_s": 0.0,
            "order_block": 1, "pool": 512, "warm_wave_max": 8,
            "oracle_requests": 4, "oracle_max_len": 4096, "trace_s": 3.0,
            "max_total": 16384, "sampled_every": 2,
            "prompt": {"median": 4096, "sigma": 1.0, "min": 256,
                       "max": 15360},
            "output": {"median": 256, "sigma": 0.6, "min": 32, "max": 1024},
            "sampling": {"temperature": 0.8, "top_k": 20}}
    assert {k: mix[k] for k in want} == want
    from benchmark import traffic
    sizes = traffic.size_pool(mix)
    assert all(s["prompt_len"] > 128 for s in sizes)     # over a chunk
    assert all(s["prompt_len"] + s["output_len"] <= 16384 for s in sizes)
    greedy_short = [s for s in sizes if not s["sampled"] and
                    2048 < s["prompt_len"] + s["output_len"] <= 4096]
    assert len(greedy_short) >= 20    # the oracle finds one over 2,048


def _window(widths, seconds=50.0, traced=3.0):
    """A serving run's dictionary as the readers see it, by hand: 1,000
    decode iterations of 32 slots at a cache of 6,000 tokens (3 full and 6
    sliding layers) and 100 chunk rows of 128 lanes at a cache of 4,000."""
    lanes = 32 * 1000
    chunk_lanes, chunk_rows = 100 * 128, 100
    before = {"spans": {"counters": {}}, "model": widths, "chunks": 0,
              "preemptions": 1, "usable_blocks": 24575}
    after = {"model": widths, "chunks": 1100, "preemptions": 3,
             "usable_blocks": 24575, "spans": {"counters": {
                 "decode_tokens": lanes, "prefill_tokens": chunk_lanes,
                 "full_tokens_read": 3 * (lanes * 6000 + chunk_lanes * 4000),
                 "full_tokens_copied": 3 * (lanes * 6000 + chunk_rows * 4000),
                 "window_tokens_read": 6 * (lanes + chunk_lanes) * 512,
                 "window_tokens_copied": 6 * (lanes * 528 + chunk_rows * 656),
                 "kv_blocks_in_use_sum": 1100 * 12000,
                 "kv_window_blocks_in_use_sum": 1100 * 2600}}}
    return {"platform": "tpu", "device_kind": "TPU v5e",
            "stats_before": before, "stats_after": after,
            "window_s": seconds,
            "trace": {"window_s": traced, "busy_s": traced * 0.95,
                      "op_self_s": {
                          "%paged_attention_q1.3": traced * 0.20,
                          "%paged_attention_mq.4": traced * 0.10,
                          "%paged_attention_window_q1.2": traced * 0.04,
                          "%paged_attention_window_mq.5": traced * 0.02,
                          "%fusion.1": traced * 0.60}}}


def test_the_configurations_readers_by_hand():
    with open(os.path.join(REPO, "benchmark/configs/"
                           "laguna-s-2.1-ep8-d9.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "laguna", ROOTS)
    cfg = model.program_config(config, **config["program"])
    from paddle_tpu.models.laguna import describe
    run = _window(describe(cfg))

    def read(name):
        return harness.load_by_name("layer_metrics", name, ROOTS).read(run)

    full_read = 3 * (32000 * 6000 + 12800 * 4000)
    full_copied = 3 * (32000 * 6000 + 100 * 4000)
    assert read("full_attention_roofline_pct.sat") == pytest.approx(
        100 * max(full_copied * 4096 / 819e9,
                  full_read * 4 * 48 * 128 / 197e12) / (0.30 * 50.0))
    win_read = 6 * (32000 + 12800) * 512
    win_copied = 6 * (32000 * 528 + 100 * 656)
    assert read("window_attention_roofline_pct.sat") == pytest.approx(
        100 * max(win_copied * 4096 / 819e9,
                  win_read * 4 * 72 * 128 / 197e12) / (0.06 * 50.0))
    assert read("kv_pool_used_pct.sat") == pytest.approx(
        100 * 12000 / 24575)
    assert read("preemptions.sat") == 2
    assert read("paged_attn_device_pct.sat") == pytest.approx(
        100 * 0.36 / 0.95)
    for name in NEW[:3]:                   # a share stays under the whole
        assert 0 < read(name) < 100, name
    # the existing readers take this family's widths as they are reported
    assert read("mfu_pct.sat") == pytest.approx(
        100 * (32000 * model.serve_flops_per_token(config, 0) +
               12800 * model.serve_flops_per_token(config, 0, head=False))
        / (50.0 * 197e12))
    # a program that brings no such counters, as the parent's: nothing read
    run["stats_after"]["model"] = None
    assert read("window_attention_roofline_pct.sat") is None
    assert read("full_attention_roofline_pct.sat") is None
    del run["stats_after"]["spans"]["counters"]["kv_blocks_in_use_sum"]
    assert read("kv_pool_used_pct.sat") is None
    del run["stats_after"]["preemptions"]
    assert read("preemptions.sat") is None


def test_the_walk_carries_each_positions_least_margin_over_the_sparse_layers():
    import jax.numpy as jnp
    import numpy as np
    ref = harness.load_by_name("reference", "laguna", ROOTS)
    with open(os.path.join(REHEARSAL, "bench", "configs",
                           "tiny-laguna-serve.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "laguna", ROOTS)
    cfg = model.program_config(config, **config["program"])
    params = model.make_weights(cfg, 2 ** 31 + 3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, 24))
    state = ref.embed(params, ids)
    margins = [np.asarray(state["margin"])]
    for i in range(ref.n_blocks(params)):
        state = ref.block(params, i, state, config)
        margins.append(np.asarray(state["margin"]))
    assert len(margins) == 10
    assert np.isinf(margins[0]).all() and np.isinf(margins[1]).all()  # dense
    assert np.isfinite(margins[2]).all() and (margins[2] >= 0).all()
    for a, b in zip(margins[2:], margins[3:]):
        assert (b <= a).all()
    assert (margins[-1] < margins[2]).any()
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, state["x"], config)),
        np.asarray(ref.forward(params, ids, config)), atol=2e-5, rtol=0)
    flat = np.asarray(ref.head(params, state, {
        **config, "oracle": {"tie_margin": float(np.median(margins[-1]))}}))
    assert 0 < (~flat.any(-1)).sum() < 24
