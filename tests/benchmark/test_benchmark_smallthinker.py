"""SmallThinker through the benchmark on the CPU: the
``tiny-smallthinker.sat`` cell of a rehearsal tree of its own
(``rehearsal_smallthinker/``: new files only) runs a toy of the window/full
family as that architecture has it (NoPE full layers, a router on the
layer's input, ReGLU experts held whole) through ``runners/serve.py`` and
the nineteen readers the real cell reports; a window layer that reads one
position beyond its window, or the router fed the normed stream, turns
``correct`` false; the readers take this family's counts from the
names the engine reports."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, run as bench_run  # noqa: E402

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_smallthinker")
ROOTS = [os.path.join(REPO, "benchmark")]
CELL, REAL = "tiny-smallthinker.sat", "smallthinker21b-serve-longdoc-sat"
# the sixteen readers the window/full family's first cell reads and the
# engine step's three wall readers, in their order in ``per_layer``; this
# cell brings none
READERS = ["mixed_wall_p50_ms.sat", "decode_wall_p50_ms.sat",
           "live_slots_mean.sat", "mixed_dispatches_per_req.sat",
           "device_idle_pct.sat", "compiles_in_window.sat",
           "frontline_host_ms.sat", "step_host_ms.sat",
           "decode_iter_wall_ms.sat", "mixed_real_lane_pct.sat",
           "paged_attn_device_pct.sat", "moe_ffn_device_pct.sat",
           "moe_grouped_roofline_pct.sat", "moe_load_max_over_mean.sat",
           "mfu_pct.sat",
           "window_attention_roofline_pct.sat",
           "full_attention_roofline_pct.sat", "kv_pool_used_pct.sat",
           "preemptions.sat"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, compile_cache_config_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    yield


def run_cell(capsys, *argv):
    rc = bench_run.main(["--root", REHEARSAL, "--workload", CELL, *argv])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_toy_runs_through_the_serving_runner(capsys, cache_dir,
                                                 runs_seen):
    rc, lines = run_cell(capsys, "--seed", str(2 ** 31 + 17), "--seconds",
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
    # a lane of a window layer read at most its window while a full
    # layer's read on; every layer is sparse and every expert held here
    (run,) = runs_seen
    a, b = (run[k]["spans"]["counters"] for k in ("stats_before",
                                                  "stats_after"))
    d = {k: b[k] - a.get(k, 0) for k in b}
    assert d["moe_pairs_local"] == d["moe_pairs_total"] > 0
    lanes = d["moe_pairs_total"] / 4 / 8          # top-4, 8 sparse layers
    assert d["window_tokens_read"] / (6 * lanes) <= 8
    assert d["full_tokens_read"] / (2 * lanes) > 8
    assert 0 < d["kv_window_blocks_in_use_sum"] < d["kv_blocks_in_use_sum"]
    assert run["stats_after"]["model"]["family"] == "smallthinker"


def test_the_traced_run_reads_the_cells_layer_metrics(capsys, cache_dir):
    rc, lines = run_cell(capsys, "--seed", "8", "--seconds", "1.5",
                         "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    got = lines[-1]["metrics"]
    # what needs a device plane or a chip's peaks reads nothing on a CPU
    # and is left out of the line; the counters' readers read
    assert {"moe_load_max_over_mean.sat", "step_host_ms.sat",
            "frontline_host_ms.sat", "live_slots_mean.sat",
            "mixed_real_lane_pct.sat", "mixed_dispatches_per_req.sat",
            "compiles_in_window.sat", "kv_pool_used_pct.sat",
            "preemptions.sat"} <= set(got)
    assert not {"moe_grouped_roofline_pct.sat", "mfu_pct.sat",
                "window_attention_roofline_pct.sat",
                "full_attention_roofline_pct.sat",
                "device_idle_pct.sat"} & set(got)
    assert 0 < got["kv_pool_used_pct.sat"]["value"] <= 100


def _one_beyond_the_window(monkeypatch):
    """The program's window layers attend ``window + 1`` positions (the
    ring still holds that one)."""
    from paddle_tpu.models import laguna as L
    sound = L._attend_rows

    def one_beyond(q, pool, idx, tbl, start, dl, window, use_kernel):
        return sound(q, pool, idx, tbl, start, dl,
                     None if window is None else window + 1, use_kernel)

    monkeypatch.setattr(L, "_attend_rows", one_beyond)


def _router_fed_the_normed_stream(monkeypatch):
    """The program routes on ``N_in(x)`` where the reference routes on
    ``x``."""
    from paddle_tpu.models import laguna as L, pangu_ultra_moe as P
    sound = P.route
    monkeypatch.setattr(P, "route", lambda lp, r, cfg: sound(
        lp, L._norm(r, lp["ln_in"], cfg), cfg))


@pytest.mark.parametrize("fault,seed", [
    (_one_beyond_the_window, 31), (_router_fed_the_normed_stream, 23)],
    ids=["one-beyond-the-window", "router-fed-the-normed-stream"])
def test_a_planted_fault_is_not_correct(capsys, cache_dir, monkeypatch,
                                        fault, seed):
    """The engine serves on, healthy; the reference, which computes the
    architecture by itself, sees the fault in the emitted tokens."""
    fault(monkeypatch)
    rc, lines = run_cell(capsys, "--seed", str(seed), "--seconds", "1.5",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    gap, faults = last["compared"]
    assert gap["name"] == "oracle_worst_gap" and gap["value"] > gap["limit"]
    assert faults == {"name": "health_faults", "value": 0.0, "limit": 0.0}


# What this family holds of the repo's ``BENCHMARK.json``: its cell, and
# that the cell is AMONG the lists of the readers it reads in. Nothing of
# where those entries stand in ``per_layer`` or of who else is listed:
# a later configuration appends after them. The assertions are a
# ``check_*(bench)`` function, so the rehearsal of that PR
# (``test_benchmark_contract.py``) finds them and calls them on the
# dictionary it will leave; the ``test_`` hands them the repo's file.

def _listed(bench, cell):
    return [m for m in bench["per_layer"] if cell in m.get("workloads", ())]


def check_smallthinker_cell_and_its_readers(bench):
    """The real cell reads the sixteen readers of the window/full family
    and the engine step's three wall readers, and brings none; the
    rehearsal's entries are their twins; the cell is a 1-chip cell on its
    configuration and mix, and reports ``out_tokens_per_s``. ``bench``:
    the repo's ``BENCHMARK.json``, or a dictionary a test made of it."""
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        ours = _listed(json.load(f), CELL)
    assert [m["name"] for m in _listed(bench, REAL)] == READERS
    assert [m["name"] for m in ours] == READERS
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in ours:
        assert {k: m[k] for k in m if k != "workloads"} == {
            k: by_name[m["name"]][k] for k in m if k != "workloads"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert REAL in e2e["out_tokens_per_s"]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-sat"
    assert cell["config"] == "smallthinker-21ba3b-d8"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]


def test_which_readers_list_the_cell(bench):
    check_smallthinker_cell_and_its_readers(bench)


@pytest.mark.parametrize("tree", ["toy", "real"])
def test_the_cut_keeps_the_guides_floors_and_the_lists_whole(tree):
    """The contract's own rules (``check_cut``) on the toy configuration
    of this tree, which the contract's cases do not walk, and what they
    mean for the real one: every published key unchanged but depth, the
    layout lists at their published length."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "contract", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "test_benchmark_contract.py"))
    contract = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(contract)
    base = (os.path.join(REHEARSAL, "bench") if tree == "toy"
            else os.path.join(REPO, "benchmark"))
    names = (("tiny-smallthinker-serve", "tiny-smallthinker") if tree == "toy"
             else ("smallthinker-21ba3b-d8", "smallthinker-21ba3b-instruct"))
    with open(os.path.join(base, "configs", names[0] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(base, "published", names[1] + ".json")) as f:
        published = json.load(f)["config"]
    contract.check_cut(cfg, published)
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        assert len(cfg[key]) == published["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 and cfg["period"] == 4
    assert cfg["engine"]["prefix_cache"] is False
    with pytest.raises(AssertionError, match="differs from the published"):
        contract.check_cut({**cfg, "rope_layout": cfg["rope_layout"][:8]},
                           published)
    with pytest.raises(AssertionError, match="never name a width"):
        contract.check_cut({**cfg, "reduced": ["num_hidden_layers",
                                               "moe_ffn_hidden_size"]},
                           published)


def test_the_traffic_file_holds_the_cells_parameters():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "longdoc-sat.json")) as f:
        mix = json.load(f)
    want = {"kind": "serve", "loop": "closed", "clients": 64, "ramp_s": 20.0,
            "drain_s": 0.0, "order_block": 1, "sampled_every": 2,
            "oracle_requests": 4, "oracle_max_len": 8192,
            "max_total": 16384,
            "prompt": {"median": 6144, "sigma": 0.5, "min": 1024,
                       "max": 14336},
            "output": {"median": 512, "sigma": 0.6, "min": 64, "max": 2048},
            "sampling": {"temperature": 0.8, "top_k": 20}}
    assert {k: mix[k] for k in want} == want
    from benchmark import traffic
    sizes = traffic.size_pool(mix)
    assert all(s["prompt_len"] + s["output_len"] <= 16384 for s in sizes)
    # most prompts pass the window and a chunk: the rings wrap in prefill
    over = sum(s["prompt_len"] > 4096 + 128 for s in sizes) / len(sizes)
    assert 0.7 < over < 0.85
    # the oracle finds greedy sequences past the window under its cap
    judged = [s for s in sizes if not s["sampled"] and
              4224 < s["prompt_len"] and
              s["prompt_len"] + s["output_len"] <= 8192]
    assert len(judged) >= 40


def _window(widths, seconds=50.0, traced=3.0):
    """A serving run's dictionary as the readers see it, by hand: 1,000
    decode iterations of 32 slots at a cache of 8,000 tokens (2 full and 6
    window layers) and 100 chunk rows of 128 lanes at a cache of 6,000."""
    lanes = 32 * 1000
    chunk_lanes, chunk_rows = 100 * 128, 100
    before = {"spans": {"counters": {}}, "model": widths, "chunks": 0,
              "preemptions": 0, "usable_blocks": 49151}
    after = {"model": widths, "chunks": 1100, "preemptions": 0,
             "usable_blocks": 49151, "spans": {"counters": {
                 "decode_tokens": lanes, "prefill_tokens": chunk_lanes,
                 "moe_pairs_local": 8 * 6 * (lanes + chunk_lanes),
                 "moe_expert_calls": 8 * 64 * 1100,
                 "full_tokens_read": 2 * (lanes * 8000 + chunk_lanes * 6000),
                 "full_tokens_copied": 2 * (lanes * 8000 + chunk_rows * 6000),
                 "window_tokens_read": 6 * (lanes + chunk_lanes) * 4096,
                 "window_tokens_copied": 6 * (lanes * 4112 +
                                              chunk_rows * 4240),
                 "kv_blocks_in_use_sum": 1100 * 38000,
                 "kv_window_blocks_in_use_sum": 1100 * 25000}}}
    return {"platform": "tpu", "device_kind": "TPU v5e",
            "stats_before": before, "stats_after": after,
            "window_s": seconds,
            "trace": {"window_s": traced, "busy_s": traced * 0.95,
                      "op_self_s": {
                          "%paged_attention_q1.3": traced * 0.10,
                          "%paged_attention_mq.4": traced * 0.05,
                          "%paged_attention_window_q1.2": traced * 0.15,
                          "%paged_attention_window_mq.5": traced * 0.10,
                          "%moe_grouped_matmul_gated.7": traced * 0.20,
                          "%moe_grouped_matmul.8": traced * 0.10,
                          "%fusion.1": traced * 0.25}}}


def test_the_configurations_readers_by_hand():
    """The readers the cell lists load THIS family's counts by the name
    the engine reports and read its widths from ``stats()``."""
    with open(os.path.join(REPO, "benchmark/configs/"
                           "smallthinker-21ba3b-d8.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "smallthinker", ROOTS)
    cfg = model.program_config(config, **config["program"])
    from paddle_tpu.models.laguna import describe
    run = _window(describe(cfg))

    def read(name):
        return harness.load_by_name("layer_metrics", name, ROOTS).read(run)

    full_read = 2 * (32000 * 8000 + 12800 * 6000)
    full_copied = 2 * (32000 * 8000 + 100 * 6000)
    assert read("full_attention_roofline_pct.sat") == pytest.approx(
        100 * max(full_copied * 2048 / 819e9,
                  full_read * 4 * 28 * 128 / 197e12) / (0.15 * 50.0))
    win_read = 6 * (32000 + 12800) * 4096
    win_copied = 6 * (32000 * 4112 + 100 * 4240)
    assert read("window_attention_roofline_pct.sat") == pytest.approx(
        100 * max(win_copied * 2048 / 819e9,
                  win_read * 4 * 28 * 128 / 197e12) / (0.25 * 50.0))
    rows, calls = 8 * 6 * 44800, 8 * 64 * 1100
    assert read("moe_grouped_roofline_pct.sat") == pytest.approx(
        100 * max(rows * 6 * 2560 * 768 / 197e12,
                  (calls * 3 * 2560 * 768 * 2 +
                   rows * (2 * 2560 + 2 * 768) * 2) / 819e9) / (0.30 * 50.0))
    assert read("kv_pool_used_pct.sat") == pytest.approx(100 * 38000 / 49151)
    assert read("paged_attn_device_pct.sat") == pytest.approx(
        100 * 0.40 / 0.95)
    assert read("mfu_pct.sat") == pytest.approx(
        100 * (32000 * model.serve_flops_per_token(config, 0) +
               12800 * model.serve_flops_per_token(config, 0, head=False))
        / (50.0 * 197e12))
    for name in ("full_attention_roofline_pct.sat",
                 "window_attention_roofline_pct.sat",
                 "moe_grouped_roofline_pct.sat", "mfu_pct.sat"):
        assert 0 < read(name) < 100, name
    # a program that describes no such family, as the parent's: nothing
    run["stats_after"]["model"] = None
    assert read("window_attention_roofline_pct.sat") is None
    assert read("moe_grouped_roofline_pct.sat") is None


def test_the_walk_carries_each_positions_least_margin():
    """Every layer is sparse, so the margin falls from the first block
    on; ``head`` flattens the positions under the tie margin and only
    those, and the walk's logits are ``forward``'s."""
    import jax.numpy as jnp
    import numpy as np
    ref = harness.load_by_name("reference", "smallthinker", ROOTS)
    with open(os.path.join(REHEARSAL, "bench", "configs",
                           "tiny-smallthinker-serve.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "smallthinker", ROOTS)
    cfg = model.program_config(config, **config["program"])
    params = model.make_weights(cfg, 2 ** 31 + 3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, 24))
    state = ref.embed(params, ids)
    margins = [np.asarray(state["margin"])]
    for i in range(ref.n_blocks(params)):
        state = ref.block(params, i, state, config)
        margins.append(np.asarray(state["margin"]))
    assert len(margins) == 9
    assert np.isinf(margins[0]).all()
    assert np.isfinite(margins[1]).all() and (margins[1] >= 0).all()
    for a, b in zip(margins[1:], margins[2:]):
        assert (b <= a).all()
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, state["x"], config)),
        np.asarray(ref.forward(params, ids, config)), atol=2e-5, rtol=0)
    tie = float(np.median(margins[-1]))
    flat = np.asarray(ref.head(params, state, {
        **config, "oracle": {"tie_margin": tie}}))
    assert ((~flat.any(-1)) == (margins[-1] < tie)).all()
    assert 0 < (~flat.any(-1)).sum() < 24
