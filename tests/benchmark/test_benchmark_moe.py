"""The latent-attention family with routed experts through the benchmark
on the CPU: the ``tiny-moe.sat`` cell of a rehearsal tree of its own
(``rehearsal_moe/``: new files only, the accepted rehearsal is not
touched) runs a chip's share of the toy through ``runners/serve.py`` and
every reader the family brought, a picked local expert left out of the
program turns ``correct`` false, and the roofline and utilisation readers
are held to a count by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, run as bench_run  # noqa: E402

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal_moe")
ROOTS = [os.path.join(REPO, "benchmark")]
NEW = ["moe_ffn_device_pct.sat", "moe_grouped_roofline_pct.sat",
       "latent_attn_roofline_pct.sat", "moe_load_max_over_mean.sat",
       "mfu_pct.sat"]
APPENDED = ["decode_iter_wall_ms.sat", "step_host_ms.sat",
            "frontline_host_ms.sat", "live_slots_mean.sat",
            "mixed_real_lane_pct.sat", "mixed_dispatches_per_req.sat",
            "device_idle_pct.sat", "compiles_in_window.sat",
            "paged_attn_device_pct.sat"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, compile_cache_config_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    yield


def run_cell(capsys, *argv):
    rc = bench_run.main(["--root", REHEARSAL, "--workload", "tiny-moe.sat",
                         *argv])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


def test_the_share_runs_through_the_serving_runner(capsys, cache_dir):
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


def test_the_traced_run_reads_the_familys_layer_metrics(capsys, cache_dir):
    rc, lines = run_cell(capsys, "--seed", "6", "--seconds", "1.5",
                         "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    got = lines[-1]["metrics"]
    # what needs a device plane or a chip's peaks reads nothing on a CPU
    # and is left out of the line; the counters' readers read
    assert {"moe_load_max_over_mean.sat", "decode_iter_wall_ms.sat",
            "step_host_ms.sat", "frontline_host_ms.sat",
            "live_slots_mean.sat", "mixed_real_lane_pct.sat",
            "mixed_dispatches_per_req.sat",
            "compiles_in_window.sat"} <= set(got)
    assert not {"moe_grouped_roofline_pct.sat", "mfu_pct.sat",
                "latent_attn_roofline_pct.sat",
                "device_idle_pct.sat"} & set(got)
    # 8 held experts, 4 picks of 16 a token, a few tokens a call: the
    # fullest expert holds more than the mean, never more than all rows
    assert 1.0 <= got["moe_load_max_over_mean.sat"]["value"] <= 8.0


def test_a_picked_local_expert_left_out_is_not_correct(capsys, cache_dir,
                                                       monkeypatch):
    """The program drops each token's first pick (its weight set to zero
    after the weights were normalised); the reference routes by itself and
    keeps it: the engine serves on, healthy, and the oracle sees it."""
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


# Since PR 32 the real cell reports every reader this tree's toy cell
# does: the nine it shares with the dense family (``APPENDED``: the cell's
# name appended to their ``workloads``) and the family's own five (``NEW``:
# entries appended at the end of ``per_layer``). A family's test file holds
# of the repo's ``BENCHMARK.json`` only what the family brought, never what
# is last in ``per_layer`` nor the whole of a reader's ``workloads``: the
# next configuration appends its cell's name to the lists of the readers
# that read in it and its own readers' entries after whatever is last then
# (``benchmark/__init__.py``). How that is held, since PR 35 (PR 33's own
# file had pinned both again): a file's structural assertions are a
# ``check_*(bench)`` function, as below; ``test_benchmark_contract.py``'s
# rehearsal of that PR finds every such function of every
# ``test_benchmark_*.py`` (``structural_checks``: none is listed by hand)
# and calls it on the dictionary that PR will leave; and
# ``test_no_test_goes_round_the_rehearsal`` reads the sources: the repo's
# file is opened by the ``bench`` fixture alone (``conftest.py``), a
# ``test_`` only hands it to a ``check_*``, nothing indexes ``per_layer``
# from its end or holds a ``workloads`` equal to a list.

def _listed(bench, cell):
    return [m for m in bench["per_layer"] if cell in m.get("workloads", ())]


def check_which_readers_list_the_cell(bench):
    """``bench``: the repo's ``BENCHMARK.json``, or a dictionary a test
    made of it."""
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        ours = _listed(json.load(f), "tiny-moe.sat")
    assert {m["name"] for m in ours} == set(NEW + APPENDED)
    real = _listed(bench, "pangu718b-serve-reason-sat")
    assert {m["name"] for m in real} == set(NEW + APPENDED)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in ours:      # the rehearsal's entries are the real ones' twins
        assert {k: m[k] for k in m if k != "workloads"} == {
            k: by_name[m["name"]][k] for k in m if k != "workloads"}
        path = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".py")
        src = open(path).read()
        assert f'LAYER = "{m["layer"]}"' in src
        assert f'MOVES = "{m["moves"]}"' in src
        assert f'UNIT = "{m["unit"]}"' in src


def test_which_readers_list_the_cell(bench):
    check_which_readers_list_the_cell(bench)


def test_the_toys_cut_keeps_the_guides_floors():
    """The contract's own rules (``check_cut``) on the toy configuration
    of this tree, which the contract's cases do not walk."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "contract", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "test_benchmark_contract.py"))
    contract = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(contract)
    base = os.path.join(REHEARSAL, "bench")
    with open(os.path.join(base, "configs", "tiny-moe-serve.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(base, "published", "tiny-pangu.json")) as f:
        published = json.load(f)["config"]
    contract.check_cut(cfg, published)
    with pytest.raises(AssertionError, match="at least 8 routed experts"):
        contract.check_cut({**cfg, "n_routed_experts": 4,
                            "published_counts": {
                                **cfg["published_counts"]}}, published)


def _window(model_widths, seconds=50.0, traced=3.0):
    """A serving run's dictionary as the readers see it, by hand: 1,000
    decode iterations of 64 slots at a cache of 600 tokens over four expert
    layers and five layers of attention."""
    decode = 64 * 1000
    before = {"spans": {"counters": {}}, "model": model_widths}
    after = {"model": model_widths, "spans": {"counters": {
        "decode_tokens": decode, "prefill_tokens": 0,
        "moe_pairs_total": decode * 8 * 4, "moe_pairs_local": decode * 2,
        "moe_expert_calls": 1000 * 4 * 14, "moe_rows_max": 1000 * 4 * 5,
        "latent_tokens_read": decode * 600 * 5}}}
    return {"platform": "tpu", "device_kind": "TPU v5e",
            "stats_before": before, "stats_after": after,
            "window_s": seconds,
            "trace": {"window_s": traced, "busy_s": traced * 0.95,
                      "op_self_s": {
                          "%moe_grouped_matmul_gated.3": traced * 0.20,
                          "%moe_grouped_matmul.4": traced * 0.10,
                          "%paged_attention_latent.2": traced * 0.05,
                          "%fusion.1": traced * 0.60}}}


def test_the_familys_readers_by_hand():
    with open(os.path.join(REPO, "benchmark/configs/"
                           "openpangu-ultra-moe-718b-ep16-d5.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "pangu_ultra_moe", ROOTS)
    cfg = model.program_config(config, **config["program"])
    from paddle_tpu.models.pangu_ultra_moe import describe
    run = _window(describe(cfg))

    def read(name):
        return harness.load_by_name("layer_metrics", name, ROOTS).read(run)

    assert read("moe_ffn_device_pct.sat") == pytest.approx(
        100 * 0.30 / 0.95)
    # 14 of 16 experts called a layer: 56,000 calls x 94.37 MB of weights
    calls, rows = 1000 * 4 * 14, 64 * 1000 * 2
    weight_s = (calls * 3 * 7680 * 2048 * 2 +
                rows * (2 * 7680 + 2 * 2048) * 2) / 819e9
    assert read("moe_grouped_roofline_pct.sat") == pytest.approx(
        100 * weight_s / (0.30 * 50.0))
    tokens = 64 * 1000 * 600 * 5
    assert read("latent_attn_roofline_pct.sat") == pytest.approx(
        100 * max(tokens * 1152 / 819e9,
                  tokens * 2 * 128 * 1088 / 197e12) / (0.05 * 50.0))
    assert read("moe_load_max_over_mean.sat") == pytest.approx(
        1000 * 4 * 5 * 16 / (64 * 1000 * 2))
    per_token = model.serve_flops_per_token(config, 600)
    assert read("mfu_pct.sat") == pytest.approx(
        100 * 64 * 1000 * per_token / (50.0 * 197e12))
    for name in NEW:                       # a share of a peak stays under it
        if name != "moe_load_max_over_mean.sat":
            assert 0 < read(name) < 100, name
    # a program that brings no such counters, as the parent's: nothing read
    run["stats_after"]["model"] = None
    assert all(read(name) is None for name in NEW[1:])
    run["trace"]["op_self_s"] = {"%fusion.1": 1.0}
    assert read("moe_ffn_device_pct.sat") is None


# ---- the positions the reference does not judge (its module docstring) ----

def _reference():
    return harness.load_by_name("reference", "pangu_ultra_moe", ROOTS)


@pytest.mark.parametrize("logits,edge", [
    # picks {0, 1}; held expert 2 lies 0.1 under the weakest pick
    ([5.0, 4.0, 3.9, 1.0, 0.0, -1.0], 0.1),
    # picks {0, 2}; held 2 is IN, 0.3 over the best left out, which is
    # held 3: either way one of them is 0.3 from changing sides
    ([5.0, 1.0, 4.0, 3.7, 0.0, -1.0], 0.3),
    # the eighth-against-ninth tie is between two experts held ELSEWHERE
    # (1 and 4): nothing held here is near the edge, the position is sound
    ([5.0, 3.0, 1.0, 0.0, 3.001, -1.0], 2.001),
    # a held expert tied with the weakest pick exactly
    ([5.0, 4.0, 4.0, 1.0, 0.0, -1.0], 0.0),
])
def test_routing_margin_by_hand(logits, edge):
    """Six experts, two a token, experts 2 and 3 held here: the margin is
    the nearest held expert's distance from the edge of the top-2 over the
    standard deviation of the token's six logits."""
    import jax.numpy as jnp
    import numpy as np
    ref = _reference()
    z = np.asarray(logits, np.float32)
    lp = {"router": jnp.asarray(z[None, :]),          # m = [[1]] -> z
          "w_gu": jnp.zeros((2, 1, 2))}
    cfg = {"num_experts_per_tok": 2, "expert_offset": 2}
    got = ref.routing_margin(jnp.ones((1, 1)), lp, cfg)
    np.testing.assert_allclose(np.asarray(got), [edge / z.std()], rtol=1e-5,
                               atol=1e-7)


@pytest.mark.parametrize("tie,flat", [(None, []), (0.01, [2]),
                                      (1.0, [1, 2])])
def test_head_gives_a_flat_row_where_the_margin_is_under_the_configurations(
        tie, flat):
    """Rows under ``oracle.tie_margin`` are all zero (every token is the
    maximum there, so the runner's gap reads 0), the others are the plain
    logits; a configuration without the key judges every row."""
    import jax.numpy as jnp
    import numpy as np
    ref = _reference()
    rng = np.random.default_rng(0)
    params = {"ln_f": jnp.ones((8,)),
              "lm_head": jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    cfg = {"rms_norm_eps": 1e-5}
    if tie is not None:
        cfg["oracle"] = {"tolerance": 0.1, "tie_margin": tie}
    state = {"x": x, "margin": jnp.asarray([np.inf, 0.5, 0.001])}
    got = np.asarray(ref.head(params, state, cfg))
    plain = np.asarray(ref.logits(params, x, cfg))
    for row in range(3):
        want = np.zeros(5) if row in flat else plain[row]
        np.testing.assert_array_equal(got[row], want)


def test_the_walk_carries_each_positions_least_margin_over_the_expert_layers():
    import jax.numpy as jnp
    import numpy as np
    ref = _reference()
    with open(os.path.join(REHEARSAL, "bench", "configs",
                           "tiny-moe-serve.json")) as f:
        config = json.load(f)
    model = harness.load_by_name("models", "pangu_ultra_moe", ROOTS)
    cfg = model.program_config(config, **config["program"])
    params = model.make_weights(cfg, 2 ** 31 + 3)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, 24))
    state = ref.embed(params, ids)
    margins = [np.asarray(state["margin"])]
    for i in range(ref.n_blocks(params)):
        state = ref.block(params, i, state, config)
        margins.append(np.asarray(state["margin"]))
    assert np.isinf(margins[0]).all() and np.isinf(margins[1]).all()  # dense
    assert np.isfinite(margins[2]).all() and (margins[2] >= 0).all()
    for a, b in zip(margins[2:], margins[3:]):
        assert (b <= a).all()
    assert (margins[-1] < margins[2]).any()
    # the walk's plain logits are forward's, whatever head leaves unjudged
    np.testing.assert_allclose(
        np.asarray(ref.logits(params, state["x"], config)),
        np.asarray(ref.forward(params, ids, config)), atol=2e-5, rtol=0)
