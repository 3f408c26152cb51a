"""The harness end to end at a tiny size on the CPU, and the program held
to the benchmark's plain reference.

The tiny configuration and its mixes live under ``rehearsal/`` and are in
no cell of the repo's ``BENCHMARK.json``; what they report here is counts
and control flow, never a device number. Nothing in this directory
describes a TPU topology or loads libtpu (``benchmark/rehearse.py`` is a
script run by hand).
"""

import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.reference import mistral as ref   # noqa: E402

REHEARSAL = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "rehearsal")
ROOTS = [os.path.join(REPO, "benchmark")]
model = harness.load_by_name("models", "llama", ROOTS)
# the contract's keys, and last the numbers compared beside their limits
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device",
             "compared"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, compile_cache_config_restored):
    """run.py turns the persistent compile cache on: keep it out of the
    checkout, and put jax's settings back afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    yield


def run_cell(capsys, *argv):
    rc = bench_run.main(list(argv))
    captured = capsys.readouterr()
    run_cell.stderr = captured.err.strip().splitlines()
    return rc, [json.loads(ln) for ln in captured.out.strip().splitlines()]


E2E = {"tiny.sat": {"out_tokens_per_s", "setup_s"},
       "tiny.steady": {"ttft_p90_ms", "tpot_p90_ms", "setup_s"},
       "tiny.train": {"train_tokens_per_s", "setup_s"},
       "tiny.train4": {"train_tokens_per_s", "setup_s"},
       # a model benchmark/ has no file for, brought by the rehearsal tree
       "toy.train": {"train_tokens_per_s", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_and_prints_the_contracts_last_line(cell, capsys,
                                                      cache_dir):
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload", cell,
                         "--seed", str(2 ** 31 + 7), "--seconds", "1",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert list(last) == LINE_KEYS            # the contract's, in order
    assert last["correct"] is True and last["failed"] == 0
    for c in last["compared"]:                # each number under its limit
        assert set(c) == {"name", "value", "limit"}
        assert c["value"] <= c["limit"], c
    # ... and the same numbers end standard error, one a line
    assert run_cell.stderr[-len(last["compared"]):] == [
        f"compared {c['name']}: {c['value']!r} (limit {c['limit']!r})"
        for c in last["compared"]]
    assert last["attempted"] > 0
    assert set(last["metrics"]) == E2E[cell]
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    notes = {k: v for ln in lines[:-1] for k, v in ln.items()}
    assert notes["compiles"]["in_window"] == 0
    if "train" in cell:
        chk = notes["reference_check"]        # float32 here: tight
        assert abs(chk["loss"] - chk["ref_loss"]) < 1e-4
        assert abs(chk["grad_norm"] - chk["ref_grad_norm"]) < \
            1e-4 * chk["ref_grad_norm"]
    else:
        assert notes["oracle"]["ok"] and len(notes["oracle"]["checked"]) == 4
        assert notes["health"]["blocks_in_use"] == 0


def test_traced_run_reports_the_cells_layer_metrics(capsys, cache_dir,
                                                    runs_seen):
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload",
                         "tiny.sat", "--seed", "5", "--seconds", "1.5",
                         "--trace", "1")
    assert rc == 0
    got = lines[-1]["metrics"]
    # the trace-backed metric finds no device plane on a CPU: its reader
    # returns nothing and the harness leaves it out of the line
    assert "device_idle_pct.sat" not in got
    assert {"mixed_wall_p50_ms.sat", "decode_wall_p50_ms.sat",
            "live_slots_mean.sat", "mixed_dispatches_per_req.sat",
            "compiles_in_window.sat"} <= set(got)
    assert got["compiles_in_window.sat"]["value"] == 0
    # a share of the slots; the monitor's one sample of this window can
    # come late on a loaded CPU, after the clients were cut, and read 0.
    # That slots were live is judged by COUNT: in one of the monitor's
    # readings at least, the window's edges among them
    assert 0 <= got["live_slots_mean.sat"]["value"] <= 100
    (run,) = runs_seen
    assert run["samples"] and run["max_slots"] == 4
    assert any(s["live_slots"] > 0 for s in (
        run["stats_before"], *run["samples"], run["stats_after"]))


@pytest.mark.parametrize("live,want", [([4, 2], 75.0), ([0], 0.0),
                                       ([], None)])
def test_live_slots_mean_by_hand(live, want):
    """The reader's arithmetic where no clock is: the samples' mean over
    ``max_slots``; no sample, nothing read."""
    read = harness.load_by_name("layer_metrics", "live_slots_mean.sat",
                                ROOTS).read
    run = {"samples": [{"live_slots": n, "queued": 0} for n in live],
           "max_slots": 4}
    assert read(run) == want


def test_new_cell_config_and_metric_are_files_found_by_name(
        tmp_path, capsys, cache_dir):
    """A later PR adds a model, a mix and a layer metric by adding files
    and entries to BENCHMARK.json; it edits no file that is there."""
    root = tmp_path / "tree"
    shutil.copytree(REHEARSAL, root)
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    cfg = json.loads((root / "bench/configs/tiny-serve.json").read_text())
    cfg.update(name="tiny-wide", hidden_size=96, num_attention_heads=6,
               num_key_value_heads=3)
    (root / "bench/configs/tiny-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/tiny-sat.json").read_text())
    mix.update(clients=3, prompt={"median": 6, "sigma": 0.3, "min": 3,
                                  "max": 8})
    (root / "bench/traffic/tiny-short.json").write_text(json.dumps(mix))
    os.makedirs(root / "bench/layer_metrics")
    (root / "bench/layer_metrics/retired_per_s.new.py").write_text(
        'LAYER = "engine step"\nMOVES = "out_tokens_per_s"\nUNIT = "1/s"\n'
        'def read(run):\n'
        '    a, b = run["stats_before"], run["stats_after"]\n'
        '    return (b["retired"] - a["retired"]) / run["window_s"]\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "none",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "added by the test"})
    bench["workloads"].append({"name": "tiny-wide.short",
                               "config": "tiny-wide",
                               "traffic": "tiny-short", "chips": 1,
                               "why": "added by the test"})
    bench["end_to_end"][0]["workloads"].append("tiny-wide.short")
    bench["per_layer"].append({
        "name": "retired_per_s.new", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "engine step",
        "moves": "out_tokens_per_s", "workloads": ["tiny-wide.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, lines = run_cell(capsys, "--root", str(root), "--workload",
                         "tiny-wide.short", "--seed", "9", "--seconds", "1",
                         "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    assert lines[-1]["metrics"]["retired_per_s.new"]["value"] > 0
    rc, lines = run_cell(capsys, "--root", str(root), "--workload",
                         "tiny-wide.short", "--seed", "9", "--seconds", "1",
                         "--trace", "0")
    assert set(lines[-1]["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_cell_of_the_repos_benchmark_refuses_to_run_off_the_chip(capsys,
                                                                   cells):
    for cell in cells:
        rc = bench_run.main(["--workload", cell, "--seconds", "1"])
        captured = capsys.readouterr()
        assert rc != 0
        assert captured.out == ""             # no result of any kind
        assert "not 'tpu'" in captured.err


# ---------------------------------------------------------------------------
# the program against the plain reference, tiny and float32
# ---------------------------------------------------------------------------

def _tiny(**program):
    with open(os.path.join(REHEARSAL, "bench/configs/tiny-serve.json")) as f:
        config = json.load(f)
    program = {"dtype": "float32", "param_dtype": "float32", **program}
    return config, model.program_config(config, **program)


def test_paged_prefill_then_decode_match_the_reference_logits():
    """Prefill a prompt into the paged pool, decode teacher-forced
    through the cache, and hold every logits row to the reference's full
    forward pass. float32 against float32 under 'highest' (conftest): the
    only differences are summation orders, so 2e-4 on logits of order 1
    is roomy, and a wrong block, mask or rotary position is of order 1."""
    from paddle_tpu.models import generation as G
    config, cfg = _tiny()
    params = model.make_weights(cfg, 11)
    bs, W, n, k = 4, 8, 9, 7
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, n + k)
    want = np.asarray(ref.forward(ref.from_program(params),
                                  jnp.asarray(ids), config))
    pool = G.init_paged_pool(cfg, 1 + W, bs)
    table = jnp.arange(1, 1 + W, dtype=jnp.int32)[None]
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :n] = ids[:n]
    logits, pool, _ = G.paged_prefill(
        params, cfg, jnp.asarray(prompt), jnp.asarray([n], jnp.int32),
        table, pool, jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(logits)[0], want[n - 1],
                               atol=2e-4, rtol=0)
    for i in range(k):
        logits, pool, _ = G.paged_decode_step(
            params, cfg, jnp.asarray(ids[n + i:n + i + 1], jnp.int32),
            jnp.asarray([n + i], jnp.int32), table, pool,
            jnp.asarray([True]))
        np.testing.assert_allclose(np.asarray(logits)[0], want[n + i],
                                   atol=2e-4, rtol=0)


def test_train_loss_and_gradient_norm_match_the_reference():
    """The program's loss function (remat on, as the cells run it) against
    the reference's loss and global gradient norm, float32 both: 1e-5
    relative leaves room for summation order only."""
    from paddle_tpu.models import llama
    config, cfg = _tiny(remat=True)
    params = model.make_weights(cfg, 3)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 24))
    lab = np.full_like(ids, -100)
    lab[:, :-1] = ids[:, 1:]
    loss, grads = jax.value_and_grad(llama.loss_fn)(
        params, jnp.asarray(ids), jnp.asarray(lab), cfg)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g)
                              for g in jax.tree_util.tree_leaves(grads))))
    want_loss, want_norm = ref.loss_and_grad_norm(
        ref.from_program(params), jnp.asarray(ids), jnp.asarray(lab), config)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert norm == pytest.approx(float(want_norm), rel=1e-5)


def test_weights_are_a_function_of_the_seed():
    _, cfg = _tiny()
    a = model.make_weights(cfg, 2 ** 31 + 1)
    b = model.make_weights(cfg, 2 ** 31 + 1)
    c = model.make_weights(cfg, 1)
    assert np.array_equal(a["layers"]["wq"], b["layers"]["wq"])
    assert not np.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert np.all(np.asarray(a["ln_f"]) == 1)
    # unit-variance products: std of a weight is fan_in ** -0.5
    assert float(jnp.std(a["layers"]["w_down"])) == pytest.approx(
        cfg.intermediate_size ** -0.5, rel=0.05)


# Checksums of ``harness.make_weights`` at the parent commit (PR 25), taken
# before it moved to ``models/llama.py``: the tiny-serve configuration,
# sha256 of each leaf's bytes, first 16 hex digits. The same seed gives
# the same weights bit for bit, so the same seed reads the same numbers.
PARENT_WEIGHTS = {
    ("float32", 0): {
        "embed": "5544f141fbb14bf0", "layers/ln_attn": "02722f124d0f1736",
        "layers/ln_mlp": "02722f124d0f1736",
        "layers/w_down": "eec71df41bc3ab17",
        "layers/w_gate": "1c20e6da469c75b7",
        "layers/w_up": "f17f6614ff179b4a", "layers/wk": "6d670eed0461df83",
        "layers/wo": "c7491d7f44a99468", "layers/wq": "215630f0623ccbbb",
        "layers/wv": "6d82f594d0d0ddb4", "lm_head": "2beb66ec39bd6fbb",
        "ln_f": "2f20cd03c9cd392a"},
    ("float32", 4026531839): {
        "embed": "a68a4962f033ac4f", "layers/ln_attn": "02722f124d0f1736",
        "layers/ln_mlp": "02722f124d0f1736",
        "layers/w_down": "d410179daa37532b",
        "layers/w_gate": "a7a06eedef37234c",
        "layers/w_up": "5342baab730b3879", "layers/wk": "5d922b949f733a0e",
        "layers/wo": "f730b263a8e0b5f8", "layers/wq": "2a825300e434a19f",
        "layers/wv": "304a5cb89e917405", "lm_head": "20a2afbb3dbf2d26",
        "ln_f": "2f20cd03c9cd392a"},
    ("bfloat16", 0): {
        "embed": "cf4c690422c368b9", "layers/ln_attn": "1ede9ebfa1ad011b",
        "layers/ln_mlp": "1ede9ebfa1ad011b",
        "layers/w_down": "8824712553264cc6",
        "layers/w_gate": "f4a5a059dab35648",
        "layers/w_up": "29ee9043dbde3c47", "layers/wk": "31e678980d17934d",
        "layers/wo": "602af4b26af3e77d", "layers/wq": "8a74c040804ceb08",
        "layers/wv": "72510fdf294ae802", "lm_head": "800630405c65f491",
        "ln_f": "e72710531b01d91e"},
    ("bfloat16", 4026531839): {
        "embed": "7f2f8b000fa5027e", "layers/ln_attn": "1ede9ebfa1ad011b",
        "layers/ln_mlp": "1ede9ebfa1ad011b",
        "layers/w_down": "d26a645073677a9c",
        "layers/w_gate": "1014741f2b3ae56d",
        "layers/w_up": "a27898ab57f6c8d4", "layers/wk": "e44c1f3921871dce",
        "layers/wo": "93db8850e12a285d", "layers/wq": "f4561613cb43da44",
        "layers/wv": "84bf563490fe31fa", "lm_head": "d6638c7dfd2818d8",
        "ln_f": "e72710531b01d91e"},
}


@pytest.mark.parametrize("dtype,seed", sorted(PARENT_WEIGHTS))
def test_weights_through_the_models_file_are_the_parents_leaf_for_leaf(
        dtype, seed):
    import hashlib
    _, cfg = _tiny(dtype=dtype, param_dtype=dtype)
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        model.make_weights(cfg, seed))
    got = {"/".join(str(k.key) for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:16]
           for path, leaf in leaves}
    assert got == PARENT_WEIGHTS[(dtype, seed)]


def test_the_references_block_walk_gives_the_logits_its_forward_gives():
    """The oracle walks the PROGRAM's tree block by block (``embed``,
    ``block`` x ``n_blocks``, ``head``); the plain ``forward`` over the
    reference's own layout is what that walk has to equal. The walk's
    block is compiled and ``forward`` runs eagerly, so sums are ordered
    differently: 2e-5 on float32 logits of order 1 (2e-6 read here); a
    layer skipped or taken twice is of order 1."""
    config, cfg = _tiny()
    params = model.make_weights(cfg, 2 ** 31 + 5)
    ids = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, 21))
    x = ref.embed(params, ids)
    assert ref.n_blocks(params) == config["num_hidden_layers"]
    for i in range(ref.n_blocks(params)):
        x = ref.block(params, i, x, config)
    want = ref.forward(ref.from_program(params), ids, config)
    np.testing.assert_allclose(np.asarray(ref.head(params, x, config)),
                               np.asarray(want), atol=2e-5, rtol=0)
    # a block is its own layer, not its neighbour's
    assert not np.allclose(np.asarray(ref.block(params, 0, x, config)),
                           np.asarray(ref.block(params, 1, x, config)))


def test_the_toys_traced_run_reads_the_train_layer_metrics(capsys, cache_dir):
    """Another architecture's cell reports the per-layer metrics of its
    kind of run through readers that know no model."""
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload",
                         "toy.train", "--seed", "3", "--seconds", "1",
                         "--trace", "1")
    assert rc == 0 and lines[-1]["correct"] is True
    got = lines[-1]["metrics"]
    # off the chip there is no peak to be a share of, and no device plane
    assert set(got) == {"step_ms.train", "compiles_in_window.train"}
    assert got["step_ms.train"]["value"] > 0
    assert got["compiles_in_window.train"]["value"] == 0
    notes = {k: v for ln in lines[:-1] for k, v in ln.items()}
    assert notes["flops_per_token"] == 6.0 * (
        48 * 128 + 48 * 48 + 2 * 48 * 96
        + 4 * (48 * 48 + 48 * 16 + 2 * 2 * 48 * 32))


# ---------------------------------------------------------------------------
# the rest of a run with the timed path broken underneath: ``correct``
# has to come out false
# ---------------------------------------------------------------------------

def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, cache_dir, monkeypatch):
    """Every token the compiled sampler hands out is moved one id up: the
    engine serves on, healthy, and only the oracle can see it."""
    from paddle_tpu.models import generation as G
    sound = G.sample_tokens

    def altered(logits, *args):
        return (sound(logits, *args) + 1) % logits.shape[-1]

    monkeypatch.setattr(G, "sample_tokens", altered)
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload",
                         "tiny.sat", "--seed", "21", "--seconds", "1",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is False and last["failed"] == 0
    gap, faults = last["compared"]
    assert gap["name"] == "oracle_worst_gap" and gap["value"] > 0.1
    assert faults == {"name": "health_faults", "value": 0.0, "limit": 0.0}


def test_half_of_the_batch_left_out_is_not_correct(capsys, cache_dir,
                                                   monkeypatch):
    """The program's loss over the first half of the rows alone, the mean
    taken over those: finite, plausible, and not the reference's."""
    load = harness.load_model

    def broken(config, roots):
        module = load(config, roots)
        whole = module.loss_fn
        module.loss_fn = lambda p, ids, lab, cfg: whole(
            p, ids[:ids.shape[0] // 2], lab[:ids.shape[0] // 2], cfg)
        return module

    monkeypatch.setattr(harness, "load_model", broken)
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload",
                         "toy.train", "--seed", "22", "--seconds", "1",
                         "--trace", "0")
    assert rc == 0 and lines[-1]["correct"] is False
    over = [c["name"] for c in lines[-1]["compared"]
            if c["value"] > c["limit"]]
    assert "loss_abs_diff" in over
