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
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture
def cache_dir(tmp_path, monkeypatch, compile_cache_config_restored):
    """run.py turns the persistent compile cache on: keep it out of the
    checkout, and put jax's settings back afterwards."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    yield


def run_cell(capsys, *argv):
    rc = bench_run.main(list(argv))
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in out]


E2E = {"tiny.sat": {"out_tokens_per_s", "setup_s"},
       "tiny.steady": {"ttft_p90_ms", "tpot_p90_ms", "setup_s"},
       "tiny.train": {"train_tokens_per_s", "setup_s"},
       "tiny.train4": {"train_tokens_per_s", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(E2E))
def test_cell_runs_and_prints_the_contracts_last_line(cell, capsys,
                                                      cache_dir):
    rc, lines = run_cell(capsys, "--root", REHEARSAL, "--workload", cell,
                         "--seed", str(2 ** 31 + 7), "--seconds", "1",
                         "--trace", "0")
    assert rc == 0
    last = lines[-1]
    assert set(last) == LINE_KEYS             # exactly the contract's keys
    assert last["correct"] is True and last["failed"] == 0
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


def test_traced_run_reports_the_cells_layer_metrics(capsys, cache_dir):
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
    assert 0 < got["live_slots_mean.sat"]["value"] <= 100


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


def test_a_cell_of_the_repos_benchmark_refuses_to_run_off_the_chip(capsys):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
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
    return config, harness.llama_config(config, **program)


def test_paged_prefill_then_decode_match_the_reference_logits():
    """Prefill a prompt into the paged pool, decode teacher-forced
    through the cache, and hold every logits row to the reference's full
    forward pass. float32 against float32 under 'highest' (conftest): the
    only differences are summation orders, so 2e-4 on logits of order 1
    is roomy, and a wrong block, mask or rotary position is of order 1."""
    from paddle_tpu.models import generation as G
    config, cfg = _tiny()
    params = harness.make_weights(cfg, 11)
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
    params = harness.make_weights(cfg, 3)
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
    a = harness.make_weights(cfg, 2 ** 31 + 1)
    b = harness.make_weights(cfg, 2 ** 31 + 1)
    c = harness.make_weights(cfg, 1)
    assert np.array_equal(a["layers"]["wq"], b["layers"]["wq"])
    assert not np.array_equal(a["layers"]["wq"], c["layers"]["wq"])
    assert np.all(np.asarray(a["ln_f"]) == 1)
    # unit-variance products: std of a weight is fan_in ** -0.5
    assert float(jnp.std(a["layers"]["w_down"])) == pytest.approx(
        cfg.intermediate_size ** -0.5, rel=0.05)
