"""The three readers of a dispatch's idle parts: ``fetch_copy_ms.sat``
(the ``serve:copy`` span a dispatch), ``operands_upload_ms.sat`` (the
``serve:upload`` span a dispatch) and ``dispatch_max_ms.sat`` (the
window's longest dispatch, from the histogram ``dispatch_s``). Each on a
hand-made ``run``; a program whose ``stats()`` has no such rows (one
with the two rows of sums alone, or one with no ``"spans"``) reading as
nothing; and a CPU
engine's ``stats()`` before and after a few steps giving each a number,
the longest dispatch inside the bucket of the one the engine timed."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from test_benchmark_spans import (  # noqa: E402
    AFTER, BEFORE, hist, in_place_after, span, stats)

ROOTS = [os.path.join(REPO, "benchmark")]
NEW = ["fetch_copy_ms.sat", "operands_upload_ms.sat", "dispatch_max_ms.sat"]
# the cell they were written for; see PERF.md §7 for the other three
CELL = "mistral7b-serve-chat-sat"


def reader(name):
    return harness.load_by_name("layer_metrics", name, ROOTS).read


# the window: 50 dispatches, one of them a stall of 2.1 s; a stall of 3 s
# before the window is in the lifetime histogram and not in the window
EARLIER = [0.04] * 60 + [0.3] * 39 + [3.0]
WINDOW = [0.04] * 30 + [0.45] * 19 + [2.1]
RUN = {
    "stats_before": stats(
        100, {"serve:copy": span(2.0, 100, decode=(40, 0.5), mixed=(60, 1.5)),
              "serve:upload": span(1.0, 100)},
        histograms={"dispatch_s": hist(EARLIER)}),
    "stats_after": stats(
        150, {"serve:copy": span(2.5, 150, decode=(60, 0.6), mixed=(90, 1.9)),
              "serve:upload": span(1.25, 150)},
        histograms={"dispatch_s": hist(EARLIER + WINDOW)})}


@pytest.mark.parametrize("name,want", [
    # 0.5 s over 50 dispatches
    ("fetch_copy_ms.sat", 10.0),
    # 0.25 s over 50 dispatches
    ("operands_upload_ms.sat", 5.0),
])
def test_span_readers_by_hand(name, want):
    assert reader(name)(RUN) == pytest.approx(want)


def test_the_longest_dispatch_is_the_windows_within_its_bucket():
    got = reader("dispatch_max_ms.sat")(RUN)
    assert 2100.0 <= got <= 2100.0 * 1.34
    # without the stall the window's longest is a 0.45 s dispatch; the
    # 3 s one before the window is subtracted out
    quiet = dict(RUN, stats_after=dict(RUN["stats_after"], spans=dict(
        RUN["stats_after"]["spans"],
        histograms={"dispatch_s": hist(EARLIER + WINDOW[:-1] + [0.04])})))
    assert 450.0 <= reader("dispatch_max_ms.sat")(quiet) <= 450.0 * 1.34
    # a window whose first dispatch the histogram holds alone
    first = dict(RUN, stats_before=stats(100, {}))
    assert 3000.0 <= reader("dispatch_max_ms.sat")(first) <= 3000.0 * 1.34


@pytest.mark.parametrize("name", NEW)
def test_the_parents_rows_read_as_nothing(name):
    """A program that writes ``serve:operands`` and ``serve:fetch`` as
    spans of their own has none of the leaves or ``dispatch_s``; an older
    one has no ``"spans"``. The readers run over both: nothing to read, no
    raise, the metric left out of the line."""
    assert reader(name)({"stats_before": BEFORE, "stats_after": AFTER}) \
        is None
    no_spans = {"stats_before": {"chunks": 100},
                "stats_after": {"chunks": 150}}
    assert reader(name)(no_spans) is None
    assert reader(name)({}) is None
    idle = {"stats_before": RUN["stats_after"],
            "stats_after": RUN["stats_after"]}      # nothing happened
    assert reader(name)(idle) is None


def test_a_cpu_engine_gives_each_reader_a_number():
    import jax

    from paddle_tpu.inference.serving import ServingConfig, ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig(vocab_size=97, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=96)
    eng = ServingEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        ServingConfig(block_size=4, max_slots=2,
                                      max_model_len=48, decode_chunk=2,
                                      prefill_chunk=8))
    rng = np.random.default_rng(5)

    def wave():
        for n in (5, 20, 7):
            eng.submit(rng.integers(0, 97, (n,)).astype(np.int32),
                       max_new_tokens=4, eos_token_id=None)
        while eng.pending:
            eng.step()

    wave()                                    # every shape compiled
    before = eng.stats()
    held = {k: len(v) for k, v in eng._dispatch_ms.items()}
    wave()
    run = {"stats_before": before, "stats_after": eng.stats()}
    got = {name: reader(name)(run) for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    # the same two stamps feed the engine's recent window: the window's
    # dispatches are the ones added to it since, and the reader reads the
    # upper edge of the bucket that holds the longest
    longest = max(ms for k, v in eng._dispatch_ms.items()
                  for ms in list(v)[held[k]:])
    assert longest <= got["dispatch_max_ms.sat"] <= longest * 1.34


def check_the_dispatch_readers_entries(bench):
    """The three entries in a ``BENCHMARK.json`` of the repo's kind (the
    file itself, or a dictionary a test made of it): appended right after
    what was last before them, of the engine step's layer, read from the
    program's spans, moving ``out_tokens_per_s``, each listing the cell it
    was written for among its cells (its file is the contract's check)."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert in_place_after([m["name"] for m in bench["per_layer"]],
                          "preemptions.sat", NEW)
    for name in NEW:
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"], m["unit"],
                m["better"]) == ("engine step", "program_span",
                                 "out_tokens_per_s", "ms", "lower")
        assert CELL in m["workloads"]


def test_the_three_entries_are_appended(bench):
    check_the_dispatch_readers_entries(bench)
