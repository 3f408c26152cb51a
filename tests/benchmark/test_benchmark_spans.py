"""The layer metrics that read the program's own spans and counters
(ISSUE 24): each reader on a hand-made ``run``, a program that has no
``stats()["spans"]`` (PR 23's program) reading as nothing, and the CPU
rehearsal's traced ``tiny.sat`` and ``tiny.steady`` runs printing the five
that need no device plane. (PR 24 might not edit the accepted rehearsal
and brought these entries in a tree of its own, ``rehearsal_spans/``;
PR 26 folded them into ``rehearsal/BENCHMARK.json``.)"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, run as bench_run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")
ROOTS = [os.path.join(REPO, "benchmark")]
EDGES = [1e-5 * 10.0 ** (i / 8.0) for i in range(65)]


def reader(name):
    return harness.load_by_name("layer_metrics", name, ROOTS).read


def span(seconds, count, **kinds):
    row = {"count": count, "seconds": seconds}
    if kinds:
        row["kinds"] = {k: {"count": c, "seconds": s}
                        for k, (c, s) in kinds.items()}
    return row


def hist(samples):
    cum = [sum(1 for v in samples if v <= e) for e in EDGES] + [len(samples)]
    return {"le": EDGES, "cumulative": cum, "count": len(samples),
            "sum": sum(samples)}


def stats(chunks, spans, counters=None, histograms=None):
    return {"chunks": chunks,
            "spans": {"spans": spans, "counters": counters or {},
                      "histograms": histograms or {}}}


BEFORE = stats(
    100,
    {"serve:cmds": span(1.0, 10), "serve:route": span(0.5, 200),
     "serve:deliver": span(2.0, 100), "serve:supervise": span(0.25, 100),
     "serve:plan": span(1.0, 200), "serve:operands": span(3.0, 100),
     "serve:commit": span(2.0, 100), "serve:journal": span(0.5, 100),
     "serve:dispatch": span(5.0, 100, decode=(40, 1.0), mixed=(60, 4.0)),
     "serve:fetch": span(50.0, 100, decode=(40, 9.0), mixed=(60, 41.0))},
    {"decode_iterations": 300, "mixed_lanes_real": 1000,
     "mixed_lanes_total": 40000},
    {"queue_wait_s": hist([0.001] * 30)})
AFTER = stats(
    150,
    {"serve:cmds": span(1.1, 12), "serve:route": span(0.6, 300),
     "serve:deliver": span(2.2, 150), "serve:supervise": span(0.35, 150),
     "serve:plan": span(1.05, 300), "serve:operands": span(3.15, 150),
     "serve:commit": span(2.2, 150), "serve:journal": span(0.6, 150),
     "serve:dispatch": span(5.5, 150, decode=(60, 1.1), mixed=(90, 4.4)),
     "serve:fetch": span(65.0, 150, decode=(60, 12.9), mixed=(90, 52.1))},
    {"decode_iterations": 460, "mixed_lanes_real": 3000,
     "mixed_lanes_total": 80000},
    {"queue_wait_s": hist([0.001] * 30 + [0.010] * 80 + [0.5] * 20)})
RUN = {"stats_before": BEFORE, "stats_after": AFTER}


@pytest.mark.parametrize("name,want", [
    # (0.1 + 0.1 + 0.2 + 0.1) s over 50 dispatches
    ("frontline_host_ms.sat", 10.0),
    # (0.05 + 0.15 + 0.2 + 0.1) s over 50 dispatches
    ("step_host_ms.sat", 10.0),
    # decode (0.1 + 3.9) s over 160 iterations
    ("decode_iter_wall_ms.sat", 25.0),
    # 2000 real lanes of 40000
    ("mixed_real_lane_pct.sat", 5.0),
])
def test_span_and_counter_readers_by_hand(name, want):
    assert reader(name)(RUN) == pytest.approx(want)


def test_queue_wait_percentile_is_the_windows_not_the_lifetimes():
    # the window holds 80 waits of 10 ms and 20 of 0.5 s; the 30 early
    # 1 ms waits are subtracted out. The 90th is the 90th of 100: the
    # bucket holding 0.5 s (33% wide), read linearly inside it
    got = reader("queue_wait_p90_ms.steady")(RUN)
    assert 500 / 1.34 <= got <= 500.0
    half = dict(RUN, stats_after=stats(150, {}, {}, {
        "queue_wait_s": hist([0.001] * 30 + [0.010] * 80)}))
    assert 10 / 1.34 <= reader("queue_wait_p90_ms.steady")(half) <= 10.0


def test_paged_attention_share_is_found_by_kernel_name():
    op = ('%{} = bf16[32,8,{},128]{{3,2,1,0}} custom-call(...), '
          'custom_call_target="tpu_custom_call"')
    trace = {"busy_s": 4.0, "window_s": 4.1, "op_self_s": {
        op.format("paged_attention_mq.1", 512): 1.9,
        op.format("paged_attention_q1.1", 4): 0.5,
        "%fusion.191 = bf16[32,128,14336]{2,1,0} fusion(...)": 0.9,
        op.format("flash_attention_fwd.1", 4): 0.3}}
    assert reader("paged_attn_device_pct.sat")({"trace": trace}) == \
        pytest.approx(60.0)
    # the parent's trace calls the kernel closed_call.10: nothing to read
    parent = dict(trace, op_self_s={op.format("closed_call.10", 512): 1.9})
    assert reader("paged_attn_device_pct.sat")({"trace": parent}) is None
    assert reader("paged_attn_device_pct.sat")({"trace": None}) is None


@pytest.mark.parametrize("name", [
    "frontline_host_ms.sat", "step_host_ms.sat", "decode_iter_wall_ms.sat",
    "mixed_real_lane_pct.sat", "queue_wait_p90_ms.steady"])
def test_a_program_without_spans_reads_as_nothing(name):
    """The driver runs these readers over the parent commit too, whose
    ``stats()`` has no ``"spans"`` key: the reader returns nothing and
    does not raise, and the line leaves the metric out."""
    parent = {"stats_before": {"chunks": 100}, "stats_after": {"chunks": 150}}
    assert reader(name)(parent) is None
    assert reader(name)({}) is None
    idle = {"stats_before": AFTER, "stats_after": AFTER}   # nothing happened
    assert reader(name)(idle) is None


def in_place_after(names, anchor, block):
    """``block`` stands in ``names`` as it was appended: in this order,
    right after ``anchor``. What a later PR appends comes after all of
    ``names`` and is no business of this check; an entry put in between
    is, since the driver reads it as a change to the entry whose place it
    takes."""
    at = names.index(anchor) + 1
    return names[at:at + len(block)] == list(block)


SPAN_READERS = {"frontline_host_ms.sat": ("front line", "program_span"),
                "step_host_ms.sat": ("engine step", "program_span"),
                "decode_iter_wall_ms.sat": ("engine step", "program_span"),
                "mixed_real_lane_pct.sat": ("paged programs",
                                            "program_counter"),
                "paged_attn_device_pct.sat": ("kernels", "device_trace")}


def check_the_span_readers_entries(bench):
    """PR 24's five readers in a ``BENCHMARK.json`` of the repo's kind:
    the file itself, or a dictionary a test made of it."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (layer, source) in SPAN_READERS.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            layer, source, "out_tokens_per_s")
        # the cell they were written for; a later cell appends its name
        assert "mistral7b-serve-chat-sat" in m["workloads"]
    # they were appended: what the benchmark had keeps its place, and so
    # do they. What follows them is free
    assert in_place_after([m["name"] for m in bench["per_layer"]],
                          "compiles_in_window.train", SPAN_READERS)


def test_every_new_reader_is_an_entry_with_its_layer_and_unit(bench):
    check_the_span_readers_entries(bench)


REHEARSAL_SIX = [*((name, "tiny.sat") for name in SPAN_READERS),
                 ("queue_wait_p90_ms.steady", "tiny.steady")]


def check_the_rehearsals_span_readers_entries(rehearsal):
    assert [w["name"] for w in rehearsal["workloads"]][:2] == [
        "tiny.sat", "tiny.steady"]
    by_name = {m["name"]: m for m in rehearsal["per_layer"]}
    for name, cell in REHEARSAL_SIX:
        assert cell in by_name[name]["workloads"]
        reader(name)                # each has its file
    assert set(by_name["compiles_in_window.train"]["workloads"]) >= {
        "tiny.train", "tiny.train4", "toy.train"}
    assert in_place_after([m["name"] for m in rehearsal["per_layer"]],
                          "compiles_in_window.train",
                          [name for name, _ in REHEARSAL_SIX])


def test_the_rehearsal_carries_the_six_span_readers_entries():
    """The rehearsal's serving cells report the span and counter readers
    too, appended after what PR 23's rehearsal had, each with its file."""
    with open(os.path.join(REHEARSAL, "BENCHMARK.json")) as f:
        check_the_rehearsals_span_readers_entries(json.load(f))


def _traced_rehearsal(cell, capsys):
    rc = bench_run.main(["--root", REHEARSAL, "--workload", cell,
                         "--seed", str(2 ** 31 + 11), "--seconds", "1.5",
                         "--trace", "1"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True
    return line["metrics"]


def window_delta(run, *path):
    """A counter of ``stats()`` over the window: after minus before."""
    def at(stats):
        for key in path:
            stats = stats.get(key, {})
        return stats or 0
    return at(run["stats_after"]) - at(run["stats_before"])


def test_rehearsal_traced_run_prints_the_span_metrics(
        capsys, tmp_path, monkeypatch, compile_cache_config_restored,
        runs_seen):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    got = _traced_rehearsal("tiny.sat", capsys)
    # no device plane on a CPU: the trace-backed reader finds nothing
    assert "paged_attn_device_pct.sat" not in got
    assert got["frontline_host_ms.sat"]["value"] > 0
    assert got["step_host_ms.sat"]["value"] > 0
    # a dispatch runs decode_chunk iterations or fewer. By COUNT: what an
    # iteration costs against a dispatch's median is for the chip to say,
    # not for 1.5 s of a CPU that other workers load
    (run,) = runs_seen
    with open(os.path.join(REHEARSAL, "bench/configs/tiny-serve.json")) as f:
        chunk = json.load(f)["engine"]["decode_chunk"]
    dispatches = window_delta(run, "spans", "spans", "serve:dispatch",
                              "kinds", "decode", "count")
    assert 0 < window_delta(run, "spans", "counters", "decode_iterations") \
        <= dispatches * chunk
    assert got["decode_iter_wall_ms.sat"]["value"] > 0
    assert 0 < got["mixed_real_lane_pct.sat"]["value"] <= 100
    assert got["compiles_in_window.sat"]["value"] == 0


def test_rehearsal_steady_run_prints_the_queue_wait(
        capsys, tmp_path, monkeypatch, compile_cache_config_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    got = _traced_rehearsal("tiny.steady", capsys)
    assert got["queue_wait_p90_ms.steady"]["value"] > 0
