"""The benchmark's own arithmetic and its contract with the driver, held
on the CPU: names and units in the driver's alphabet, every file found by
name, traffic a pure function of the seed, percentiles and window
accounting on hand-made events, operations counted from the published
widths, the trace reduction on a recorded excerpt of a chip trace."""

import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops, metrics, trace, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


class TestBenchmarkJson:
    def test_top_level_keys_are_exactly_the_contracts(self, bench):
        assert sorted(bench) == sorted([
            "command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"])
        assert 1 <= bench["run_seconds"] <= 51
        assert isinstance(bench["run_seconds"], int)
        assert len(json.dumps(bench)) < 64 * 1024
        assert all(_line(w) for w in bench["command"])
        assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
                   for p in bench["paths"])

    def test_names_and_units_are_in_the_drivers_alphabet(self, bench):
        names = []
        for group, keys in (
                ("configs", {"name", "source", "file", "reduced", "why"}),
                ("workloads", {"name", "config", "traffic", "chips", "why"})):
            for e in bench[group]:
                assert set(e) == keys, e
                assert NAME.match(e["name"]), e["name"]
                assert _line(e["why"]), e["why"]
            names.append([e["name"] for e in bench[group]])
        for c in bench["configs"]:
            assert _line(c["source"]) and c["source"].startswith("https://")
            assert all(NAME.match(k) for k in c["reduced"])
            assert len(c["reduced"]) <= 16
        for w in bench["workloads"]:
            assert NAME.match(w["config"]) and NAME.match(w["traffic"])
            assert w["chips"] in (1, 4)
        metric_names = []
        for group, keys in (
                ("end_to_end", {"name", "unit", "better", "bound", "source"}),
                ("per_layer", {"name", "unit", "better", "source", "layer",
                               "moves"})):
            for m in bench[group]:
                assert set(m) - {"workloads"} == keys, m
                assert NAME.match(m["name"]), m["name"]
                assert UNIT.match(m["unit"]), m["unit"]
                assert m["better"] in ("lower", "higher")
                metric_names.append(m["name"])
        names.append(metric_names)
        for group in names:
            assert len(group) == len(set(group)), group

    def test_sources_bounds_and_moves(self, bench):
        cells = [w["name"] for w in bench["workloads"]]
        e2e = {m["name"]: m for m in bench["end_to_end"]}
        assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
        for m in e2e.values():
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
            assert set(m.get("workloads", cells)) <= set(cells)

        def reported(metric, cell):
            return cell in metric.get("workloads", cells)

        for m in bench["per_layer"]:
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert _line(m["layer"])
            assert m["moves"] in e2e and m["moves"] != "setup_s"
            for cell in m.get("workloads", cells):
                assert cell in cells
                assert reported(e2e[m["moves"]], cell), (m["name"], cell)
        for cell in cells:
            assert any(reported(m, cell) for m in e2e.values()
                       if m["name"] != "setup_s"), cell
            assert any(reported(m, cell) for m in bench["per_layer"]), cell

    def test_every_entry_is_a_file_found_by_name(self, bench):
        root = os.path.join(REPO, bench["paths"][0])
        used = set()
        for c in bench["configs"]:
            assert c["file"].startswith(bench["paths"][0] + "/")
            with open(os.path.join(REPO, c["file"])) as f:
                cfg = json.load(f)
            assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
            assert cfg["reduced"] == c["reduced"]
            assert "assumed" in cfg and "deployment" in cfg
            assert os.path.exists(os.path.join(
                root, "reference", cfg["reference"] + ".py"))
        for w in bench["workloads"]:
            mix = traffic.load(w["traffic"])
            assert os.path.exists(os.path.join(root, "runners",
                                               mix["kind"] + ".py"))
            used.add(w["config"])
        assert used == {c["name"] for c in bench["configs"]}
        assert len({c["file"] for c in bench["configs"]}) == len(
            bench["configs"])
        for m in bench["per_layer"]:
            path = os.path.join(root, "layer_metrics", m["name"] + ".py")
            src = open(path).read()
            assert f'LAYER = "{m["layer"]}"' in src, m["name"]
            assert f'MOVES = "{m["moves"]}"' in src, m["name"]
            assert f'UNIT = "{m["unit"]}"' in src, m["name"]
        four = [w for w in bench["workloads"] if w["chips"] == 4]
        assert len(four) <= max(1, len(bench["workloads"]) // 4)

    def test_no_published_width_is_changed(self, bench):
        published = {"hidden_size": 4096, "intermediate_size": 14336,
                     "num_attention_heads": 32, "num_key_value_heads": 8,
                     "vocab_size": 32768, "rope_theta": 1e6,
                     "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
                     "sliding_window": None, "tie_word_embeddings": False}
        for c in bench["configs"]:
            with open(os.path.join(REPO, c["file"])) as f:
                cfg = json.load(f)
            for key, value in published.items():
                assert cfg[key] == value, (c["name"], key)
            assert cfg["num_hidden_layers"] < 32
            assert c["reduced"] == ["num_hidden_layers"]


class TestTraffic:
    MIX = {"loop": "open", "rate_per_s": 2.0, "shape_seed": 7, "pool": 64,
           "order_block": 8,
           "prompt": {"median": 512, "sigma": 0.9, "min": 32, "max": 3072},
           "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 768},
           "max_total": 4096, "sampled_every": 2,
           "sampling": {"temperature": 0.8, "top_k": 20}}

    def test_same_seed_same_requests_and_due_times(self):
        big = 2 ** 31 + 12345          # more than 32 signed bits hold
        a = traffic.make_requests(self.MIX, 32768, big, 50)
        b = traffic.make_requests(self.MIX, 32768, big, 50)
        for x, y in zip(a, b):
            assert np.array_equal(x["prompt"], y["prompt"])
            assert x["kw"] == y["kw"] and x["due_s"] == y["due_s"]

    def test_seeds_permute_one_multiset_of_sizes_and_gaps(self):
        a = traffic.make_requests(self.MIX, 32768, 1, 64)
        b = traffic.make_requests(self.MIX, 32768, 2, 64)
        size = lambda r: (len(r["prompt"]), r["kw"]["max_new_tokens"],  # noqa: E731
                          r["sampled"])
        assert sorted(map(size, a)) == sorted(map(size, b))
        assert [size(r) for r in a] != [size(r) for r in b]
        # ... and only inside blocks of order_block: every block of eight
        # is the same work for every seed
        for k in range(0, 64, 8):
            assert sorted(map(size, a[k:k + 8])) == \
                sorted(map(size, b[k:k + 8]))
        gaps = lambda rs: sorted(np.round(np.diff(  # noqa: E731
            [0.0] + [r["due_s"] for r in rs]), 9))
        assert gaps(a) == gaps(b)
        assert not np.array_equal(a[0]["prompt"][:8], b[0]["prompt"][:8])

    def test_sizes_respect_the_clip_and_the_cap(self):
        for s in traffic.size_pool(self.MIX):
            assert 32 <= s["prompt_len"] <= 3072
            assert 16 <= s["output_len"] <= 768
            assert s["prompt_len"] + s["output_len"] <= 4096
        pool = traffic.size_pool(self.MIX)
        assert sum(s["sampled"] for s in pool) == len(pool) // 2

    def test_train_batches_are_new_every_step(self):
        a = traffic.train_batch(256, 2, 16, 5, 0)
        assert np.array_equal(a, traffic.train_batch(256, 2, 16, 5, 0))
        assert not np.array_equal(a, traffic.train_batch(256, 2, 16, 5, 1))
        assert a.shape == (2, 16) and a.dtype == np.int32


class TestMetrics:
    def test_percentile_is_linear_between_ranks(self):
        xs = [4.0, 1.0, 3.0, 2.0, 5.0]
        assert metrics.percentile(xs, 50) == 3.0
        assert metrics.percentile(xs, 90) == pytest.approx(4.6)
        assert metrics.percentile(xs, 90) == pytest.approx(
            float(np.percentile(xs, 90)))
        with pytest.raises(ValueError):
            metrics.percentile([], 50)

    def test_a_failed_request_counts_as_missing(self):
        xs = list(range(1, 10))                     # 9 measured
        # one failure: the tail is read over 10 requests, the failed last
        assert metrics.percentile_with_missing(xs, 1, 50) == pytest.approx(
            float(np.percentile(xs + [1e9], 50)))
        assert metrics.percentile_with_missing(xs, 1, 80) == pytest.approx(
            float(np.percentile(xs + [1e9], 80)))
        # the 90th of 10 falls between the 9th and the missing one: no
        # number, rather than a flattering one
        assert metrics.percentile_with_missing(xs, 1, 90) is None
        assert metrics.percentile_with_missing(xs, 0, 90) == \
            metrics.percentile(xs, 90)

    def test_window_accounting_on_hand_made_events(self):
        recs = [
            {"due": 1.0, "first": 1.5, "last": 2.5, "n_out": 11, "ok": True},
            {"due": 2.0, "first": 2.2, "last": 2.2, "n_out": 1, "ok": True},
            {"due": 3.0, "first": None, "last": None, "n_out": 0,
             "ok": False},                          # refused
            {"due": 9.0, "first": 9.1, "last": 9.9, "n_out": 5, "ok": True},
        ]
        inside = metrics.due_in_window(recs, 1.0, 5.0)
        assert len(inside) == 3
        assert metrics.count_failed(inside) == 1
        assert metrics.ttft_s(inside) == pytest.approx([0.5, 0.2])
        # one token has no gap between tokens
        assert metrics.tpot_s(inside) == pytest.approx([0.1])
        assert metrics.tokens_in_window([0.5, 1.0, 1.5, 4.99, 5.0], 1.0,
                                        5.0) == 3

    def test_tapered_rate_by_hand(self):
        # one token a second at 0.5, 1.5, ... 9.5 in a 10 s window
        times = [i + 0.5 for i in range(10)]
        assert metrics.tapered_rate(times, 0.0, 10.0, 0.0) == 1.0
        # edges of 2 s: weights .25 .75 1 1 1 1 1 1 .75 .25 over area 8
        assert metrics.tapered_rate(times, 0.0, 10.0, 2.0) == \
            pytest.approx(8.0 / 8.0)
        # a burst of 100 tokens 0.1 s inside a hard edge counts in full,
        # 0.1 s outside it not at all; with a soft edge it hardly counts
        # either way
        burst_in, burst_out = [9.9] * 100, [10.1] * 100
        hard = [metrics.tapered_rate(times + b, 0.0, 10.0, 0.0)
                for b in (burst_in, burst_out)]
        soft = [metrics.tapered_rate(times + b, 0.0, 10.0, 2.0)
                for b in (burst_in, burst_out)]
        assert hard == [11.0, 1.0]
        assert soft == pytest.approx([1.0 + 100 * 0.05 / 8, 1.0])
        # an edge longer than half the window is clamped to a triangle
        assert metrics.tapered_rate(times, 0.0, 10.0, 99.0) == \
            metrics.tapered_rate(times, 0.0, 10.0, 5.0)

    def test_whole_step_rate_ends_on_a_step_boundary(self):
        finish = [10.0 + 0.4 * i for i in range(30)]
        r = metrics.whole_step_rate(finish, 10.0, 2.0, 8192)
        assert r["steps"] == 5 and r["window_s"] == pytest.approx(2.0)
        assert r["tokens_per_s"] == pytest.approx(5 * 8192 / 2.0)
        r = metrics.whole_step_rate(finish, 10.0, 2.1, 8192)
        assert r["steps"] == 6 and r["window_s"] == pytest.approx(2.4)
        assert r["tokens_per_s"] == pytest.approx(8192 / 0.4)
        with pytest.raises(ValueError):
            metrics.whole_step_rate(finish, 10.0, 60.0, 8192)


class TestFlops:
    M = {"hidden_size": 4096, "num_attention_heads": 32,
         "num_key_value_heads": 8, "intermediate_size": 14336,
         "vocab_size": 32768, "num_hidden_layers": 2}

    def test_parameters_by_hand(self):
        # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336,
        # two norm vectors
        by_hand = (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
                   + 2 * 4096)
        assert flops.layer_params(self.M) == by_hand == 218_112_000
        assert flops.total_params(self.M) == 704_663_552
        assert flops.total_params({**self.M, "num_hidden_layers": 4}) == \
            1_140_887_552
        assert flops.total_params({**self.M, "num_hidden_layers": 16}) == \
            3_758_231_552
        # the gather is not a matmul: the head counts, the table does not
        assert flops.matmul_params(self.M) == \
            2 * (218_112_000 - 8192) + 4096 * 32768

    def test_train_flops_per_token_by_hand(self):
        # 6 x 570.4M matmul parameters + 6 * L * S * hidden of attention
        assert flops.train_flops_per_token(self.M, 4096) == pytest.approx(
            6 * 570_425_344 + 6 * 2 * 4096 * 4096)
        assert flops.train_flops_per_token(self.M, 4096) / 1e9 == \
            pytest.approx(3.62, abs=0.01)
        assert flops.train_flops_per_token(
            {**self.M, "num_hidden_layers": 4}, 4096) / 1e9 == \
            pytest.approx(6.44, abs=0.01)

    def test_cache_bytes_and_peaks(self):
        assert flops.kv_bytes_per_token(
            {**self.M, "num_hidden_layers": 16}) == 64 * 1024
        assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
        assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
        with pytest.raises(KeyError, match="no published peaks"):
            flops.peaks("TPU v9 imaginary")


class TestTraceArithmetic:
    HAND = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["while.1", 100, 800], ["fusion.1", 100, 300],
            ["all-reduce.2", 400, 200], ["fusion.3", 650, 200],
            ["copy.4", 1000, 100]]},
            {"name": "Steps", "events": [["step", 0, 1200]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench:wait_step", 0, 950], ["PjitFunction(step)", 900, 300]]}]},
    ]}

    def test_busy_idle_and_self_time(self):
        r = trace.reduce(self.HAND, 1)
        # the while encloses its body: only the body is busy
        assert r["busy_s"] * 1e9 == pytest.approx(300 + 200 + 200 + 100)
        assert r["window_s"] * 1e9 == pytest.approx(1200)
        ops = dict(r["breakdown"]["device_ops"])
        assert "while.1" not in ops
        assert ops["fusion.1"] * 1e9 == pytest.approx(300)
        gaps = r["breakdown"]["idle_gaps"]
        assert [round(g[1] * 1e9) for g in gaps] == [150, 100, 100, 50]
        assert gaps[0][0] == "bench:wait_step"        # 850-1000
        assert gaps[-1][0] == "bench:wait_step"

    def test_exposed_collective_time(self):
        r = trace.reduce(self.HAND, 1)
        assert r["collective_s"] * 1e9 == pytest.approx(200)
        assert r["collective_exposed_s"] * 1e9 == pytest.approx(200)
        hidden = json.loads(json.dumps(self.HAND))
        hidden["planes"][0]["lines"][0]["events"].append(
            ["fusion.9", 450, 100])    # runs beside the all-reduce
        r = trace.reduce(hidden, 1)
        assert r["collective_exposed_s"] * 1e9 == pytest.approx(100)

    def test_no_device_plane_reads_nothing(self):
        host_only = {"planes": self.HAND["planes"][1:]}
        assert trace.reduce(host_only, 1) is None

    def test_recorded_excerpt_of_a_chip_trace(self):
        """150 ms from the middle of the first traced run of
        mistral7b-serve-chat-sat on a v5e (PR 23): inside one mixed step,
        so the device never rests and the paged kernel leads."""
        events = trace.load_excerpt(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "fixtures",
            "trace_excerpt.json.gz"))
        lines = {ln["name"] for p in trace.device_planes(events)
                 for ln in p["lines"]}
        assert trace.OPS_LINE in lines
        r = trace.reduce(events, 1)
        assert r["window_s"] == pytest.approx(0.140218007, rel=1e-6)
        assert r["busy_s"] == pytest.approx(0.14021753, rel=1e-6)
        assert r["busy_s"] / r["window_s"] > 0.9999
        assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
        name, seconds = r["breakdown"]["device_ops"][0]
        assert name == ("closed_call.10 custom-call:tpu_custom_call "
                        "bf16[32,8,512,128]")
        assert seconds == pytest.approx(0.082216395, rel=1e-6)
        assert r["breakdown"]["device_ops"][4][0] == \
            "fusion.190 fusion f32[32,128]"        # a tuple-shaped output
        assert all(len(n) <= 96 for n, _ in r["breakdown"]["device_ops"])
        assert not any(trace.is_control_flow(k) for k in r["op_self_s"])

    def test_interval_helpers(self):
        assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
        assert trace.subtract([(0, 10)], [(1, 4), (5, 7)]) == \
            [(0, 1), (4, 5), (7, 10)]
        assert trace.total([(1, 4), (5, 7)]) == 5
