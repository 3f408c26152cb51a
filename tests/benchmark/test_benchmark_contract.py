"""The benchmark's own arithmetic and its contract with the driver, held
on the CPU: names and units in the driver's alphabet, every file found by
name, published widths kept and cuts kept to the guide's floors, traffic a
pure function of the seed, percentiles and window accounting on hand-made
events, operations counted from the published widths, the trace reduction
on a recorded excerpt of a chip trace.

The cases about configurations run over two trees: the repo's
``BENCHMARK.json`` and the CPU rehearsal's (``rehearsal/``), which brings
a model ``benchmark/`` has no file for. They hold no model's numbers: a
configuration names its published table (``published/<name>.json``), and
the rules below are about kinds of key, not about values."""

import ast
import glob
import importlib
import inspect
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import flops, harness, metrics, trace, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def _json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the two trees, and one case a configuration
# ---------------------------------------------------------------------------

TREES = {"repo": REPO, "rehearsal": REHEARSAL}


def part_of(tree, key):
    """``configs`` or ``paths`` of a tree's ``BENCHMARK.json``: which
    configurations there are and where their files lie, read while the
    cases below are collected, when no fixture exists yet. The metrics'
    entries and the cells' are the ``bench`` fixture's alone
    (``conftest.py``; ``test_no_test_goes_round_the_rehearsal``)."""
    assert key in ("configs", "paths")
    return _json(TREES[tree], "BENCHMARK.json")[key]


def roots_of(tree):
    """Where a tree's files are looked for: its own directory first, then
    the repo's (``run.py`` does the same)."""
    own = os.path.join(TREES[tree], part_of(tree, "paths")[0])
    return [own, os.path.join(REPO, "benchmark")]


def find_file(tree, kind, name, ext):
    for root in roots_of(tree):
        path = os.path.join(root, kind, name + ext)
        if os.path.exists(path):
            return path
    raise AssertionError(f"no {kind}/{name}{ext} under {roots_of(tree)}")


CONFIGS = [pytest.param(tree, c["name"], id=f"{tree}:{c['name']}")
           for tree in TREES for c in part_of(tree, "configs")]


def load_config(tree, name):
    entry = next(c for c in part_of(tree, "configs") if c["name"] == name)
    return entry, _json(TREES[tree], entry["file"])


# The one list of what a cut may touch (model-configs guide, section 4):
# COUNTS of things held here, never a width. Every spelling the catalog's
# architectures use for a role is here, so that a configuration of one of
# them needs no edit to this file.
COUNTS = {
    "depth": ("num_hidden_layers", "num_layers", "n_layers", "n_layer"),
    "leading dense layers": ("first_k_dense_replace", "num_dense_layers",
                             "n_dense_first_layers"),
    "routed experts held": ("n_routed_experts", "num_experts",
                            "num_local_experts", "num_routed_experts",
                            "moe_num_experts", "moe_num_primary_experts"),
    "vocabulary rows": ("vocab_size",),
    "next-token-prediction modules": (
        "num_nextn_predict_layers", "num_mtp_modules", "mtp_num_layers",
        "mtp_num_hidden_layers"),
}
ROLE = {key: role for role, keys in COUNTS.items() for key in keys}


def check_cut(cfg, published):
    """The guide's section 4 on one configuration file against its
    published table. Raises ``AssertionError`` naming the rule."""
    reduced = cfg["reduced"]
    for key, value in published.items():
        if key not in reduced:
            assert key in cfg and cfg[key] == value, \
                f"{key} differs from the published value and is not reduced"
    for key in reduced:
        assert key in ROLE, \
            f"{key} is not a count: reduced may never name a width"
        assert isinstance(cfg[key], int) and not isinstance(cfg[key], bool)
        assert 0 <= cfg[key] < published[key], \
            f"{key} is in reduced and not smaller than published"
    if "published_counts" in cfg:
        # the published counts stated beside the held ones are the table's
        assert cfg["published_counts"] == {k: published[k] for k in reduced}

    def held(role):
        return next((cfg[k] for k in COUNTS[role] if k in cfg), None)

    experts = held("routed experts held")
    if experts is not None and any(ROLE[k] == "routed experts held"
                                   for k in reduced):
        assert experts >= 8, "at least 8 routed experts are held"
    if "vocab_size" in reduced:
        assert cfg["vocab_size"] * 8 >= published["vocab_size"], \
            "at least an eighth of the vocabulary is held"
    dense = held("leading dense layers") or 0
    following = held("depth") - dense
    assert following >= int(cfg.get("period", 1)), \
        "at least one whole period follows the leading dense layers"
    if dense or experts is not None:
        # a stack that is not uniform, or sparse: four of the layers that
        # follow the leading dense ones. (A uniform dense stack's period
        # is one layer, and every kind of layer is in any cut of it.)
        assert following >= 4, \
            "at least four layers follow the leading dense ones"


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# ---------------------------------------------------------------------------
# what a ``BENCHMARK.json`` of the repo's kind has to be. Each takes the
# parsed file, so the file itself and a dictionary a test made of it go
# through the same code (``test_the_next_configuration_*`` below)
# ---------------------------------------------------------------------------

def check_top_level_keys(bench):
    assert sorted(bench) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert len(json.dumps(bench)) < 64 * 1024
    assert all(_line(w) for w in bench["command"])
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])


def check_names_and_units(bench):
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


def check_sources_bounds_and_moves(bench):
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


def check_cells_and_metrics_are_files(bench, roots):
    """``roots``: where files are looked for, as ``run.py`` looks: a tree
    of the caller's own first, then the repo's ``benchmark/``."""
    def found(kind, name):
        hits = [p for p in (os.path.join(r, kind, name) for r in roots)
                if os.path.exists(p)]
        assert hits, f"no {kind}/{name} under {roots}"
        return hits[0]

    used = set()
    for w in bench["workloads"]:
        mix = traffic.load(w["traffic"], roots)
        found("runners", mix["kind"] + ".py")
        used.add(w["config"])
    assert used == {c["name"] for c in bench["configs"]}
    assert len({c["file"] for c in bench["configs"]}) == len(
        bench["configs"])
    for m in bench["per_layer"]:
        src = open(found("layer_metrics", m["name"] + ".py")).read()
        assert f'LAYER = "{m["layer"]}"' in src, m["name"]
        assert f'MOVES = "{m["moves"]}"' in src, m["name"]
        assert f'UNIT = "{m["unit"]}"' in src, m["name"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


# ``per_layer`` as it stands: every entry the driver has accepted, in its
# place. The one rule about order: APPEND, never insert. An entry that is
# there keeps its index (the driver reads an entry put in the middle as a
# change to the one whose place it takes and refuses the PR); what a later
# PR appends after these is free, and no test knows which entries are
# last. Who edits this list: a ``benchmark`` PR, and only to add, at its
# end, the names that accepted PRs have appended since (PR 35 added PR
# 33's four), or to take out a metric it retires. A ``model_config`` PR
# leaves it alone: its new entries come after these.
PER_LAYER_THAT_EXISTS = [
    "mixed_wall_p50_ms.sat", "decode_wall_p50_ms.sat", "live_slots_mean.sat",
    "mixed_dispatches_per_req.sat", "step_ms.train", "mfu_pct.train",
    "peak_hbm_gb.train", "collective_exposed_pct.train4",
    "device_idle_pct.sat", "device_idle_pct.train", "compiles_in_window.sat",
    "compiles_in_window.train", "frontline_host_ms.sat", "step_host_ms.sat",
    "decode_iter_wall_ms.sat", "mixed_real_lane_pct.sat",
    "paged_attn_device_pct.sat", "moe_ffn_device_pct.sat",
    "moe_grouped_roofline_pct.sat", "latent_attn_roofline_pct.sat",
    "moe_load_max_over_mean.sat", "mfu_pct.sat",
    "window_attention_roofline_pct.sat", "full_attention_roofline_pct.sat",
    "kv_pool_used_pct.sat", "preemptions.sat"]


def check_what_exists_keeps_its_place(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(PER_LAYER_THAT_EXISTS)] == PER_LAYER_THAT_EXISTS


def structural_checks():
    """Every structural assertion the files here make of the repo's
    ``BENCHMARK.json``, FOUND and not listed: each function of a
    ``tests/benchmark/test_benchmark_*.py`` whose name starts with
    ``check_`` and whose first parameter is named ``bench`` (the marker;
    ``benchmark/__init__.py`` states it). Such a function takes ``(bench)``
    or ``(bench, roots)``, so that whoever finds it can call it. A family's
    file that a later PR adds is among them by being there."""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_benchmark_*.py"))):
        # by the name pytest imported it under, beside this file
        mod = importlib.import_module(os.path.basename(path)[:-3])
        for name, fn in sorted(vars(mod).items()):
            if not (name.startswith("check_") and inspect.isfunction(fn)):
                continue
            params = list(inspect.signature(fn).parameters)
            if params[:1] != ["bench"] or name == "check_structure":
                continue
            assert params in (["bench"], ["bench", "roots"]), (path, name)
            if fn not in found:
                found.append(fn)
    return found


def check_structure(bench, roots):
    """Walk ``structural_checks()`` over ``bench``: the repo's
    ``BENCHMARK.json`` or a dictionary a test made of it."""
    for check in structural_checks():
        if "roots" in inspect.signature(check).parameters:
            check(bench, roots)
        else:
            check(bench)


class TestBenchmarkJson:
    def test_top_level_keys_are_exactly_the_contracts(self, bench):
        check_top_level_keys(bench)

    def test_names_and_units_are_in_the_drivers_alphabet(self, bench):
        check_names_and_units(bench)

    def test_sources_bounds_and_moves(self, bench):
        check_sources_bounds_and_moves(bench)

    def test_cells_and_metrics_are_files_found_by_name(self, bench):
        check_cells_and_metrics_are_files(
            bench, [os.path.join(REPO, "benchmark")])

    def test_what_exists_keeps_its_place(self, bench):
        check_what_exists_keeps_its_place(bench)

    def test_every_check_of_every_file(self, bench):
        check_structure(bench, [os.path.join(REPO, "benchmark")])


# ---------------------------------------------------------------------------
# the next ``model_config`` PR, done to a copy of the repo's file: in memory
# (``next_configuration``) and in a directory (``next_configuration_on_disk``)
# ---------------------------------------------------------------------------

FAMILYS_OWN = ("moe_", "latent_", "window_")   # one family's kernel readers
CELL = "next-serve-sat"
# Four, as a family brings: a kernel's share of busy time, a roofline share
# for each of two kernels, a share of the cache. Under names no reader will
# take: an entry of a name the repo's file has is a duplicate, and the
# rehearsal would fail on the very PR it stands for (PR 33's readers had to
# be renamed for that).
NEW_ENTRIES = [
    {"name": f"next_{what}.sat", "unit": "%", "better": better,
     "source": source, "layer": layer, "moves": "out_tokens_per_s",
     "workloads": [CELL]}
    for what, better, source, layer in [
        ("kernel_device_pct", "lower", "device_trace", "kernels"),
        ("chunk_kernel_roofline_pct", "higher", "device_trace", "kernels"),
        ("token_kernel_roofline_pct", "higher", "device_trace", "kernels"),
        ("state_cache_pct", "lower", "program_counter", "engine step")]]
NEW_NAMES = [e["name"] for e in NEW_ENTRIES]


def _next_pr(bench, config, traffic_mix, reads_in, readers_dir):
    """What that PR does to ``BENCHMARK.json`` (``benchmark/__init__.py``),
    to ``bench`` in place: a configuration, a cell on it, the cell's name
    appended to ``out_tokens_per_s`` and to every reader ``reads_in`` says
    it reads in; and the files of its new readers, which read nothing, in
    ``readers_dir``. Their ENTRIES the caller puts where it wants them."""
    bench["configs"].append(config)
    bench["workloads"].append({
        "name": CELL, "config": config["name"], "traffic": traffic_mix,
        "chips": 1, "why": "closed loop; the next family's kernels"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "out_tokens_per_s" or reads_in(m):
            m["workloads"].append(CELL)
    os.makedirs(readers_dir)
    for e in NEW_ENTRIES:
        with open(os.path.join(readers_dir, e["name"] + ".py"), "w") as f:
            f.write(f'LAYER = "{e["layer"]}"\nMOVES = "{e["moves"]}"\n'
                    f'UNIT = "{e["unit"]}"\n\n\ndef read(run):\n'
                    f'    return None\n')


@pytest.fixture
def next_configuration(bench, tmp_path):
    """In memory: the cell reads in EVERY ``.sat`` reader that is not one
    family's own kernel reader (so in the three other readers PR 33
    brought too: any family with full layers over a block pool does).
    Nothing is written into the repo's tree: the readers' files go to a
    tree of the test's own, which ``roots`` puts first."""
    _next_pr(
        bench,
        {"name": "next-model-d4", "source": "https://huggingface.co/org/next",
         "file": "benchmark/configs/next-model-d4.json",
         "reduced": ["num_hidden_layers"],
         "why": "linear-attention and full layers mixed, 512 routed experts"},
        "reason-sat",
        lambda m: (m["name"].endswith(".sat")
                   and not m["name"].startswith(FAMILYS_OWN)),
        str(tmp_path / "layer_metrics"))
    return bench, [str(tmp_path), os.path.join(REPO, "benchmark")]


def test_the_next_configuration_is_appended_and_every_check_passes(
        next_configuration):
    bench, roots = next_configuration
    bench["per_layer"] += [dict(e) for e in NEW_ENTRIES]
    check_structure(bench, roots)
    # ... and "every check" is every file's: the walk found them
    assert {f"{c.__module__}.{c.__name__}" for c in structural_checks()} >= {
        "test_benchmark_contract.check_top_level_keys",
        "test_benchmark_contract.check_names_and_units",
        "test_benchmark_contract.check_sources_bounds_and_moves",
        "test_benchmark_contract.check_cells_and_metrics_are_files",
        "test_benchmark_contract.check_what_exists_keeps_its_place",
        "test_benchmark_spans.check_the_span_readers_entries",
        "test_benchmark_moe.check_which_readers_list_the_cell",
        "test_benchmark_laguna.check_which_readers_list_the_cell"}
    # the cell reads what the families share and its own four, in order
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]]
    assert {"step_host_ms.sat", "paged_attn_device_pct.sat", "mfu_pct.sat",
            "full_attention_roofline_pct.sat", "kv_pool_used_pct.sat",
            "preemptions.sat"} < set(listed)
    assert [n for n in listed if n in NEW_NAMES] == NEW_NAMES
    assert not [n for n in listed if n.startswith(FAMILYS_OWN)]


@pytest.mark.parametrize("pin", ["its four are last",
                                 "its lists hold its cell alone"])
def test_with_a_pin_of_pr33_put_back_the_rehearsal_fails(
        next_configuration, monkeypatch, pin):
    """What the rehearsal is for. PR 33's family file held, of the repo's
    file, that its four readers were LAST in ``per_layer`` and that each
    listed its cell ALONE; both were true of the file as it was, the
    assertions stood in a ``test_`` that opened the file itself, and the
    rehearsal, which listed its checks by hand, never met them: ISSUE 35
    found them by doing the next PR's addition on a copy. Either one put
    back into the family's ``check_*`` fails the rehearsal, here, in the
    PR that writes it."""
    import test_benchmark_laguna as laguna
    sound = laguna.check_which_readers_list_the_cell

    def check_which_readers_list_the_cell(bench):
        sound(bench)
        names = [m["name"] for m in bench["per_layer"]]
        if pin == "its four are last":
            assert names[len(names) - 4:] == laguna.NEW, pin
        else:
            for m in bench["per_layer"]:
                if m["name"] in laguna.NEW:
                    assert len(m["workloads"]) == 1, pin

    bench, roots = next_configuration
    bench["per_layer"] += [dict(e) for e in NEW_ENTRIES]
    check_structure(bench, roots)                      # sound as it stands
    monkeypatch.setattr(laguna, "check_which_readers_list_the_cell",
                        check_which_readers_list_the_cell)
    with pytest.raises(AssertionError, match=pin):
        check_structure(bench, roots)


@pytest.mark.parametrize("at", range(len(PER_LAYER_THAT_EXISTS)))
def test_an_entry_put_in_the_middle_is_told_apart(next_configuration, at):
    """One of the new entries, inserted at a place that exists (PR 33's
    four among them), the others appended: everything else still holds (so
    nothing but its place is wrong), and the order's check says so.
    "Append, never insert" is what the tests hold of the order, and all
    they hold."""
    bench, roots = next_configuration
    bench["per_layer"].insert(at, dict(NEW_ENTRIES[0]))
    bench["per_layer"] += [dict(e) for e in NEW_ENTRIES[1:]]
    check_top_level_keys(bench)
    check_names_and_units(bench)
    check_sources_bounds_and_moves(bench)
    check_cells_and_metrics_are_files(bench, roots)
    with pytest.raises(AssertionError):
        check_what_exists_keeps_its_place(bench)


@pytest.fixture
def next_configuration_on_disk(bench, tmp_path):
    """The addition done for real, as ISSUE 35 did it by hand: in a
    directory, a copy of ``BENCHMARK.json`` with a configuration (a copy
    of the newest configuration's file under another name), a cell on it,
    the cell's name on ``out_tokens_per_s`` and on every reader the newest
    cell reads in (sixteen at PR 35), four reader files and their entries
    at the end. Returns the directory, ``roots`` as ``run.py`` would have
    them there, and the names of those readers."""
    root = tmp_path / "tree"
    newest = bench["workloads"][-1]
    config = dict(next(c for c in bench["configs"]
                       if c["name"] == newest["config"]))
    with open(os.path.join(REPO, config["file"])) as f:
        sizes = json.load(f)
    config.update(name="next-model-ep8-d9",
                  file="benchmark/configs/next-model-ep8-d9.json",
                  why="a copy of the newest configuration under another name")
    os.makedirs(root / "benchmark" / "configs")
    (root / config["file"]).write_text(
        json.dumps({**sizes, "name": config["name"]}))
    shared = [m["name"] for m in bench["per_layer"]
              if newest["name"] in m.get("workloads", ())]
    _next_pr(bench, config, newest["traffic"],
             lambda m: m["name"] in shared,
             str(root / "benchmark" / "layer_metrics"))
    bench["per_layer"] += [dict(e) for e in NEW_ENTRIES]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return (root, [str(root / "benchmark"), os.path.join(REPO, "benchmark")],
            shared)


def test_the_addition_done_in_a_directory_passes_every_check(
        next_configuration_on_disk):
    root, roots, shared = next_configuration_on_disk
    added = json.loads((root / "BENCHMARK.json").read_text())
    check_structure(added, roots)
    # what ``run.py`` would find for the cell there: its configuration's
    # file, cut as the guide allows, and a reader for each of its entries
    cell = next(w for w in added["workloads"] if w["name"] == CELL)
    entry = next(c for c in added["configs"] if c["name"] == cell["config"])
    cfg = _json(root, entry["file"])
    assert cfg["name"] == entry["name"] and cfg["reduced"] == entry["reduced"]
    check_cut(cfg, _json(REPO, "benchmark", "published",
                         cfg["published"] + ".json")["config"])
    reads = [m["name"] for m in added["per_layer"]
             if CELL in m.get("workloads", ())]
    assert reads == shared + NEW_NAMES
    for name in reads:
        assert callable(harness.load_by_name("layer_metrics", name,
                                             roots).read)
    assert all(harness.load_by_name("layer_metrics", n, roots).read({})
               is None for n in NEW_NAMES)


# ---------------------------------------------------------------------------
# no way round the rehearsal: the sources under ``tests/benchmark/``
# ---------------------------------------------------------------------------

OPENERS = {("conftest.py", "bench"),                  # the one fixture
           ("test_benchmark_contract.py", "part_of")}  # configs, paths only
FIXTURES_ON_BENCH = {("conftest.py", "cells"),
                     ("test_benchmark_contract.py", "next_configuration"),
                     ("test_benchmark_contract.py",
                      "next_configuration_on_disk")}


def _functions(tree):
    """Every function of a parsed file with the names of those round it."""
    def walk(node, outer):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield child, outer
                yield from walk(child, outer + [child.name])
            else:
                yield from walk(child, outer)
    yield from walk(tree, [])


def _outside_functions(node):
    for child in ast.iter_child_nodes(node):
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield child
            yield from _outside_functions(child)


def _mentions(node, *names):
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(node))


def _is_negative(node):
    return any(isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.USub)
               and isinstance(n.operand, ast.Constant)
               for n in ast.walk(node))


def _handed_to_checks(fn):
    """The calls in ``fn`` that hand a name to a ``check_*`` function as
    its first argument: (the function's name, the argument's node)."""
    calls = [(getattr(n.func, "id", getattr(n.func, "attr", "")), n.args[0])
             for n in ast.walk(fn) if isinstance(n, ast.Call) and n.args]
    return [(name, arg) for name, arg in calls
            if name.startswith("check_") and isinstance(arg, ast.Name)]


def ways_round(sources):
    """``sources``: file name -> text of each file under
    ``tests/benchmark/``. The ways a file could hold something of the
    repo's ``BENCHMARK.json`` that the rehearsal never meets, each as a
    line of words; none is the rule."""
    found = []
    for file, text in sorted(sources.items()):
        tree = ast.parse(text)
        # 1. the repo's file is opened in one place
        scopes = [("<module>", list(_outside_functions(tree)))] + [
            (fn.name, list(ast.walk(fn)))
            for fn, outer in _functions(tree) if not outer]
        for name, nodes in scopes:
            opens = any(
                isinstance(n, (ast.Call, ast.BinOp))
                and _mentions(n, "REPO", "TREES")
                and any(isinstance(c, ast.Constant) and isinstance(
                    c.value, str) and c.value.endswith("BENCHMARK.json")
                    and " " not in c.value for c in ast.walk(n))
                for n in nodes)
            if opens and (file, name) not in OPENERS:
                found.append(f"{file}: {name} opens the repo's "
                             f"BENCHMARK.json; the bench fixture does that")
        for fn, outer in _functions(tree):
            params = [a.arg for a in fn.args.args]
            where = f"{file}: {'.'.join(outer + [fn.name])}"
            # 2. a test_ only hands the fixture's dictionary to a check_*
            if fn.name.startswith("test_") and "bench" in params:
                handed = {id(arg) for _, arg in _handed_to_checks(fn)}
                if any(isinstance(n, ast.Name) and n.id == "bench"
                       and id(n) not in handed for n in ast.walk(fn)):
                    found.append(f"{where} does more with bench than hand "
                                 f"it to a check_* function")
            # 3. nor does a fixture of a file's own pass it on
            if "bench" in params and (file, fn.name) not in \
                    FIXTURES_ON_BENCH and any(
                        "fixture" in ast.unparse(d)
                        for d in fn.decorator_list):
                found.append(f"{where} is a fixture over bench")
            # 4. nothing counts per_layer from its end ...
            tainted = set()
            for n in ast.walk(fn):       # assignments, in source order
                if isinstance(n, ast.Assign) and (
                        "per_layer" in ast.unparse(n.value)
                        or _mentions(n.value, *tainted)):
                    tainted |= {t.id for t in n.targets
                                if isinstance(t, ast.Name)}
            for n in ast.walk(fn):
                if isinstance(n, ast.Subscript) and _is_negative(n.slice) \
                        and ("per_layer" in ast.unparse(n.value)
                             or _mentions(n.value, *tainted)):
                    found.append(f"{where} counts per_layer from its end: "
                                 f"{ast.unparse(n)}")
                # 5. ... or holds a reader's workloads equal to a list
                if isinstance(n, ast.Compare) and any(
                        isinstance(op, ast.Eq) for op in n.ops):
                    sides = [n.left, *n.comparators]
                    if any(isinstance(s, ast.Subscript) and isinstance(
                            s.slice, ast.Constant) and s.slice.value ==
                            "workloads" for s in sides) and any(
                                isinstance(s, ast.List) for s in sides):
                        found.append(f"{where} holds a workloads equal to "
                                     f"a list: {ast.unparse(n)}")
    return found


def _the_sources():
    sources = {}
    for path in glob.glob(os.path.join(HERE, "*.py")):
        with open(path) as f:
            sources[os.path.basename(path)] = f.read()
    return sources


def test_no_test_goes_round_the_rehearsal():
    """The repo's ``BENCHMARK.json`` is opened by the ``bench`` fixture
    alone (and, for the names of its configurations and its ``paths``, by
    ``part_of``); a ``test_`` does nothing with it but hand it to a
    ``check_*`` function, and every such function is one the rehearsal
    finds; no file counts ``per_layer`` from its end or holds a
    ``workloads`` equal to a list. A family file written by copying the
    last one then either brings its assertions as a ``check_*(bench)`` the
    rehearsal walks, or fails here, in the PR that adds it."""
    sources = _the_sources()
    assert len(sources) >= 6 and "conftest.py" in sources
    assert ways_round(sources) == []
    walked = {c.__name__ for c in structural_checks()} | {"check_structure"}
    for file, text in sources.items():
        for fn, _ in _functions(ast.parse(text)):
            if fn.name.startswith("test_") and "bench" in [
                    a.arg for a in fn.args.args]:
                called = {name for name, arg in _handed_to_checks(fn)
                          if arg.id == "bench"}
                assert called and called <= walked, (file, fn.name, called)


# (the slice is spelt apart so that a grep of these files for it finds none)
AS_PR33_WROTE_IT = '''
def test_which_readers_list_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"]FROM_THE_END] == NEW
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [REAL]
'''.replace("FROM_THE_END", "[" + "-4:]")


@pytest.mark.parametrize("text,said", [
    (AS_PR33_WROTE_IT, ["opens the repo's BENCHMARK.json",
                        "counts per_layer from its end",
                        "holds a workloads equal to a list"]),
    ('def test_the_tail(bench):\n'
     '    names = [m["name"] for m in bench["per_layer"]]\n'
     '    assert names[-1] == "ours.sat"\n',
     ["does more with bench than hand it", "counts per_layer from its end"]),
    ('BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))\n',
     ["<module> opens the repo's BENCHMARK.json"]),
    ('@pytest.fixture\ndef ours(bench):\n    return bench["per_layer"]\n',
     ["ours is a fixture over bench"]),
    ('def test_which_readers_list_the_cell(bench):\n'
     '    check_which_readers_list_the_cell(bench)\n', []),
], ids=["as-pr33-wrote-it", "the-fixture-and-an-index-from-the-end",
        "opened-at-import", "a-fixture-of-its-own", "a-check-handed-over"])
def test_a_family_file_that_goes_round_is_told(text, said):
    got = ways_round({"test_benchmark_copied.py": text})
    assert len(got) == len(said), got
    for words in said:
        assert any(words in line for line in got), (words, got)


class TestConfigurations:
    """One case a configuration of each tree; the toy of the rehearsal
    tree passes the same cases a real configuration will."""

    @pytest.mark.parametrize("tree,name", CONFIGS)
    def test_configuration_is_files_found_by_name(self, tree, name):
        entry, cfg = load_config(tree, name)
        assert entry["file"].startswith(part_of(tree, "paths")[0] + "/")
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert "assumed" in cfg and "deployment" in cfg
        model = harness.load_by_name("models", cfg["model"], roots_of(tree))
        for part in ("program_config", "make_weights", "vocab_size",
                     "total_params", "matmul_params",
                     "train_flops_per_token", "cache_bytes_per_token"):
            assert callable(getattr(model, part)), (cfg["model"], part)
        assert model.total_params(cfg) > model.matmul_params(cfg) > 0
        find_file(tree, "reference", cfg["reference"], ".py")
        find_file(tree, "published", cfg["published"], ".json")
        # a tolerance is written with the reason for it, a configuration
        limits = {k: v for k, v in cfg["oracle"].items() if k != "reason"}
        assert limits and all(isinstance(v, float) and v > 0
                              for v in limits.values())
        assert len(cfg["oracle"]["reason"]) > 80

    @pytest.mark.parametrize("tree,name", CONFIGS)
    def test_published_widths_are_kept_and_the_cut_keeps_the_floors(
            self, tree, name):
        _, cfg = load_config(tree, name)
        published = _json(find_file(tree, "published", cfg["published"],
                                    ".json"))
        check_cut(cfg, published["config"])
        if tree == "repo":
            entry, _ = load_config(tree, name)
            assert published["source"] == entry["source"]

    TOY = ("rehearsal", "toy-moe-train")

    @pytest.mark.parametrize("change,rule", [
        ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                      "moe_intermediate_size"], "moe_intermediate_size": 16},
         "reduced may never name a width"),
        ({"n_routed_experts": 4}, "at least 8 routed experts"),
        ({"vocab_size": 32}, "an eighth of the vocabulary"),
        ({"num_hidden_layers": 4}, "at least four layers follow"),
        ({"hidden_size": 32}, "hidden_size differs from the published"),
    ], ids=["a-width-in-reduced", "four-experts", "a-sixteenth-of-the-"
            "vocabulary", "three-expert-layers", "a-width-changed"])
    def test_a_cut_below_the_floors_is_refused(self, change, rule):
        _, cfg = load_config(*self.TOY)
        published = _json(find_file("rehearsal", "published",
                                    cfg["published"], ".json"))["config"]
        check_cut(cfg, published)                 # sound as it stands
        cfg = {**cfg, **change}
        cfg.pop("published_counts")
        with pytest.raises(AssertionError, match=rule):
            check_cut(cfg, published)

    def test_the_toy_is_another_architecture_cut_in_counts(self):
        """What the rehearsal proves is worth proving only while its toy
        is unlike the repo's configurations."""
        _, cfg = load_config(*self.TOY)
        repo_models = {load_config("repo", c["name"])[1]["model"]
                       for c in part_of("repo", "configs")}
        assert cfg["model"] not in repo_models
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "models", cfg["model"] + ".py"))
        assert cfg["hidden_size"] != 4096
        assert "n_routed_experts" in cfg["reduced"]


BENCHMARK_CODE = ["run.py", "harness.py", "rehearse.py", "flops.py",
                  "runners/serve.py", "runners/train.py"]


@pytest.mark.parametrize("path", BENCHMARK_CODE)
def test_shared_benchmark_code_names_no_model_and_no_parameter(path):
    """A model's name or a parameter's name in the code every
    configuration shares is an edit the next architecture would need."""
    src = open(os.path.join(REPO, "benchmark", path)).read()
    for word in ("llama", "Llama", "mistral", "wq", "lm_head", "ln_f",
                 "init_params"):
        assert not re.search(r"\b%s\b" % word, src), (path, word)
    if path == "flops.py":
        assert "def " not in src.replace("def peaks(", "")


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


class TestCounts:
    """The counts of the repo's one family of model, by hand, at the
    published widths its configurations name."""

    M = {"hidden_size": 4096, "num_attention_heads": 32,
         "num_key_value_heads": 8, "intermediate_size": 14336,
         "vocab_size": 32768, "num_hidden_layers": 2}

    counts = harness.load_by_name("models", "llama",
                                  [os.path.join(REPO, "benchmark")])

    def test_parameters_by_hand(self):
        flops = self.counts
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
        flops = self.counts
        # 6 x 570.4M matmul parameters + 6 * L * S * hidden of attention
        assert flops.train_flops_per_token(self.M, 4096) == pytest.approx(
            6 * 570_425_344 + 6 * 2 * 4096 * 4096)
        assert flops.train_flops_per_token(self.M, 4096) / 1e9 == \
            pytest.approx(3.62, abs=0.01)
        assert flops.train_flops_per_token(
            {**self.M, "num_hidden_layers": 4}, 4096) / 1e9 == \
            pytest.approx(6.44, abs=0.01)

    def test_cache_bytes_and_peaks(self):
        assert self.counts.cache_bytes_per_token(
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
