"""For the tests under ``tests/benchmark/``: the repo's ``BENCHMARK.json``
as the ONE fixture that opens it, and what a run of the CPU rehearsal
handed its readers."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def bench():
    """The repo's ``BENCHMARK.json``, parsed anew for each test. No other
    code under ``tests/benchmark/`` opens that file, and a ``test_`` does
    nothing with this dictionary but hand it to a ``check_*(bench)``
    function: what a file asserts of the metrics' entries is then a
    function the rehearsal of the next ``model_config`` PR finds and
    calls on the dictionary that PR will leave
    (``test_benchmark_contract.py``: ``structural_checks``, and
    ``test_no_test_goes_round_the_rehearsal``, which holds both)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def cells(bench):
    """The names of the repo's cells, for a test that runs each."""
    return [w["name"] for w in bench["workloads"]]


@pytest.fixture
def runs_seen(monkeypatch):
    """The dictionaries the runners of this test's ``run.py`` calls handed
    the layer-metric readers, in order. A 1.5 s window on a CPU that five
    other workers load says nothing by its times; its COUNTS (iterations,
    dispatches, slots) hold whatever the clock did, and they are in here,
    not in the printed line."""
    from benchmark import harness
    seen = []
    load = harness.load_by_name

    def tapped(kind, name, roots):
        mod = load(kind, name, roots)          # a module of its own a call
        if kind == "runners":
            run = mod.run

            def kept(ctx):
                seen.append(run(ctx))
                return seen[-1]

            mod.run = kept
        return mod

    monkeypatch.setattr(harness, "load_by_name", tapped)
    return seen
