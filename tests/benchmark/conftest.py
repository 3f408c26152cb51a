"""For the tests of the CPU rehearsal: what a run handed its readers."""

import pytest


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
