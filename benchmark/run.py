"""Run one cell of ``BENCHMARK.json`` once and print the contract's line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Earlier lines are notes for a reader (sample counts, medians, the oracle).

This file holds no table of cells, configurations, mixes, metrics or kinds
of run: each is a file found by the name ``BENCHMARK.json`` gives it (see
``benchmark/__init__.py``). A cell of the repo's ``BENCHMARK.json`` runs
on a TPU or not at all. ``--root <dir>`` points at another tree with a
``BENCHMARK.json`` of its own that carries ``"rehearsal": true``: the tiny
CPU rehearsal under ``tests/benchmark/rehearsal`` (the repo's own file can
never carry that key: the driver refuses any key it does not know).
"""

from __future__ import annotations

import time

T_START = time.time()          # set-up is counted from here

import argparse                # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def note(**fields) -> None:
    """A line for the reader; never the last one."""
    print(json.dumps(fields, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=REPO)
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rehearsal = bool(bench.get("rehearsal"))
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    # a rehearsal tree brings its own data files and may bring readers and
    # runners; what it does not bring is the repo's
    roots = [os.path.join(root, bench["paths"][0]), HERE]
    from benchmark import traffic
    mix = traffic.load(cell["traffic"], roots)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    import jax
    from benchmark import harness
    device = harness.device_record()
    if not rehearsal and device["platform"] != "tpu":
        print(f"run.py: JAX found platform {device['platform']!r}, not 'tpu';"
              f" a cell of BENCHMARK.json is measured on the chip or not at "
              f"all", file=sys.stderr)
        return 3
    if device["count"] < int(cell["chips"]):
        print(f"run.py: cell {cell['name']!r} needs {cell['chips']} chip(s), "
              f"JAX sees {device['count']}", file=sys.stderr)
        return 3
    from paddle_tpu.jit import enable_compile_cache
    cache_dir = enable_compile_cache()
    note(cell=cell["name"], seed=args.seed, seconds=seconds, trace=args.trace,
         device=device, jax=jax.__version__, compile_cache_dir=cache_dir)

    ctx = harness.Context(
        cell=cell, config=config, mix=mix, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), t_start=T_START, roots=roots, note=note,
        scratch=os.path.join(REPO, ".bench_scratch"))
    runner = harness.load_by_name("runners", mix["kind"], roots)
    run = runner.run(ctx)
    run["platform"] = device["platform"]

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            value = harness.load_by_name("layer_metrics", m["name"],
                                         roots).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            value = run["end_to_end"].get(m["name"])
            if applies(m, cell["name"]) and value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=int(run["memory_peak_bytes"]))
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"]),
            "metrics": metrics, "device": device}
    if args.trace and run.get("trace") is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = run["trace"]["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
