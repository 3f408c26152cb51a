"""Regenerate every narrated count from the live registry (r4 VERDICT weak
#2 / next #9: the builder's README counts drifted from the registry — so
the counts are now GENERATED, and tests/test_docs_fresh.py fails CI-style
when they drift). Speeds are not narrated here: they are in PERF.md and
PERF_LEDGER.jsonl, measured by benchmark/run.py.

    python -m paddle_tpu.tools.refresh_docs          # rewrite docs
    python -m paddle_tpu.tools.refresh_docs --check  # exit 1 on drift
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def measured_counts() -> dict:
    """Ground truth from the live registry/namespaces."""
    import paddle_tpu  # noqa: F401
    from paddle_tpu.ops.gen_docs import generate  # imports every domain
    # reuse gen_docs' import set without writing the file
    import paddle_tpu.ops, paddle_tpu.nn.functional  # noqa: E401,F401
    import paddle_tpu.sparse, paddle_tpu.signal  # noqa: E401,F401
    import paddle_tpu.geometric, paddle_tpu.vision.ops  # noqa: E401,F401
    import paddle_tpu.fft, paddle_tpu.audio  # noqa: E401,F401
    import paddle_tpu.incubate.nn.functional  # noqa: F401
    import paddle_tpu.distributed.moe_utils  # noqa: F401
    import paddle_tpu.distributed.ps  # noqa: F401
    import paddle_tpu.vision.transforms  # noqa: F401
    import paddle_tpu.text, paddle_tpu.metric  # noqa: E401,F401
    import paddle_tpu.optimizer  # noqa: F401
    from paddle_tpu.core.dispatch import OP_REGISTRY
    from paddle_tpu.ops.sweep_specs import attach_specs, sweep_coverage
    attach_specs()
    covered, total = sweep_coverage()

    import paddle_tpu.nn as nn
    from paddle_tpu.nn.layer import Layer
    layers = sorted(n for n in dir(nn)
                    if isinstance(getattr(nn, n, None), type)
                    and issubclass(getattr(nn, n), Layer)
                    and n != "Layer")
    import paddle_tpu.nn.functional as F
    fnames = [n for n in dir(F) if not n.startswith("_")
              and callable(getattr(F, n))]
    import paddle_tpu.optimizer as opt
    from paddle_tpu.optimizer.optimizer import Optimizer
    optimizers = [n for n in dir(opt)
                  if isinstance(getattr(opt, n, None), type)
                  and issubclass(getattr(opt, n), Optimizer)
                  and n != "Optimizer"]
    from paddle_tpu.optimizer import lr as lrmod
    base = getattr(lrmod, "LRScheduler")
    lrs = [n for n in dir(lrmod)
           if isinstance(getattr(lrmod, n, None), type)
           and issubclass(getattr(lrmod, n), base)
           and n != "LRScheduler"]
    from paddle_tpu.testing.chaos import INJECTORS
    from paddle_tpu.flags import get_flags
    health_flags = sorted(n for n in get_flags()
                          if n.startswith("FLAGS_health_"))
    serving_flags = sorted(n for n in get_flags()
                           if n.startswith("FLAGS_serving_"))
    return {
        "ops": total,
        "swept": covered,
        "swept_pct": 100 * covered // total,
        "layers": len(layers),
        "functional": len(fnames),
        "optimizers": len(optimizers),
        "lr_schedulers": len(lrs),
        "chaos_injectors": len(INJECTORS),
        "health_flags": len(health_flags),
        "serving_flags": len(serving_flags),
        "_health_flag_rows": health_flags,   # consumed by health_flags_table
        "_serving_flag_rows": serving_flags,  # ... serving_flags_table
    }


# every generated span sits between these markers in the docs
_GEN = re.compile(r"<!--gen:(?P<key>[a-z0-9_]+)-->(?P<body>.*?)"
                  r"<!--/gen-->", re.S)


def render(key: str, counts: dict) -> str:
    if key in ("health_flags_table", "serving_flags_table"):
        # generated flags table straight from the live registry (ONE
        # shared renderer with ops/gen_docs.py) so the docs cannot drift
        # from flags.py or from each other
        from paddle_tpu.flags import flags_table
        rows = flags_table(counts["_" + key.replace("_table", "_rows")
                                  .replace("flags", "flag")])
        return "\n" + "\n".join(rows) + "\n"
    if key in counts:
        return str(counts[key])
    if key == "sweep_line":
        return (f"{counts['swept']}/{counts['ops']} ops "
                f"({counts['swept_pct']}%) oracle-swept")
    raise KeyError(key)


def refresh(check: bool = False) -> int:
    counts = measured_counts()
    drift = []
    for rel in ("README.md", "docs/FAULT_TOLERANCE.md",
                "docs/PERFORMANCE.md", "docs/SERVING.md"):
        path = os.path.join(ROOT, rel)
        src = open(path).read()

        def sub(m):
            want = render(m.group("key"), counts)
            have = m.group("body")
            if have != want:
                drift.append(f"{rel}: {m.group('key')}: "
                             f"{have!r} -> {want!r}")
            return f"<!--gen:{m.group('key')}-->{want}<!--/gen-->"

        out = _GEN.sub(sub, src)
        if not check and out != src:
            open(path, "w").write(out)
    if check and drift:
        print("DRIFT:\n  " + "\n  ".join(drift))
        return 1
    if drift and not check:
        print("refreshed:\n  " + "\n  ".join(drift))
    else:
        print("docs match artifacts")
    return 0


def main():
    check = "--check" in sys.argv
    sys.exit(refresh(check=check))


if __name__ == "__main__":
    main()
