"""What every kind of run shares: the context a runner gets, the device
record, weights from the seed, the benchmark's own spans, the traced
window."""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional


def device_record() -> Dict[str, Any]:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    reports none, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class Context:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float                     # time.time() at process start
    roots: List[str]
    note: Callable[..., None]
    scratch: str

    def span(self, name: str):
        """One span of the benchmark's own host code, written into the
        profiler's trace as ``bench:<name>`` (only while a trace is being
        taken), so a device gap can be laid against it."""
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name)

    def setup_seconds(self, t0_perf: float) -> float:
        """Process start to the instant ``t0_perf`` (a perf_counter time)
        at which the measured window opened."""
        return (time.time() - (time.perf_counter() - t0_perf)) - self.t_start


def llama_config(config: Dict[str, Any], **program):
    """The program's config object from the configuration file's published
    keys (no width is touched here: the file is the configuration as it is
    run) plus the ``program`` settings of the cell's kind of run."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig
    heads = int(config["num_attention_heads"])
    if config.get("head_dim") and int(config["head_dim"]) * heads != int(
            config["hidden_size"]):
        raise ValueError("LlamaConfig derives head_dim as hidden/heads; "
                         "this configuration's head_dim differs")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    for key in ("dtype", "param_dtype"):
        if key in program:
            program[key] = dtypes[program[key]]
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=int(config["intermediate_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=heads,
        num_key_value_heads=int(config["num_key_value_heads"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        tie_word_embeddings=bool(config.get("tie_word_embeddings", False)),
        **program)


def make_weights(cfg, seed: int, shardings=None):
    """Seeded random weights on the device, in ONE jitted call, in the
    type they are stored in, laid out as the program lays its parameters
    out (shapes and names from ``llama.init_params``, values the
    benchmark's own: unit-variance products, norms at one)."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    shapes = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.key(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for i, (path, s) in enumerate(paths):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("ln"):
                leaves.append(jnp.ones(s.shape, s.dtype))
                continue
            fan_in = s.shape[-2] if s.ndim == 3 else cfg.hidden_size
            w = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32) * (float(fan_in) ** -0.5)
            leaves.append(w.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # fold the (possibly >2**31) seed into two 31-bit words
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make, out_shardings=shardings)(key)


class TracedWindow:
    """A profiler trace of a few seconds inside the measured window, taken
    only in a ``--trace 1`` run, written under ``scratch`` and removed once
    it is reduced."""

    def __init__(self, scratch: str, name: str):
        self.dir = os.path.join(scratch, "trace-" + name)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host python frames: too large
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, device_count: int) -> Optional[Dict[str, Any]]:
        """The trace reduced to numbers (``trace.reduce``). Where the
        environment names a file in ``BENCHMARK_KEEP_EXCERPT``, a small cut
        of the trace and a summary of its planes and lines are left there:
        that is how the test fixture was recorded, and how a builder looks
        at a trace by hand."""
        from benchmark import trace
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files or self.t0 is None:
            return None
        events = trace.read_xplane(files[0])
        keep = os.environ.get("BENCHMARK_KEEP_EXCERPT")
        if keep:
            trace.write_excerpt(events, keep)
        out = trace.reduce(events, device_count)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def load_by_name(kind: str, name: str, roots):
    """The module ``<root>/<kind>/<name>.py`` from the first root that has
    it. Names may hold dots and dashes, so it is loaded by path."""
    import importlib.util
    for root in roots:
        path = os.path.join(root, kind, name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"),
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {list(roots)}")


def load_reference(ctx: Context):
    """The plain reference the configuration names (``"reference":
    "mistral"`` -> ``reference/mistral.py``), found by name."""
    return load_by_name("reference", ctx.config["reference"], ctx.roots)
