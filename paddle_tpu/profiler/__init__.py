"""``paddle.profiler`` parity over the PJRT/XPlane tracer.

Parity target: ``python/paddle/profiler/profiler.py`` in the reference
(Profiler with targets, ``make_scheduler`` step states, RecordEvent host
spans, chrome-trace export; CUPTI device tracer). TPU redesign (SURVEY §5):
the device side is the PJRT profiler — ``jax.profiler`` captures an XPlane
trace viewable in TensorBoard/Perfetto; the host side keeps the reference's
RecordEvent UX over ONE span primitive (:func:`annotate`, a
``jax.profiler.TraceAnnotation``) and ONE cumulative aggregator
(:class:`SpanStats`) that ``summary()``, ``RecordEvent`` and the serving
engine's ``stats()["spans"]`` all read.
"""

from __future__ import annotations

import bisect
import enum
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax.profiler

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "SummaryView", "annotate", "annotate_step", "SpanStats",
           "snapshot_delta", "histogram_percentile"]


def annotate(name: str, **ids):
    """The one way the program opens a profiling span: a
    ``jax.profiler.TraceAnnotation(name, **ids)``. With no profiler
    session active entering it costs about half a microsecond and records
    nothing; with one active the span lands in the same XPlane as the
    device's operations, on the same clock, so an idle gap on the device
    can be laid against it. ``ids`` become the event's arguments (the
    serving spans carry ``step=`` and ``kind=``)."""
    return jax.profiler.TraceAnnotation(name, **ids)


def annotate_step(name: str, step_num: int):
    """A ``jax.profiler.StepTraceAnnotation``: profiler tools group the
    device operations under it by ``step_num`` (the train step's span)."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


# -- the cumulative aggregator ------------------------------------------------

# histogram bucket upper edges: 8 a decade from 10 us to 1000 s (each
# bucket is 33% wide); one more bucket above catches the rest
HISTOGRAM_EDGES = tuple(1e-5 * 10.0 ** (i / 8.0) for i in range(65))


class _Span:
    """One open span of a :class:`SpanStats`: the trace annotation plus
    the two ``perf_counter`` stamps (``t0``/``t1``) its seconds come
    from, left readable so a caller can time ACROSS spans from the same
    stamps instead of taking a second pair. The stamps are taken outside
    the annotation, so the span machinery's own cost is inside the
    seconds it reports and back-to-back spans leave (almost) no time
    between them unaccounted."""

    __slots__ = ("_stats", "_name", "_kind", "_ids", "_ann", "t0", "t1")

    def __init__(self, stats, name, kind, ids):
        if kind is not None:
            ids["kind"] = kind
        self._stats, self._name, self._kind, self._ids = (
            stats, name, kind, ids)
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        self._ann = annotate(self._name, **self._ids)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.t1 = time.perf_counter()
        self._stats.add(self._name, self.t1 - self.t0, self._kind)
        return False


class SpanStats:
    """Always-on cumulative totals: per span name a count and total
    seconds (split per ``kind`` where one is given), plain monotonic
    counters, and fixed log-spaced-bucket histograms. Nothing is ever
    reset or windowed: :meth:`snapshot` returns plain dicts and two
    snapshots SUBTRACT (:func:`snapshot_delta`) to give any window's
    mean and percentiles. Safe from any thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: Dict[str, list] = {}      # name -> [count, seconds]
        self._kinds: Dict[str, Dict[str, list]] = {}
        self._counters: Dict[str, int] = {}
        self._hists: Dict[str, list] = {}      # name -> [buckets, sum]

    def span(self, name: str, kind: Optional[str] = None, **ids) -> _Span:
        """Context manager: an :func:`annotate` span whose wall seconds
        are also added here under ``name`` (and under its ``kind``)."""
        return _Span(self, name, kind, ids)

    def add(self, name: str, seconds: float,
            kind: Optional[str] = None) -> None:
        with self._lock:
            row = self._spans.get(name)
            if row is None:
                row = self._spans[name] = [0, 0.0]
            row[0] += 1
            row[1] += seconds
            if kind is not None:
                row = self._kinds.setdefault(name, {}).setdefault(
                    kind, [0, 0.0])
                row[0] += 1
                row[1] += seconds

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def observe(self, name: str, value: float) -> None:
        """One sample into the histogram ``name``."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = [
                    [0] * (len(HISTOGRAM_EDGES) + 1), 0.0]
            h[0][bisect.bisect_left(HISTOGRAM_EDGES, value)] += 1
            h[1] += value

    def snapshot(self) -> Dict[str, Any]:
        """Plain data (JSON-serializable). Histograms carry their bucket
        edges (``le``) and CUMULATIVE bucket counts, so a reader needs
        nothing from this module to subtract and read two of them."""
        with self._lock:
            spans = {}
            for name, (n, s) in self._spans.items():
                spans[name] = {"count": n, "seconds": s}
                kinds = self._kinds.get(name)
                if kinds:
                    spans[name]["kinds"] = {
                        k: {"count": kn, "seconds": ks}
                        for k, (kn, ks) in kinds.items()}
            hists = {}
            for name, (buckets, total) in self._hists.items():
                cum, acc = [], 0
                for b in buckets:
                    acc += b
                    cum.append(acc)
                hists[name] = {"le": list(HISTOGRAM_EDGES),
                               "cumulative": cum, "count": acc,
                               "sum": total}
            return {"spans": spans, "counters": dict(self._counters),
                    "histograms": hists}


def snapshot_delta(after: Dict[str, Any],
                   before: Dict[str, Any]) -> Dict[str, Any]:
    """``after - before`` for two :meth:`SpanStats.snapshot` results: what
    happened between them, in the same shape."""

    def row(a, b):
        out = {"count": a["count"] - b.get("count", 0),
               "seconds": a["seconds"] - b.get("seconds", 0.0)}
        if "kinds" in a:
            out["kinds"] = {k: row(v, b.get("kinds", {}).get(k, {}))
                            for k, v in a["kinds"].items()}
        return out

    spans = {k: row(v, before["spans"].get(k, {}))
             for k, v in after["spans"].items()}
    counters = {k: v - before["counters"].get(k, 0)
                for k, v in after["counters"].items()}
    hists = {}
    for k, a in after["histograms"].items():
        b = before["histograms"].get(k)
        cum = a["cumulative"] if b is None else [
            x - y for x, y in zip(a["cumulative"], b["cumulative"])]
        hists[k] = {"le": a["le"], "cumulative": cum, "count": cum[-1],
                    "sum": a["sum"] - (b["sum"] if b else 0.0)}
    return {"spans": spans, "counters": counters, "histograms": hists}


def histogram_percentile(hist: Dict[str, Any], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) of a snapshot histogram (or of a
    difference of two), linear inside the bucket it falls in; None when
    it holds no sample. The bucket above the last edge reads as that
    edge."""
    n = hist["count"]
    if n <= 0:
        return None
    rank = q / 100.0 * n
    le, cum = hist["le"], hist["cumulative"]
    for i, c in enumerate(cum):
        if c >= rank and c > 0:
            if i >= len(le):
                return le[-1]
            lo = le[i - 1] if i else 0.0
            below = cum[i - 1] if i else 0
            return lo + (le[i] - lo) * (rank - below) / (c - below)
    return le[-1]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView(enum.Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-state schedule (reference semantics): skip_first, then cycles of
    closed/ready/record with RECORD_AND_RETURN closing each cycle."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return schedule


def _default_schedule(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback: point the XPlane dump at ``dir_name`` (open
    with TensorBoard's profile plugin or Perfetto)."""
    def handler(prof: "Profiler"):
        prof._last_export_dir = dir_name
    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(path: str):
    raise NotImplementedError(
        "load_profiler_result: open the XPlane dump directory with "
        "TensorBoard's profile plugin (tensorboard --logdir <dir>)")


# -- host-side spans ---------------------------------------------------------

# the process-wide aggregator RecordEvent spans land in; Profiler.summary()
# prints what it gained since that profiler's start()
host_events = SpanStats()


class RecordEvent:
    """Host span (ref: paddle.profiler.RecordEvent): shows up in the XPlane
    timeline via :func:`annotate` and in Profiler.summary() aggregates."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = host_events.span(self.name)
        self._span.__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """ref: paddle.profiler.Profiler(targets, scheduler, on_trace_ready).

    ``step()`` drives the scheduler; RECORD states run under an active
    ``jax.profiler`` trace capturing device + host activity to ``trace_dir``.
    """

    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False,
                 trace_dir: str = "./profiler_log"):
        self.targets = list(targets or [ProfilerTarget.CPU])
        if scheduler is None:
            self.scheduler = _default_schedule
        elif callable(scheduler):
            self.scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self.scheduler = make_scheduler(closed=max(0, lo), ready=0,
                                            record=hi - lo, repeat=1)
        else:
            raise ValueError(f"unsupported scheduler: {scheduler!r}")
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.trace_dir = trace_dir
        self._last_export_dir = None
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._tracing = False
        self._step_t0 = None
        self._step_times = []
        self._events_at_start = host_events.snapshot()

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        self._events_at_start = host_events.snapshot()
        self.current_state = self.scheduler(self.step_num)
        self._maybe_toggle_trace()
        self._step_t0 = time.perf_counter()

    def stop(self):
        if self._tracing:
            self._stop_trace()
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        if self._step_t0 is not None:
            self._step_times.append(time.perf_counter() - self._step_t0)
        self.step_num += 1
        prev = self.current_state
        self.current_state = self.scheduler(self.step_num)
        if prev != self.current_state:
            self._maybe_toggle_trace()
            if prev == ProfilerState.RECORD_AND_RETURN and \
                    self.on_trace_ready is not None:
                self.on_trace_ready(self)
        self._step_t0 = time.perf_counter()

    def _maybe_toggle_trace(self):
        want = self.current_state in (ProfilerState.RECORD,
                                      ProfilerState.RECORD_AND_RETURN)
        if want and not self._tracing and not self.timer_only:
            self._start_trace()
        elif not want and self._tracing:
            self._stop_trace()

    def _start_trace(self):
        os.makedirs(self.trace_dir, exist_ok=True)
        try:
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
        except Exception:  # second concurrent trace etc. — keep timers alive
            self._tracing = False

    def _stop_trace(self):
        try:
            jax.profiler.stop_trace()
        finally:
            self._tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- reporting -----------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        lines = ["-" * 64,
                 f"paddle_tpu profiler summary ({self.step_num} steps)"]
        if self._step_times:
            import numpy as np
            ts = np.asarray(self._step_times) * 1e3
            lines.append(f"step time ms: avg {ts.mean():.2f}  min {ts.min():.2f}"
                         f"  max {ts.max():.2f}")
        spans = snapshot_delta(host_events.snapshot(),
                               self._events_at_start)["spans"]
        spans = {k: v for k, v in spans.items() if v["count"]}
        if spans:
            lines.append(f"{'host span':<40}{'calls':>8}{'total ms':>12}")
            for name, row in sorted(spans.items(),
                                    key=lambda kv: -kv[1]["seconds"]):
                lines.append(f"{name:<40}{row['count']:>8}"
                             f"{row['seconds'] * 1e3:>12.2f}")
        if self._tracing or self._last_export_dir or not self.timer_only:
            lines.append(f"device trace (XPlane): {self.trace_dir} — open "
                         f"with TensorBoard's profile plugin")
        lines.append("-" * 64)
        out = "\n".join(lines)
        print(out)
        return out
