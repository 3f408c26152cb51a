"""From events to numbers: percentiles, window accounting, compile clock.

Times are seconds on one host clock (``time.perf_counter``). A request
record is a dict the load generator fills:

``due`` (when it was due to be sent; closed loop: when it was sent),
``sent``, ``first`` and ``last`` (token event times, None until seen),
``n_out`` (token events received), ``want`` (tokens asked for), ``ok``
(finished with every token) and ``error`` (text, when it failed).
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks (numpy's default), on a copy sorted here."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def percentile_with_missing(values: Sequence[float], missing: int,
                            q: float) -> Optional[float]:
    """Percentile over ``len(values) + missing`` requests where a missing
    one (failed, refused, unfinished) is later than every measured one.
    None when the rank falls among the missing: then the tail has no
    number, and the metric is left out of the line rather than flattered."""
    if missing <= 0:
        return percentile(values, q)
    xs = sorted(float(v) for v in values)
    n = len(xs) + int(missing)
    pos = (n - 1) * q / 100.0
    hi = min(int(math.floor(pos)) + 1, n - 1)
    if hi >= len(xs):
        return None
    lo = int(math.floor(pos))
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_in_window(records: Iterable[Dict], t0: float, t1: float
                  ) -> List[Dict]:
    return [r for r in records if t0 <= r["due"] < t1]


def ttft_s(records: Iterable[Dict]) -> List[float]:
    """First token event minus the time the request was DUE, for the
    requests that finished."""
    return [r["first"] - r["due"] for r in records if r.get("ok")]


def tpot_s(records: Iterable[Dict]) -> List[float]:
    """(last token event - first token event) / (tokens - 1) for finished
    requests of at least two tokens: the gap between tokens a reader
    feels."""
    return [(r["last"] - r["first"]) / (r["n_out"] - 1)
            for r in records if r.get("ok") and r["n_out"] > 1]


def count_failed(records: Iterable[Dict]) -> int:
    """Requests that failed, were refused, or had not finished when the
    drain ended. They get no latency: they count as missing."""
    return sum(1 for r in records if not r.get("ok"))


def tokens_in_window(token_times: Sequence[float], t0: float, t1: float
                     ) -> int:
    return sum(1 for t in token_times if t0 <= t < t1)


def tapered_rate(token_times: Sequence[float], t0: float, t1: float,
                 edge_s: float) -> float:
    """Tokens per second over the window ``[t0, t1)`` with soft edges: a
    token counts with a weight that rises linearly from 0 to 1 over the
    window's first ``edge_s`` seconds, is 1 in between and falls over its
    last ``edge_s``; the sum of weights is divided by the trapezoid's area.
    All the work and all the time of the window are in it. Why not a hard
    edge: a decode dispatch hands the clients up to ``decode_chunk`` tokens
    a slot in one instant (256 at once here), so by which side of a hard
    edge one such burst lands a run reads 6% higher or lower (measured,
    PERF.md). ``edge_s`` 0 is the plain count over the window."""
    edge = min(float(edge_s), (t1 - t0) / 2)
    if edge <= 0:
        return tokens_in_window(token_times, t0, t1) / (t1 - t0)
    total = sum(min(1.0, (t - t0) / edge, (t1 - t) / edge)
                for t in token_times if t0 <= t < t1)
    return total / (t1 - t0 - edge)


def whole_step_rate(finish_times: Sequence[float], t0: float,
                    seconds: float, tokens_per_step: int) -> Dict:
    """Rate over whole steps: from ``t0`` (the finish of the last warm-up
    step) to the first step finish at or after ``t0 + seconds``. Taking the
    window to a step boundary keeps all the work and all the time and
    removes the +-1 step a fixed cut would add to a run of a few dozen
    steps."""
    done = [t for t in finish_times if t > t0]
    n = next((i + 1 for i, t in enumerate(done) if t - t0 >= seconds), None)
    if n is None:
        raise ValueError("the run ended before the window did")
    span = done[n - 1] - t0
    return {"steps": n, "window_s": span,
            "tokens_per_s": n * tokens_per_step / span}


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling (or fetching from
    the persistent cache), and the count of backend compilations, from
    jax's own monitoring events (copied from ``chip_smoke.CompileClock``).
    ``compiles`` read before and after the window says whether anything
    compiled inside it; that must be 0."""

    _EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
               "backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith(self._EVENTS):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")
