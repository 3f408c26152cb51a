"""From a profiler trace to numbers: device busy and idle time, time per
operation, idle gaps laid against what the host was doing, and the part
of collective time during which nothing else ran.

``read_xplane`` turns the profiler's ``.xplane.pb`` into a plain dict
(planes -> lines -> ``[name, start_ns, duration_ns]`` events); every
reduction below works on that dict, so the arithmetic is tested on a small
recorded excerpt (``tests/benchmark/fixtures``) without the profiler.

What counts as what:

* a device plane is one whose name starts with ``/device:TPU:``;
* its operations are the events of the line named ``XLA Ops`` (if there
  is none, the line with the most events). Events nest (a ``while``
  encloses its body), so time per operation is SELF time: an event's
  duration minus its children's;
* busy is the union of the operation intervals, leaving out control flow
  (``while``, ``conditional``, ``call``), whose interval is its children's;
  idle is the rest of the traced window, which runs from the first to the
  last event of any plane;
* a collective is an operation whose name starts with ``all-reduce``,
  ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
  ``collective-permute``; its exposed time is the part of its interval
  during which no other operation runs on that device.
"""

from __future__ import annotations

import gzip
import json
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
HOST_MIN_NS = 50_000          # host events shorter than this are dropped
Interval = Tuple[float, float]


def read_xplane(path: str) -> Dict[str, Any]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        device = p.name.startswith(DEVICE_PREFIX)
        lines = []
        for ln in p.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in ln.events
                   if e.duration_ns > 0 and (
                       device or e.duration_ns >= HOST_MIN_NS
                       or e.name.startswith("bench:"))]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def write_excerpt(events: Dict[str, Any], path: str,
                  span_ns: float = 150e6, max_host: int = 400) -> None:
    """A cut of ``span_ns`` from the middle of the trace, small enough to
    keep as a test fixture (gzipped JSON)."""
    lo, hi = window(events)
    a = lo + (hi - lo) / 2
    b = a + span_ns
    planes = []
    for p in events["planes"]:
        device = p["name"].startswith(DEVICE_PREFIX)
        lines = []
        for ln in p["lines"]:
            evs = [e for e in ln["events"] if e[1] >= a and e[1] + e[2] <= b]
            if not device:
                evs = sorted(evs, key=lambda e: -e[2])[:max_host]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    with gzip.open(path, "wt") as f:
        json.dump({"planes": planes}, f)
    summary = []
    for p in events["planes"]:
        for ln in p["lines"]:
            by: Dict[str, float] = {}
            for n, _, d in ln["events"]:
                by[n] = by.get(n, 0.0) + d
            summary.append({"plane": p["name"], "line": ln["name"],
                            "events": len(ln["events"]),
                            "top": sorted(by.items(),
                                          key=lambda kv: -kv[1])[:25]})
    with open(path + ".summary.json", "w") as f:
        json.dump(summary, f, indent=1)


def load_excerpt(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(events: Dict[str, Any]) -> Interval:
    starts = [e[1] for p in events["planes"] for ln in p["lines"]
              for e in ln["events"]]
    ends = [e[1] + e[2] for p in events["planes"] for ln in p["lines"]
            for e in ln["events"]]
    return min(starts), max(ends)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]
             ) -> List[Interval]:
    """The parts of ``intervals`` (disjoint, sorted) not in ``cover``
    (disjoint, sorted)."""
    out: List[Interval] = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def self_times(line_events: Sequence[Sequence]) -> List[Tuple[str, float,
                                                              float, float]]:
    """``(name, start, end, self_ns)`` per event of one line, where nested
    events take their time out of the event that encloses them."""
    evs = sorted(([n, s, s + d] for n, s, d in line_events),
                 key=lambda e: (e[1], -e[2]))
    out, stack = [], []           # stack of indices into out
    for name, s, e in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [tuple(x) for x in out]


_LAYOUT = re.compile(r"\{[^}]*\}")
_HLO = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\]).*? ([\w\-]+)\(")


def short_name(name: str, limit: int = 96) -> str:
    """The trace prints an operation as its whole HLO line. Keep its name,
    kind and first output shape (and a custom call's target):
    ``closed_call.10 custom-call:tpu_custom_call bf16[32,8,512,128]``."""
    m = _HLO.match(_LAYOUT.sub("", name))
    if not m:
        return name[:limit]
    op = m.group(3)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        op += ":" + target.group(1)
    return f"{m.group(1)} {op} {m.group(2)}"[:limit]


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def device_planes(events: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in events["planes"]
            if p["name"].startswith(DEVICE_PREFIX)]


def ops_line(plane: Dict[str, Any]) -> Dict[str, Any]:
    for ln in plane["lines"]:
        if ln["name"] == OPS_LINE:
            return ln
    return max(plane["lines"], key=lambda ln: len(ln["events"]))


def host_spans(events: Dict[str, Any]) -> List[Tuple[str, float, float]]:
    return [(e[0], e[1], e[1] + e[2]) for p in events["planes"]
            if not p["name"].startswith(DEVICE_PREFIX)
            for ln in p["lines"] for e in ln["events"]]


def attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]
              ) -> str:
    """What the host was doing in ``gap``: the benchmark's own span that
    covers most of it, else the host event that does, else a plain
    statement that no span covers it (the program has none of its own)."""
    best = {True: ("", 0.0), False: ("", 0.0)}
    for name, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        own = name.startswith("bench:")
        if ov > best[own][1]:
            best[own] = (name, ov)
    for own in (True, False):
        if best[own][1] >= 0.5 * (gap[1] - gap[0]):
            return best[own][0]
    return best[True][0] or best[False][0] or "no host span"


def reduce(events: Dict[str, Any], device_count: int, top: int = 10
           ) -> Optional[Dict[str, Any]]:
    """Busy seconds averaged over the devices used, the traced window,
    per-operation self time, the longest idle gaps with what the host was
    doing, and exposed collective time. None when no operation ran on a
    device (a CPU rehearsal has no device plane)."""
    planes = device_planes(events)[:device_count] or device_planes(events)
    if not planes:
        return None
    lo, hi = window(events)
    spans = host_spans(events)
    busy_ns, coll_ns, exposed_ns = 0.0, 0.0, 0.0
    by_name: Dict[str, float] = {}
    gaps: List[Interval] = []
    for i, plane in enumerate(planes):
        selfs = [x for x in self_times(ops_line(plane)["events"])
                 if not is_control_flow(x[0])]
        busy = union([(s, e) for _, s, e, _ in selfs])
        busy_ns += total(busy)
        for name, _, _, own in selfs:
            by_name[name] = by_name.get(name, 0.0) + own
        if i == 0:
            gaps = subtract([(lo, hi)], busy)
        colls = union([(s, e) for n, s, e, _ in selfs if is_collective(n)])
        others = union([(s, e) for n, s, e, _ in selfs
                        if not is_collective(n)])
        coll_ns += total(colls)
        exposed_ns += total(subtract(colls, others))
    n = len(planes)
    if busy_ns <= 0:
        return None
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "op_self_s": {k: v / n / 1e9 for k, v in by_name.items()},
        "breakdown": {
            "device_ops": [[short_name(k), v / n / 1e9] for k, v in ops],
            "idle_gaps": [[short_name(attribute(g, spans)),
                           (g[1] - g[0]) / 1e9]
                          for g in longest]},
    }


def is_control_flow(name: str) -> bool:
    """A ``while``, ``conditional`` or ``call`` encloses the operations
    that run inside it and does no work of its own: its interval is not
    busy time, the gaps between its children are idle."""
    return name.lstrip("%").startswith(("while", "conditional", "call"))
