"""The program's own spans in a profiler trace of one measured window.

    python -m benchmark.spans <path.xplane.pb>

While ``jax.profiler`` records, every span the program opens through
``fugue_tpu.obs.trace.start_span`` is also a host event of the trace,
named like the span, its attributes as the event's stats. This module
reads the events named in ``PROGRAM_SPANS`` from the host planes,
clipped to the harness's ``bench.window``, per thread (a line of a host
plane), and gives:

- each span's total and self time (its duration less that of the spans
  nested in it on the same thread);
- the device's idle seconds under each program span: each idle gap is
  cut at the program spans' starts and ends inside it, and each piece
  labelled by the innermost (latest started) program span open at its
  midpoint on any thread, or ``(no program span)``. So a gap that spans
  a fetch, the harness's own work and the next query's parse is shared
  among them, not given whole to the span at its midpoint. The idle and
  window rules, ``_union`` and ``_clip``, are those of
  ``benchmark/trace.py``, and the labels follow its ``_label`` rule.

A metric reader finds the trace of its run from its own path: the
``.xplane.pb`` under ``<checkout>/.bench_trace`` that ``run.py`` wrote
and reduced. The parse is cached per file, so the readers of one run
read it once. A trace with none of these spans (a program that records
none) gives readings of None. The command prints each span's total
seconds, count and self seconds, and the idle seconds under each label.
"""

import heapq
import os
from bisect import bisect_left, bisect_right
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from benchmark.trace import DEVICE_PLANE, OPS_LINE, WINDOW, _clip, _events, _union

PROGRAM_SPANS = frozenset({
    "http.request", "serve.execute", "serve.encode",
    "sql.compile", "sql.plan",
    "workflow.run", "task", "task.attempt",
    "engine.dispatch", "engine.fetch", "engine.device_sync",
    "cache.persist", "stream.batch",
})
NO_SPAN = "(no program span)"
TRACE_DIR = ".bench_trace"


class Event:
    """One program span: clipped bounds (ns), its thread and its stats."""

    __slots__ = ("name", "start", "end", "thread", "stats", "self_ns")

    def __init__(self, name: str, start: float, end: float, thread: Tuple[int, int],
                 stats: Dict[str, Any]):
        self.name = name
        self.start = start
        self.end = end
        self.thread = thread
        self.stats = stats
        self.self_ns = end - start

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


class Spans:
    """The program spans of one window: ``events`` (start order),
    ``window_s``, and ``idle`` (seconds of device idle under each label,
    None without a device plane)."""

    def __init__(self, events: List[Event], window_s: float,
                 idle: Optional[Dict[str, float]]):
        self.events = events
        self.window_s = window_s
        self.idle = idle

    def named(self, name: str) -> List[Event]:
        return [e for e in self.events if e.name == name]


def self_times(events: List[Event]) -> None:
    """Set each event's ``self_ns``: its duration less the events nested
    directly in it on the same thread."""
    threads: Dict[Any, List[Event]] = defaultdict(list)
    for e in events:
        threads[e.thread].append(e)
    for evs in threads.values():
        stack: List[Event] = []
        for e in sorted(evs, key=lambda x: (x.start, -x.end)):
            e.self_ns = e.end - e.start
            while stack and stack[-1].end <= e.start:
                stack.pop()
            if stack and e.end <= stack[-1].end:
                stack[-1].self_ns -= e.end - e.start
            stack.append(e)


def innermost_labels(points: List[float], spans: List[Tuple[str, float, float]]) -> List[str]:
    """For each of the increasing ``points``, the name of the span that
    started last among those covering it (``s <= t <= e``), or
    ``NO_SPAN``: ``benchmark/trace.py``'s ``_label`` rule, in one sweep."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap: List[Tuple[float, int]] = []  # (-start, -order) of started spans
    labels = []
    k = 0
    for t in points:
        while k < len(order) and spans[order[k]][1] <= t:
            heapq.heappush(heap, (-spans[order[k]][1], -k))
            k += 1
        while heap and spans[order[-heap[0][1]]][2] < t:
            heapq.heappop(heap)  # ended before t, so before every later point
        labels.append(spans[order[-heap[0][1]]][0] if heap else NO_SPAN)
    return labels


def cut_at(gaps: List[Tuple[float, float]], edges: List[float]) -> List[Tuple[float, float]]:
    """Each gap cut at the sorted ``edges`` that lie inside it."""
    pieces = []
    for s, e in gaps:
        cuts = [s] + edges[bisect_right(edges, s):bisect_left(edges, e)] + [e]
        pieces += [(a, b) for a, b in zip(cuts, cuts[1:]) if b > a]
    return pieces


def _idle_gaps(device_planes: List[Any], lo: float, hi: float) -> Optional[List[Tuple[float, float]]]:
    """The complement of busy on the first device plane that ran an op
    in the window, as ``benchmark/trace.py`` takes it; None without one."""
    for plane in sorted(device_planes, key=lambda p: p.name):
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for _, s, e in _events(line):
                    c = _clip(s, e, lo, hi)
                    if c:
                        ops.append(c)
        if ops:
            edges = [lo] + [x for iv in _union(ops) for x in iv] + [hi]
            return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return None


def parse(path: str) -> Spans:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    raw = []
    device_planes = []
    for p, plane in enumerate(data.planes):
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in PROGRAM_SPANS:
                        raw.append((ev, (p, n)))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on a host plane")
    lo, hi = window
    events = []
    for ev, thread in raw:
        c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
        if c:
            events.append(Event(ev.name, c[0], c[1], thread, dict(ev.stats)))
    events.sort(key=lambda e: e.start)
    self_times(events)
    idle = None
    gaps = _idle_gaps(device_planes, lo, hi)
    if gaps is not None:
        idle = defaultdict(float)
        spans = [(e.name, e.start, e.end) for e in events]
        pieces = cut_at(gaps, sorted({t for _, s, e in spans for t in (s, e)}))
        for (s, e), label in zip(pieces, innermost_labels([(s + e) / 2 for s, e in pieces], spans)):
            idle[label] += (e - s) / 1e9
        idle = dict(idle)
    return Spans(events, (hi - lo) / 1e9, idle)


_CACHE: Dict[Any, Spans] = {}


def load(path: str) -> Spans:
    """``parse(path)``, once per file as it stands on disk."""
    st = os.stat(path)
    key = (os.path.abspath(path), st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = parse(path)
    return _CACHE[key]


def of_run(run: Any, reader_file: str) -> Optional[Spans]:
    """The program spans of a traced run, found from the path of the
    reader ``<checkout>/benchmark/metrics/<name>.py``; None for an
    untraced run, a missing trace, or a trace with no program span."""
    if not run.trace:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
    found = []
    for d, _, files in os.walk(os.path.join(root, TRACE_DIR)):
        found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
    if len(found) != 1:
        return None
    spans = load(found[0])
    return spans if spans.events else None


def completed(run: Any) -> int:
    return sum(1 for r in run.records if r["ok"])


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    sp = parse(argv[0])
    totals: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    for e in sp.events:
        t = totals[e.name]
        t[0] += 1
        t[1] += e.seconds
        t[2] += e.self_ns / 1e9
    print(f"window {sp.window_s:.6f} s")
    print(f"{'span':<20} {'count':>7} {'total_s':>12} {'self_s':>12}")
    for name, (n, total, own) in sorted(totals.items(), key=lambda kv: -kv[1][1]):
        print(f"{name:<20} {n:>7} {total:>12.6f} {own:>12.6f}")
    if sp.idle is None:
        print("no device plane: no idle gaps")
        return 0
    print(f"device idle {sum(sp.idle.values()):.6f} s, by innermost program span:")
    for name, s in sorted(sp.idle.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<20} {s:>12.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
