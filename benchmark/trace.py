"""Reduce a profiler trace (``.xplane.pb``) of one measured window.

Reads the file with ``jax.profiler.ProfileData`` alone. The window is the
benchmark's own ``bench.window`` annotation on the host; everything is
clipped to it. Per device plane (``/device:TPU:<n>``) it takes:

- busy: the union of the intervals of the ``XLA Ops`` line's events;
- per-program device time from the ``XLA Modules`` line (the jitted
  programs, which keep their names across refactors; op names such as
  ``fusion.12`` do not);
- idle gaps: the complement of busy, each labelled by the innermost
  ``bench.*`` annotation on the host that covers its midpoint (what the
  harness was doing meanwhile), or ``(no bench span)``.

``busy_s`` is averaged over the device planes that ran an operation in
the window. All times are seconds.
"""

import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
LABEL_PREFIX = "bench."
TOP = 10

Interval = Tuple[float, float]


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line: Any) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]


def reduce(path: str) -> Dict[str, Any]:
    """Summary of the trace at ``path``: ``window_s``, ``busy_s``,
    ``devices``, ``device_ops`` and ``idle_gaps`` (each at most 10
    ``[name, seconds]`` pairs, largest first)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    spans: List[Tuple[str, float, float]] = []
    device_planes = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW:
                        window = (s, e)
                    elif name.startswith(LABEL_PREFIX):
                        spans.append((name, s, e))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on a host plane")
    lo, hi = window
    busy_per_device: List[float] = []
    programs: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    spans.sort(key=lambda x: x[1])
    first_busy: Optional[List[Interval]] = None
    for plane in sorted(device_planes, key=lambda p: p.name):
        ops: List[Interval] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for _, s, e in _events(line):
                    c = _clip(s, e, lo, hi)
                    if c:
                        ops.append(c)
            elif line.name == MODULES_LINE:
                for name, s, e in _events(line):
                    c = _clip(s, e, lo, hi)
                    if c:
                        programs[name] += (c[1] - c[0]) / 1e9
        if not ops:
            continue
        busy = _union(ops)
        busy_per_device.append(sum(e - s for s, e in busy) / 1e9)
        if first_busy is None:
            first_busy = busy
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_label((s + e) / 2, spans)] += (e - s) / 1e9
    busy_s = sum(busy_per_device) / len(busy_per_device) if busy_per_device else 0.0
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "devices": len(busy_per_device),
        "device_ops": _top(programs),
        "idle_gaps": _top(gaps),
    }


def _label(t: float, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost (latest-starting) bench span that covers ``t``."""
    best = "(no bench span)"
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best


def _top(totals: Dict[str, float]) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]]
