"""Time in cross-chip collective operations in a profiler trace of one
measured window.

    python -m benchmark.collectives <path.xplane.pb>

XLA inserts the collectives of a sharded program itself (the cross-chip
sum of a group-by over row-sharded columns is an all-reduce); they run as
operations on each device plane's ``XLA Ops`` line, each event named by
its HLO instruction, whose name starts with the opcode: ``all-reduce``,
``all-gather``, ``all-to-all``, ``reduce-scatter``,
``collective-permute``, each also as an asynchronous ``-start`` /
``-done`` pair, with a ``.<n>`` suffix. This module reads
those events, clipped to the harness's ``bench.window``, and per device
plane takes the union of their intervals; ``seconds`` is its mean over
the planes that ran an operation in the window, the planes whose busy
time ``benchmark/trace.py`` averages. A chip that reaches an all-reduce
first waits there for the slowest, so the time reads both the traffic
between chips and the imbalance of their work.

A metric reader finds the trace of its run from its own path, as
``benchmark/spans.py`` does, and the parse is cached per file. The
command prints the seconds, the planes, and each collective opcode seen
with its count and total seconds.
"""

import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from benchmark.trace import DEVICE_PLANE, OPS_LINE, WINDOW, _clip, _union

TRACE_DIR = ".bench_trace"
OPCODES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter", "collective-permute")
COLLECTIVE = re.compile(r"^%?(?:" + "|".join(OPCODES) + r")(?:-start|-done)?(?:\.\d+)*$")

Op = Tuple[str, float, float]


def is_collective(name: str) -> bool:
    """Whether an ``XLA Ops`` event is a collective. On the chip the event
    is named by its whole HLO instruction (``%all-reduce.2 = s32[12]{0}
    all-reduce(...), ...``): the instruction's name is what precedes
    `` = ``."""
    return (name.lstrip("%").startswith(OPCODES)
            and COLLECTIVE.match(name.split(" = ", 1)[0]) is not None)


def _plane(ops: Iterable[Op], lo: float, hi: float) -> Optional[List[Op]]:
    """The collective operations of one plane clipped to ``[lo, hi]``;
    None where the plane ran no operation there."""
    ran = False
    coll = []
    for name, s, e in ops:
        c = _clip(s, e, lo, hi)
        if c:
            ran = True
            if is_collective(name):
                coll.append((name, c[0], c[1]))
    return coll if ran else None


def _seconds(planes: List[Optional[List[Op]]]) -> Tuple[float, int]:
    per_plane = [sum(e - s for s, e in _union([(s, e) for _, s, e in coll])) / 1e9
                 for coll in planes if coll is not None]
    if not per_plane:
        return 0.0, 0
    return sum(per_plane) / len(per_plane), len(per_plane)


def collective_seconds(planes: List[List[Op]], lo: float, hi: float) -> Tuple[float, int]:
    """``(seconds, planes)``: the union of the collective operations'
    intervals of each plane (``(name, start_ns, end_ns)`` events of its
    ``XLA Ops`` line), clipped to ``[lo, hi]``, averaged over the planes
    with any operation in the window; ``(0.0, 0)`` where none has one."""
    return _seconds([_plane(ops, lo, hi) for ops in planes])


class Collectives:
    """One window's collective time: ``seconds`` (mean over the planes
    that ran), ``planes`` and ``opcodes`` (``{name: [count, seconds]}``
    summed over every plane, clipped to the window)."""

    def __init__(self, seconds: float, planes: int, opcodes: Dict[str, List[float]]):
        self.seconds = seconds
        self.planes = planes
        self.opcodes = opcodes


def _ops(plane: Any) -> Iterator[Op]:
    for line in plane.lines:
        if line.name == OPS_LINE:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def parse(path: str) -> Collectives:
    """One pass over the trace: the window from the first host plane that
    holds it, then each device plane's ``XLA Ops`` events, of which only
    the collectives are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = sorted(data.planes, key=lambda p: p.name)
    window: Optional[Tuple[float, float]] = None
    for plane in planes:
        if plane.name.startswith("/host:"):
            window = _window(plane)
            if window is not None:
                break
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on a host plane")
    lo, hi = window
    found = [_plane(_ops(p), lo, hi) for p in planes if DEVICE_PLANE.match(p.name)]
    seconds, n = _seconds(found)
    opcodes: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for coll in found:
        for name, s, e in coll or ():
            head = name.split(" = ", 1)[0]
            opcodes[head][0] += 1
            opcodes[head][1] += (e - s) / 1e9
    return Collectives(seconds, n, dict(opcodes))


def _window(plane: Any) -> Optional[Tuple[float, float]]:
    """The ``bench.window`` annotation of a host plane, read up to the
    first one (a traced run has one; the rest of the plane is not read)."""
    for line in plane.lines:
        for ev in line.events:
            if ev.name == WINDOW:
                return (ev.start_ns, ev.start_ns + ev.duration_ns)
    return None


_CACHE: Dict[Any, Collectives] = {}


def load(path: str) -> Collectives:
    """``parse(path)``, once per file as it stands on disk."""
    st = os.stat(path)
    key = (os.path.abspath(path), st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = parse(path)
    return _CACHE[key]


def of_run(run: Any, reader_file: str) -> Optional[Collectives]:
    """The collectives of a traced run, found from the path of the reader
    ``<checkout>/benchmark/metrics/<name>.py``; None for an untraced run,
    a missing trace, or a trace with no device plane that ran."""
    if not run.trace:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
    found = []
    for d, _, files in os.walk(os.path.join(root, TRACE_DIR)):
        found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
    if len(found) != 1:
        return None
    coll = load(found[0])
    return coll if coll.planes else None


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    coll = parse(argv[0])
    print(f"collective seconds {coll.seconds:.6f} (mean of {coll.planes} planes)")
    for name, (count, secs) in sorted(coll.opcodes.items(), key=lambda kv: -kv[1][1]):
        print(f"  {name:40s} {int(count):8d} {secs:12.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
