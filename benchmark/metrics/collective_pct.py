"""``collective_pct``: 100 x the device time in cross-chip collective
operations (``all-reduce``, ``all-gather``, ``all-to-all``,
``reduce-scatter``, ``collective-permute``, their async starts and dones
included), unioned per device plane and averaged over the planes that
ran (``benchmark/collectives.py``), over the device-busy seconds
(``benchmark/trace.py``, the same mean). It includes each chip's wait at
the collective for the slowest chip, so it reads load imbalance as well
as traffic. None for an untraced run or a trace with no device plane."""

from benchmark import collectives


def read(run):
    coll = collectives.of_run(run, __file__)
    if coll is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * coll.seconds / run.trace["busy_s"]
