"""``device_idle_pct``: 100 x (1 - device-busy seconds / traced window),
busy being the union of the device's op intervals in the profiler trace
(``benchmark/trace.py``)."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
