"""``idle_unexplained_pct``: 100 x the device's idle seconds during which
no span of the program was open on any thread, over the traced window:
the base of ``device_idle_pct``, so it never exceeds it
(``benchmark/spans.py``)."""

from benchmark import spans


def read(run):
    sp = spans.of_run(run, __file__)
    t = run.trace
    if not sp or sp.idle is None or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * sp.idle.get(spans.NO_SPAN, 0.0) / t["window_s"]
