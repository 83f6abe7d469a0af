"""``rows_per_s``: the table rows scanned by every query completed in the
window, over the window (host clock). Queries start while the clock is
under ``--seconds``; the window closes when the last one completes."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(r["rows"] for r in run.records if r["ok"]) / run.window_s
