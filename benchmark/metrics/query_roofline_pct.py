"""``query_roofline_pct``: the least time the chip could take for the
window's queries over the device-busy time they took, in percent.

Each query must read its referenced columns once (rows x device width,
``benchmark/queries/<q>.py`` ``READS``); its arithmetic is a few
operations per row, far below the FLOP peak, so the bound is HBM
bandwidth (``benchmark/peaks.json``). Bandwidth-bound by construction."""


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    bw = run.peak("hbm_bytes_per_s")
    least = sum(run.query_bytes[r["query"]] for r in run.records if r["ok"]) / bw
    return 100.0 * least / t["busy_s"]
