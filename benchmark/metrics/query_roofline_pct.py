"""``query_roofline_pct``: the least time the chips could take for the
window's queries over the device-busy time they took, in percent.

Each query must read its referenced columns once (rows x device width,
``benchmark/queries/<q>.py`` ``READS``); its arithmetic is a few
operations per row, far below the FLOP peak, so the bound is HBM
bandwidth (``benchmark/peaks.json``). Bandwidth-bound by construction.
The rows are sharded over every chip that ran, each reading its share
at its own bandwidth: the least time divides by ``devices`` (the device
planes that ran an operation in the window), as ``busy_s`` is their
mean."""


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    bw = run.peak("hbm_bytes_per_s") * t["devices"]
    least = sum(run.query_bytes[r["query"]] for r in run.records if r["ok"]) / bw
    return 100.0 * least / t["busy_s"]
