"""``serve_overhead_ms``: mean client latency minus the daemon's mean job
time over the window (the ``fugue_serve_job_seconds`` histogram's sum and
count deltas for finished jobs): HTTP, session, scheduling and JSON."""


def read(run):
    before = run.counters_before.get("job_seconds")
    after = run.counters_after.get("job_seconds")
    done = [r for r in run.records if r["ok"]]
    if not before or not after or not done:
        return None
    jobs = after["count"] - before["count"]
    if jobs <= 0:
        return None
    client = sum(r["t_done"] - r["t_send"] for r in done) / len(done)
    return 1e3 * (client - (after["sum"] - before["sum"]) / jobs)
