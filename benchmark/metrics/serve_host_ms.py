"""``serve_host_ms``: the daemon's host time a query, outside its job's
execution and its scheduler wait: the mean, over ``http.request`` spans
that submitted a job in the window, of the request's duration less that
job's ``serve.execute`` duration and less its ``queued_us`` (counted in
``serve_queue_ms``). A request and its job are paired by their
``job_id`` attributes (``benchmark/spans.py``)."""

from benchmark import spans


def read(run):
    sp = spans.of_run(run, __file__)
    if not sp:
        return None
    jobs = {e.stats.get("job_id"): e for e in sp.named("serve.execute")}
    host = []
    for req in sp.named("http.request"):
        job = jobs.get(req.stats.get("job_id"))
        if job is not None and "queued_us" in job.stats:
            host.append((req.end - req.start) - (job.end - job.start) - job.stats["queued_us"] * 1e3)
    if not host:
        return None
    return sum(host) / len(host) / 1e6
