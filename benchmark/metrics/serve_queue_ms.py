"""``serve_queue_ms``: the mean scheduler wait of the daemon's jobs in the
window: the ``queued_us`` attribute (worker pickup less submission) of
their ``serve.execute`` spans, in ms (``benchmark/spans.py``)."""

from benchmark import spans


def read(run):
    sp = spans.of_run(run, __file__)
    queued = [e.stats["queued_us"] for e in sp.named("serve.execute")
              if "queued_us" in e.stats] if sp else []
    if not queued:
        return None
    return sum(queued) / len(queued) / 1e3
