"""``fetch_ms``: time in ``engine.fetch`` spans (a result's device to
host copy and Arrow build, ``jax_backend/blocks.to_arrow``) in the
window, per completed query (``benchmark/spans.py``)."""

from benchmark import spans


def read(run):
    sp = spans.of_run(run, __file__)
    done = spans.completed(run)
    fetch = sp.named("engine.fetch") if sp else []
    if not fetch or not done:
        return None
    return sum(e.end - e.start for e in fetch) / 1e6 / done
