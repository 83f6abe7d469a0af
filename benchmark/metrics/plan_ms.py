"""``plan_ms``: host time the program spent turning query text into
engine calls, per completed query: the self time of its ``sql.compile``
spans (FugueSQL parse and DAG build) and ``sql.plan`` spans (a SELECT
parsed and translated into device plans) in the window, over the queries
completed (``benchmark/spans.py``)."""

from benchmark import spans


def read(run):
    sp = spans.of_run(run, __file__)
    done = spans.completed(run)
    plan = [e for e in sp.events if e.name in ("sql.compile", "sql.plan")] if sp else []
    if not plan or not done:
        return None
    return sum(e.self_ns for e in plan) / 1e6 / done
