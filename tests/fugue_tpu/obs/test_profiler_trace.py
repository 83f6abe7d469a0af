"""Spans on the profiler's clock: while ``jax.profiler`` records, every
``start_span`` span is also a host event of the profiler trace, with its
attributes as event stats, whether or not an obs trace is open.
Tier-1 compatible; select with ``-m obs``."""

import glob
import os

import jax
import pandas as pd
import pytest

from fugue_tpu.obs.trace import NULL_CM, Trace, activate, current_span, start_span

pytestmark = pytest.mark.obs

_SQL = "SELECT k, SUM(v) AS s, COUNT(*) AS n FROM t GROUP BY k"


def _record(tmp_path, fn):
    """Run ``fn`` under the profiler; the host events of the trace as
    ``{name: [(start_ns, end_ns, line_index, stats), ...]}``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, n, dict(ev.stats))
                    )
    return events


def _within(inner, outers):
    return any(s <= inner[0] and inner[1] <= e for s, e, *_ in outers)


def test_fugue_sql_group_by_leaves_its_spans_on_the_host_plane(tmp_path):
    from fugue_tpu import fugue_sql, make_execution_engine

    engine = make_execution_engine("jax")
    t = pd.DataFrame({"k": [0, 0, 1, 1, 2], "v": [1.0, 2.0, 3.0, 4.0, 5.0]})
    fugue_sql(_SQL, t=t, engine=engine, as_fugue=True).as_pandas()  # compiled before
    out = {}

    def query():
        with jax.profiler.TraceAnnotation("query"):
            out["df"] = fugue_sql(_SQL, t=t, engine=engine, as_fugue=True).as_pandas()

    ev = _record(tmp_path, query)
    assert sorted(out["df"]["s"]) == [3.0, 5.0, 7.0]
    for name in ("sql.compile", "sql.plan", "engine.dispatch", "engine.fetch", "workflow.run"):
        assert ev.get(name), f"no {name} event: {sorted(ev)}"
        for e in ev[name]:
            assert _within(e, ev["query"]), name
    (compile_,) = ev["sql.compile"]
    (run,) = ev["workflow.run"]
    assert compile_[1] <= run[0]  # the DAG is built, then run
    for name in ("sql.plan", "engine.dispatch"):
        assert all(_within(e, ev["workflow.run"]) for e in ev[name]), name
    assert ev["engine.fetch"][-1][0] >= run[1]  # the result is fetched after the run
    dispatch_stats = [e[3] for e in ev["engine.dispatch"]]
    assert all(s["compiled"] in (0, 1) and s["program"] for s in dispatch_stats)
    assert all(s["compiled"] == 0 for s in dispatch_stats)  # warmed above
    assert ev["engine.fetch"][-1][3]["rows"] == 3


def test_obs_trace_and_profiler_both_record(tmp_path):
    trace = Trace()
    root = trace.root("root")
    got = {}

    def spans():
        with activate(root):
            with start_span("obs.both", a=1) as sp:
                sp.set_attr(b="x")
                got["span"] = sp
        root.finish()

    ev = _record(tmp_path, spans)
    (span,) = trace.find("obs.both")
    assert span is got["span"] and span.attrs == {"a": 1, "b": "x"}
    assert span.end_ns is not None and span._event is None
    ((_, _, _, stats),) = ev["obs.both"]
    assert stats == {"a": 1, "b": "x"}
    assert "root" not in ev  # a trace root is not a start_span span


def test_profiler_alone_records_without_an_obs_span(tmp_path):
    seen = {}

    def spans():
        with start_span("bridge.only", k=2) as sp:
            seen["current"] = current_span()
            seen["truthy"] = bool(sp)
            sp.set_attr(done=1)
        with pytest.raises(ValueError):
            with start_span("bridge.raises"):
                raise ValueError("x")

    ev = _record(tmp_path, spans)
    assert seen == {"current": None, "truthy": True}
    assert ev["bridge.only"][0][3] == {"k": 2, "done": 1}
    assert ev["bridge.raises"][0][3] == {"error": "ValueError"}
    assert start_span("after") is NULL_CM


def test_serve_daemon_spans_carry_queue_and_job_ids(tmp_path):
    from fugue_tpu.serve import ServeClient, ServeDaemon

    query = (
        "t = CREATE [[0,1],[0,2],[1,3],[1,4]] SCHEMA k:long,v:long\n"
        "SELECT k, SUM(v) AS s FROM t GROUP BY k"
    )
    with ServeDaemon({}) as daemon:
        client = ServeClient(*daemon.address)
        sid = client.create_session()
        client.sql(sid, query)  # warm
        answers = []

        def two_queries():
            for _ in range(2):
                answers.append(client.sql(sid, query))

        ev = _record(tmp_path, two_queries)
    assert [a["status"] for a in answers] == ["done", "done"]
    jobs = {a["job_id"] for a in answers}
    execs = {e[3]["job_id"]: e for e in ev["serve.execute"]}
    assert set(execs) == jobs
    assert all(isinstance(e[3]["queued_us"], int) and e[3]["queued_us"] >= 0 for e in execs.values())
    reqs = [e for e in ev["http.request"] if e[3].get("job_id") in jobs]
    assert len(reqs) == 2
    for req in reqs:
        assert req[3]["route"] == "sessions" and req[3]["status"] == 200
        job = execs[req[3]["job_id"]]
        assert req[0] <= job[0] and job[1] <= req[1]  # the request holds its job
        assert job[2] != req[2]  # on a worker thread, not the handler's
    assert {e[3].get("bytes", 0) > 0 for e in ev["serve.encode"] if "bytes" in e[3]} == {True}
