"""``benchmark/spans.py`` and the readers of the program's spans: self
time and innermost labels on hand-made intervals, the five readers on a
trace recorded on the CPU here, and on the recorded chip trace in
``data/``, made by a program with no spans, where they read nothing."""

import glob
import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import REPO, tiny_root

READERS = ("plan_ms", "fetch_ms", "serve_queue_ms", "serve_host_ms", "idle_unexplained_pct")
CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                          "serve_trace.xplane.pb.gz")


def _event(name, start, end, thread=(0, 0)):
    from benchmark.spans import Event

    return Event(name, start, end, thread, {})


def test_self_times_subtract_children_on_the_same_thread():
    from benchmark.spans import self_times

    outer = _event("http.request", 0, 100)
    child = _event("serve.encode", 10, 30)
    grandchild = _event("engine.fetch", 12, 20)
    later = _event("serve.encode", 40, 50)
    other = _event("serve.execute", 20, 90, thread=(0, 1))  # another thread
    self_times([later, other, grandchild, outer, child])
    assert outer.self_ns == 100 - 20 - 10
    assert child.self_ns == 20 - 8
    assert grandchild.self_ns == 8 and later.self_ns == 10
    assert other.self_ns == 70


@pytest.mark.parametrize(
    "points,want",
    [
        ([5], ["serve.execute"]),  # the innermost: started last
        ([9.5], ["http.request"]),
        ([11], ["(no program span)"]),
        ([0, 1, 5, 9, 10, 10.5], ["http.request", "serve.execute", "serve.execute",
                                  "serve.execute", "http.request", "(no program span)"]),
    ],
)
def test_innermost_labels_follow_the_bench_rule(points, want):
    from benchmark import trace
    from benchmark.spans import NO_SPAN, innermost_labels

    spans = [("http.request", 0, 10), ("serve.execute", 1, 9)]
    assert innermost_labels(points, spans) == want
    # the same rule as benchmark/trace.py's labels of bench spans
    by_trace = [trace._label(t, sorted(spans, key=lambda s: s[1])) for t in points]
    assert [NO_SPAN if x == "(no bench span)" else x for x in by_trace] == want


@pytest.fixture(scope="module")
def serve_run(tmp_path_factory):
    """A traced tiny run of the serve cell on the CPU: its result, its
    per-query latencies, and a copy of its trace."""
    from benchmark import run

    root = tiny_root(tmp_path_factory.mktemp("bench"))
    lines = []
    res = run.run_cell("tpch_serve_sf1.streams2", 11, 0.5, trace=True, require_tpu=False,
                       root=root, log=lambda s: None, out=lines.append)
    window = [json.loads(x)["window"] for x in lines if x.startswith('{"window"')][0]
    (path,) = glob.glob(os.path.join(root, ".bench_trace", "**", "*.xplane.pb"), recursive=True)
    kept = str(tmp_path_factory.mktemp("kept") / "serve.xplane.pb")
    shutil.copy(path, kept)
    return res, [lat for _, _, lat in window["query_s"]], kept


def test_readers_on_a_cpu_trace(serve_run):
    res, latencies, _ = serve_run
    assert res["correct"]
    got = {m: res["metrics"].get(m, {}).get("value") for m in READERS}
    assert got["idle_unexplained_pct"] is None  # no TPU plane on the CPU
    for m in ("plan_ms", "fetch_ms", "serve_queue_ms", "serve_host_ms"):
        assert isinstance(got[m], float) and got[m] >= 0, (m, got)
    client_ms = 1e3 * sum(latencies) / len(latencies)
    assert 0 < got["serve_host_ms"] < client_ms
    assert 0 < got["plan_ms"] < client_ms


def test_spans_command_on_a_cpu_trace(serve_run):
    _, _, path = serve_run
    proc = subprocess.run([sys.executable, "-m", "benchmark.spans", path], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for name in ("http.request", "serve.execute", "sql.compile", "sql.plan", "engine.fetch"):
        assert f"\n{name} " in proc.stdout, proc.stdout
    assert "no device plane" in proc.stdout


def test_chip_trace_without_program_spans_reads_nothing(tmp_path):
    """The recorded chip trace (a program with no spans): every reader reads
    None, and the idle the spans module finds is the idle of
    ``benchmark/trace.py``, all of it under no program span."""
    from benchmark import run, spans
    from benchmark.trace import reduce

    root = tiny_root(tmp_path)
    os.makedirs(os.path.join(root, ".bench_trace"))
    path = os.path.join(root, ".bench_trace", "serve.xplane.pb")
    with gzip.open(CHIP_TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    summary = reduce(path)
    ctx = run.RunContext(trace=summary, records=[{"query": "q1", "ok": True}])
    for m in READERS:
        assert run.plugin("metrics", m, root).read(ctx) is None, m
    sp = spans.parse(path)
    assert sp.events == []
    assert list(sp.idle) == [spans.NO_SPAN]
    assert sp.idle[spans.NO_SPAN] == pytest.approx(summary["window_s"] - summary["busy_s"], abs=1e-9)


def test_idle_gaps_are_cut_at_span_edges():
    from benchmark.spans import NO_SPAN, cut_at, innermost_labels

    # one gap of 10 across a fetch and, later, the next query's parse
    spans = [("engine.fetch", 2, 5), ("sql.compile", 7, 12)]
    pieces = cut_at([(0, 10), (20, 30)], [2, 5, 7, 12])
    assert pieces == [(0, 2), (2, 5), (5, 7), (7, 10), (20, 30)]
    labels = innermost_labels([(s + e) / 2 for s, e in pieces], spans)
    assert labels == [NO_SPAN, "engine.fetch", NO_SPAN, "sql.compile", NO_SPAN]
