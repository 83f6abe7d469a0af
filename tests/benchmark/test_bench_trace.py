"""``benchmark/trace.py`` and the trace-fed metric readers on a trace
recorded on the chip (PR 22: ``tpch_serve_sf1.streams2``, seed 77,
``--seconds 0.05 --trace 1`` on one v5e; one Q1 and one Q6 completed)."""

import gzip
import os
import shutil

import pytest

from bench_tiny import REPO

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "serve_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    from benchmark.trace import reduce

    path = tmp_path_factory.mktemp("trace") / "serve.xplane.pb"
    with gzip.open(TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce(str(path))


def test_reduces_to_fixed_numbers(summary):
    assert summary["window_s"] == pytest.approx(0.355061142, abs=1e-12)
    assert summary["busy_s"] == pytest.approx(0.315818431, abs=1e-12)
    assert summary["devices"] == 1
    ops = summary["device_ops"]
    assert len(ops) == 10
    assert ops[0] == ["jit__prog(5932467532365118286)", pytest.approx(0.315263396, abs=1e-12)]
    assert [name for name, _ in ops[:4]] == [
        "jit__prog(5932467532365118286)",
        "jit__prog(11178204380459918319)",
        "jit__filter_prog(17153500206879206655)",
        "jit__filter_prog(10402977748616326695)",
    ]
    # idle is the window less busy, all of it while a client waited on HTTP
    assert summary["idle_gaps"] == [["bench.http", pytest.approx(0.039242711, abs=1e-9)]]


def test_trace_metrics(summary):
    from benchmark import run

    config = run.load_json(REPO, "benchmark", "configs", "tpch_serve_sf1.json")
    ctx = run.RunContext(
        trace=summary,
        records=[{"query": "q1", "ok": True}, {"query": "q6", "ok": True}],
        query_bytes={q: run._query_bytes(config, run.plugin("queries", q)) for q in ("q1", "q6")},
        peaks=run.device_peaks("TPU v5 lite"),
    )
    idle = run.plugin("metrics", "device_idle_pct").read(ctx)
    roof = run.plugin("metrics", "query_roofline_pct").read(ctx)
    assert idle == pytest.approx(11.05238122621709, rel=1e-12)
    assert roof == pytest.approx(0.16705147289498776, rel=1e-12)


def test_union_and_labels():
    from benchmark import trace

    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    spans = sorted([("bench.query.q1", 0, 10), ("bench.http", 1, 9)], key=lambda s: s[1])
    assert trace._label(5, spans) == "bench.http"  # the innermost span
    assert trace._label(9.5, spans) == "bench.query.q1"
    assert trace._label(11, spans) == "(no bench span)"


def test_unknown_device_kind_is_an_error():
    from benchmark import run

    with pytest.raises(LookupError):
        run.device_peaks("TPU v9 imaginary")
    assert os.path.isfile(os.path.join(REPO, "benchmark", "peaks.json"))
