"""The ``engine.ingest`` span on the profiler's clock: one around each
arrow-to-device ingest with its size, a child per string column with its
dictionary's size, and ``devices`` on ``engine.dispatch``; under an obs
trace the string columns' spans stay children of ``engine.ingest`` on
the threads that encode them. Tier-1 compatible; select with ``-m obs``."""

import glob
import os

import jax
import pyarrow as pa
import pytest

pytestmark = pytest.mark.obs


def _record(tmp_path, fn):
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
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    )
    return events


def test_ingest_span_carries_the_table_and_each_dictionary(tmp_path):
    from fugue_tpu import ArrowDataFrame, fugue_sql, make_execution_engine

    table = pa.table({
        "k": pa.array(range(1_000), pa.int64()),
        "s": pa.chunked_array([pa.array(["a", "b", None] * 100), pa.array(["c"] * 700)]),
        "t": pa.array([f"x{i % 7}" for i in range(1_000)]),
    })
    engine = make_execution_engine(
        "jax", {"fugue.jax.placement": "device", "fugue.jax.devices": "0,1,2,3"}
    )
    got = {}

    def ingest_and_query():
        df = engine.persist(engine.to_df(ArrowDataFrame(table)))
        got["n"] = fugue_sql("SELECT COUNT(*) AS n FROM t WHERE k > 10", t=df,
                             engine=engine, as_fugue=True).as_pandas()["n"][0]

    ev = _record(tmp_path, ingest_and_query)
    assert got["n"] == 989
    (ingest,) = ev["engine.ingest"]
    assert ingest[2] == {"rows": 1_000, "columns": 3, "chunks": 4,
                         "bytes": table.nbytes, "devices": 4}
    strings = sorted(ev["engine.ingest.strings"], key=lambda e: e[2]["column"])
    assert [e[2] for e in strings] == [
        {"column": "s", "dictionary_size": 3},
        {"column": "t", "dictionary_size": 7},
    ]
    assert all(ingest[0] <= s <= e <= ingest[1] for s, e, _ in strings)
    assert ev["engine.dispatch"] and all(e[2]["devices"] == 4 for e in ev["engine.dispatch"])
    engine.stop()


def test_threaded_string_encodes_are_children_of_the_ingest_span(monkeypatch):
    """Past the threaded-encode size each string column encodes on a
    thread of its own; under an obs trace its span is still a child of
    ``engine.ingest``."""
    import numpy as np
    from jax.sharding import Mesh

    from fugue_tpu.jax_backend import blocks
    from fugue_tpu.obs.trace import Trace, activate
    from fugue_tpu.schema import Schema

    monkeypatch.setattr(blocks, "_PARALLEL_ENCODE_ROWS", 100)
    table = pa.table({
        "k": pa.array(range(1_000), pa.int64()),
        "s": pa.array([f"v{i % 3}" for i in range(1_000)]),
        "t": pa.array([f"w{i}" for i in range(1_000)]),
    })
    mesh = Mesh(np.array(jax.devices()[:4]), ("p",))
    trace = Trace()
    root = trace.root("test")
    with activate(root):
        got = blocks.from_arrow(table, Schema(table.schema), mesh)
    root.finish()
    assert len(got.columns["t"].host_dict) == 1_000
    (ingest,) = trace.find("engine.ingest")
    strings = sorted(trace.find("engine.ingest.strings"), key=lambda s: s.attrs["column"])
    assert [s.attrs for s in strings] == [
        {"column": "s", "dictionary_size": 3},
        {"column": "t", "dictionary_size": 1_000},
    ]
    assert all(s.parent_id == ingest.span_id and s.end_ns <= ingest.end_ns for s in strings)
    assert trace.complete
