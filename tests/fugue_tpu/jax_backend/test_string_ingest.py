"""String ingest chunk by chunk into an Arrow-backed host dictionary:
several chunks encode to the same codes, dictionary, nulls and stats as
one; Python strings are built only for a column a query decodes or
compares as text; results and string predicates are unchanged; and TPC-H
Q1 runs on a four-device mesh with the ``masked`` segment reduction."""

import os
import sys

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from fugue_tpu import ArrowDataFrame, fugue_sql, make_execution_engine
from fugue_tpu.jax_backend import blocks as B
from fugue_tpu.jax_backend.expr_eval import dict_fingerprint, dicts_of
from fugue_tpu.schema import Schema

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ROWS = 20_011


def _values(kind: str, n: int = ROWS) -> list:
    rng = np.random.default_rng(3)
    if kind == "few":
        pool = ["DELIVER IN PERSON", "NONE", "", "b", "a b", "é"]
        out = [pool[i] for i in rng.integers(0, len(pool), n)]
    else:  # near-unique, 1 to 30 characters
        out = [f"v{rng.integers(0, 10**9)}{'y' * (i % 22)}" for i in range(n)]
    for i in rng.integers(0, n, n // 50):
        out[i] = None
    return out


def _chunked(values: list, step: int) -> pa.ChunkedArray:
    """The same column as arrow ``string`` chunks of ``step`` rows, built
    directly (not by cutting a 2 GiB array)."""
    return pa.chunked_array(
        [pa.array(values[a : a + step], pa.string()) for a in range(0, len(values), step)],
        type=pa.string(),
    )


def _mesh(ndev: int):
    return B.make_mesh(jax.devices()[:ndev])


def _ingest(arr: pa.ChunkedArray, ndev: int):
    table = pa.table({"s": arr})
    return B.from_arrow(table, Schema(table.schema), _mesh(ndev)).columns["s"]


def _decoded(col, n: int) -> list:
    codes = np.asarray(col.data)[:n]
    valid = np.asarray(col.mask)[:n]
    d = col.host_dict.arrow.to_pylist()
    return [d[c] if v else None for c, v in zip(codes, valid)]


@pytest.fixture(params=["serial", "parallel"])
def encode_path(request, monkeypatch):
    """Encode below the parallel threshold, or force the threaded paths
    (row blocks for few values, partitions by content for many)."""
    if request.param == "parallel":
        monkeypatch.setattr(B, "_PARALLEL_ENCODE_ROWS", 1_000)
        monkeypatch.setattr(B, "_ENCODE_SAMPLE_ROWS", 4_096)
        monkeypatch.setattr(B, "_ENCODE_THREADS", 5)
    return request.param


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("kind", ["few", "many"])
def test_chunks_ingest_like_one_chunk(encode_path, ndev, kind):
    values = _values(kind)
    one = _ingest(pa.chunked_array([pa.array(values, pa.string())]), ndev)
    many = _ingest(_chunked(values, 1_733), ndev)
    for col in (one, many):
        assert _decoded(col, ROWS) == values
        assert col.stats == (0, len(col.host_dict) - 1)
        assert not np.asarray(col.mask)[ROWS:].any()  # padding rows invalid
        assert (np.asarray(col.data)[~np.asarray(col.mask)] == 0).all()
    np.testing.assert_array_equal(np.asarray(one.data), np.asarray(many.data))
    np.testing.assert_array_equal(np.asarray(one.mask), np.asarray(many.mask))
    assert one.host_dict.arrow.to_pylist() == many.host_dict.arrow.to_pylist()
    distinct = {v for v in values if v is not None}
    assert sorted(one.host_dict.arrow.to_pylist()) == sorted(distinct)
    if kind == "few" or encode_path == "serial":
        # first-occurrence order, as one arrow dictionary_encode gives it
        want = pa.array(values, pa.string()).dictionary_encode()
        assert one.host_dict.arrow.to_pylist() == want.dictionary.to_pylist()


def test_several_chunks_encode_as_large_string():
    col = _ingest(_chunked(_values("many"), 5_000), 1)
    assert col.host_dict.arrow.type == pa.large_string()
    assert col.pa_type == pa.string()


def test_empty_and_all_null_string_columns():
    for arr in (pa.chunked_array([], pa.string()), pa.chunked_array([pa.array([None, None], pa.string())])):
        col = _ingest(arr, 4)
        assert len(col.host_dict) == 0 and col.stats == (0, 0)
        assert not np.asarray(col.mask).any()


def test_host_dictionary_builds_strings_once_and_shares_them():
    hd = B.HostDictionary(pa.array(["x", "y", "zz"], pa.large_string()))
    col = B.JaxColumn(pa.string(), None, None, hd)
    assert col.host_dict._values is None  # nothing built yet
    derived = col.with_data(None, None)
    assert derived.host_dict is hd
    first = col.dictionary
    assert first.dtype == object and list(first) == ["x", "y", "zz"]
    assert derived.dictionary is first  # built once, shared
    assert len(hd) == 3 and col.is_string


def test_fingerprint_is_content_not_layout():
    a = B.HostDictionary(pa.array(["ab", "c", ""], pa.string()))
    b = B.HostDictionary(pa.array(["x", "ab", "c", ""], pa.large_string()).slice(1))
    c = B.HostDictionary(pa.array(["a", "bc", ""], pa.string()))
    assert a.fingerprint == b.fingerprint != c.fingerprint
    n = B.HostDictionary(np.array(["ab", "c", ""], dtype=object))
    assert n.fingerprint == B.HostDictionary(np.array(["ab", "c", ""], dtype=object)).fingerprint
    assert n.fingerprint != B.HostDictionary(np.array(["ab", "c", "d"], dtype=object)).fingerprint


@pytest.fixture
def strict_dictionaries(monkeypatch):
    """Building any decode table of more than ``limit`` values raises."""
    real = B.HostDictionary.values
    built = []

    def values(self):
        built.append(len(self))
        if len(self) > strict_dictionaries.limit:
            raise AssertionError(f"built a Python dictionary of {len(self)} strings")
        return real.fget(self)

    strict_dictionaries.limit = 100
    monkeypatch.setattr(B.HostDictionary, "values", property(values))
    return built


def _lineitem(rows: int, chunk_bytes: int):
    from benchmark.gen import lineitem

    saved = lineitem.CHUNK_BYTES
    lineitem.CHUNK_BYTES = chunk_bytes
    try:
        return lineitem.generate(rows, rows / 6_000_000, 5)
    finally:
        lineitem.CHUNK_BYTES = saved


def _q1(table, engine, delta: int = 90):
    from benchmark.queries import q1

    with open(os.path.join(REPO, "benchmark", "queries", "q1.sql")) as f:
        text = f.read().format(**q1.literals({"DELTA": delta}))
    df = engine.persist(engine.to_df(ArrowDataFrame(table)))
    return df, fugue_sql(text, lineitem=df, engine=engine, as_fugue=True).as_pandas()


def test_q1_builds_no_python_dictionary_of_an_unread_column(strict_dictionaries):
    from benchmark.compare import compare
    from benchmark.reference import q1 as ref

    table = _lineitem(24_000, 100_000)
    assert table.column("l_comment").num_chunks > 4
    engine = make_execution_engine(
        "jax", {"fugue.jax.placement": "device", "fugue.jax.devices": "0,1,2,3"}
    )
    for _ in range(2):
        df, got = _q1(table, engine)
    assert compare(got, ref.answer(table, {"DELTA": 90})) == (0, pytest.approx(0, abs=1e-12))
    assert engine.fallbacks == {}
    comment = df.native.columns["l_comment"]
    assert comment.host_dict._values is None
    assert len(comment.host_dict) > 20_000
    assert strict_dictionaries and max(strict_dictionaries) <= 3  # the two flags only
    engine.stop()


@pytest.mark.parametrize("ndev", [1, 4])
def test_q1_masked_on_the_mesh_matches_the_reference(ndev, force_strategy):
    from benchmark.compare import compare
    from benchmark.queries import q1
    from benchmark.reference import q1 as ref

    table = _lineitem(48_000, 200_000)
    devices = ",".join(str(i) for i in range(ndev))
    force_strategy("masked")
    engine = make_execution_engine(
        "jax",
        {
            "fugue.jax.placement": "device",
            "fugue.jax.devices": devices,
        },
    )
    for delta in (60, 120):
        df, got = _q1(table, engine, delta)
        mism, rel = compare(got, ref.answer(table, {"DELTA": delta}))
        assert mism == 0 and rel <= q1.MAX_REL_ERR
    assert int(df.native.mesh.devices.size) == ndev
    assert engine.strategy_counts.get("masked", 0) >= 2
    assert engine.fallbacks == {}
    engine.stop()


_PREDICATES = [
    "SELECT k, s FROM t WHERE s = 'NONE'",
    "SELECT k, s FROM t WHERE s <> 'NONE'",
    "SELECT k, s FROM t WHERE s IN ('a b', 'é', 'missing')",
    "SELECT k, s FROM t WHERE s LIKE '%IN%'",
    "SELECT k, s FROM t WHERE s LIKE 'a_b'",
    "SELECT k, s FROM t WHERE s > 'a'",
    "SELECT k, UPPER(s) AS u FROM t",
    "SELECT s, COUNT(*) AS n FROM t GROUP BY s",
]


@pytest.mark.parametrize("sql", _PREDICATES)
def test_results_and_predicates_match_the_native_engine(sql, encode_path):
    values = _values("few", 9_001)
    table = pa.table({"k": pa.array(np.arange(9_001)), "s": _chunked(values, 1_000)})
    engine = make_execution_engine("jax", {"fugue.jax.devices": "0,1,2,3"})
    df = engine.persist(engine.to_df(ArrowDataFrame(table)))
    assert isinstance(df.native.columns["s"].host_dict._arrow, pa.Array)
    got = fugue_sql(sql, t=df, engine=engine, as_fugue=True).as_pandas()
    want = fugue_sql(sql, t=table.to_pandas(), engine="native", as_fugue=True).as_pandas()
    cols = list(want.columns)
    assert list(got.columns) == cols
    key = [c for c in cols]
    got = got.astype(object).where(pd.notna(got), None).sort_values(key, key=lambda s: s.astype(str))
    want = want.astype(object).where(pd.notna(want), None).sort_values(key, key=lambda s: s.astype(str))
    assert got.values.tolist() == want.values.tolist()
    assert engine.fallbacks == {}
    engine.stop()


def test_dictionaries_are_built_only_when_read():
    values = _values("few", 500)
    table = pa.table({"a": pa.array(values, pa.string()), "b": pa.array(values, pa.string())})
    blocks = B.from_arrow(table, Schema(table.schema), _mesh(1))
    d = dicts_of(blocks)
    assert sorted(d) == ["a", "b"] and len(d) == 2
    fp = dict_fingerprint(blocks)
    assert [name for name, _, _ in fp] == ["a", "b"]
    assert blocks.columns["a"].host_dict._values is None
    assert list(d["a"]) == blocks.columns["a"].host_dict.arrow.to_pylist()
    assert blocks.columns["b"].host_dict._values is None
    assert dict_fingerprint(blocks) == fp
