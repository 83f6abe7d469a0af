import pytest

from fugue_tpu.collections.sql import StructuredRawSQL, TempTableName
from fugue_tpu.collections.yielded import PhysicalYielded, Yielded


def test_structured_raw_sql():
    t1, t2 = TempTableName(), TempTableName()
    raw = f"SELECT * FROM {t1} JOIN {t2} ON a=b"
    s = StructuredRawSQL.from_expr(raw, dialect="spark")
    constructed = s.construct({t1.key: "x", t2.key: "y"})
    assert constructed == "SELECT * FROM x JOIN y ON a=b"
    # identity map
    assert t1.key in s.construct()
    # callable map
    assert "QQ" in s.construct(lambda name: "QQ")


def test_yielded():
    y = PhysicalYielded("id1", "file")
    assert not y.is_set
    with pytest.raises(Exception):
        y.name
    y.set_value("/tmp/x.parquet")
    assert y.is_set and y.name == "/tmp/x.parquet"
    assert y.__uuid__() == "id1"
    with pytest.raises(Exception):
        PhysicalYielded("id2", "bogus")


def test_dataframes():
    from fugue_tpu.dataframe import ArrayDataFrame, DataFrames

    a = ArrayDataFrame([[1]], "a:int")
    b = ArrayDataFrame([[2]], "b:int")
    dfs = DataFrames(a, b)
    assert not dfs.has_dict
    assert dfs[0] is a and dfs[1] is b
    assert list(dfs.keys()) == ["_0", "_1"]
    dfs2 = DataFrames(x=a, y=b)
    assert dfs2.has_dict
    assert dfs2["x"] is a
    with pytest.raises(Exception):
        DataFrames(a, x=b)  # mixing
    with pytest.raises(Exception):
        DataFrames(dict(x=a), b)  # mixing other order
    dfs3 = dfs2.convert(lambda df: df)
    assert list(dfs3.keys()) == ["x", "y"]


def test_dialect_transpile_seam():
    """The cross-dialect hook (reference fugue/collections/sql.py:25 role,
    sqlglot-free): StructuredRawSQL.construct transpiles through the
    ``transpile_sql`` plugin when source and target dialects differ."""
    from fugue_tpu.collections.sql import StructuredRawSQL, transpile_sql

    s = StructuredRawSQL([(False, "SELECT IFF(a, 1, 2) FROM t")],
                         dialect="spark")
    # same dialect (or unset): identity, no transpiler consulted
    assert s.construct(dialect="spark") == "SELECT IFF(a, 1, 2) FROM t"
    assert s.construct() == "SELECT IFF(a, 1, 2) FROM t"

    hits = []

    @transpile_sql.candidate(
        lambda raw, from_dialect, to_dialect: to_dialect == "duckdb"
    )
    def spark_to_duckdb(raw, from_dialect, to_dialect):
        hits.append((from_dialect, to_dialect))
        return raw.replace("IFF(", "IF(")

    assert s.construct(dialect="duckdb") == "SELECT IF(a, 1, 2) FROM t"
    assert hits == [("spark", "duckdb")]


def test_transpile_seam_accepts_real_transpiler():
    # the transpile hook is an identity by default (no sqlglot in this
    # environment) but the SEAM is real: a registered dialect transpiler
    # is invoked by construct() when dialects differ
    from fugue_tpu.collections.sql import StructuredRawSQL, transpile_sql

    def _toy(raw, from_dialect, to_dialect):
        # "backtickdb" quotes identifiers with backticks; "plaindb" strips
        return raw.replace("`", '"')

    transpile_sql.register(
        lambda raw, f, t: f == "backtickdb" and t == "plaindb",
        _toy,
        priority=2.0,
    )
    try:
        s = StructuredRawSQL(
            [(False, "SELECT `a` FROM "), (True, "t")],
            dialect="backtickdb",
        )
        # same dialect: untouched
        assert s.construct({"t": "tbl"}, dialect="backtickdb") == \
            "SELECT `a` FROM tbl"
        # cross-dialect: the registered transpiler runs
        assert s.construct({"t": "tbl"}, dialect="plaindb") == \
            'SELECT "a" FROM tbl'
        # unregistered pair: identity default
        assert s.construct({"t": "tbl"}, dialect="otherdb") == \
            "SELECT `a` FROM tbl"
    finally:
        transpile_sql.unregister(_toy)
