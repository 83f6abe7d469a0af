"""Round-5 device-residency closure: NOT IN
subqueries, uncorrelated scalar subqueries, dynamic (column-valued) LIKE
patterns, and multi-string-column CONCAT all execute in-engine on device
with ``fallbacks == {}`` — the reference bar is all-SQL-in-engine
(``/root/reference/fugue_duckdb/execution_engine.py:37-135``)."""

from typing import Any

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.execution import make_execution_engine
from fugue_tpu.workflow.api import raw_sql


def _both(parts: Any, expect_device: bool = True) -> pd.DataFrame:
    e = make_execution_engine("jax")
    rj = raw_sql(*parts, engine=e, as_fugue=True).as_pandas()
    rn = raw_sql(*parts, engine="native", as_fugue=True).as_pandas()
    assert rj.fillna("<N>").values.tolist() == rn.fillna("<N>").values.tolist(), (
        parts[0], rj, rn,
    )
    if expect_device:
        assert e.fallbacks == {}, (parts[0], e.fallbacks)
    return rj


# ---- NOT IN (SELECT ...) --------------------------------------------------


def test_not_in_basic_on_device():
    a = pd.DataFrame({"k": [1.0, 2.0, 3.0, None], "v": [1.0, 2.0, 3.0, 4.0]})
    b = pd.DataFrame({"x": [2.0, 5.0]})
    r = _both(("SELECT v FROM", a,
               "WHERE k NOT IN (SELECT x FROM", b, ") ORDER BY v"))
    # null operand never passes against a non-empty set
    assert list(r["v"]) == [1.0, 3.0]


def test_not_in_null_on_right_keeps_nothing():
    a = pd.DataFrame({"k": [1.0, 2.0], "v": [1.0, 2.0]})
    b = pd.DataFrame({"x": [2.0, None]})
    r = _both(("SELECT v FROM", a, "WHERE k NOT IN (SELECT x FROM", b, ")"))
    assert len(r) == 0


def test_not_in_empty_right_keeps_everything():
    a = pd.DataFrame({"k": [1.0, None], "v": [1.0, 2.0]})
    b = pd.DataFrame({"x": pd.Series([], dtype=float)})
    r = _both(("SELECT v FROM", a,
               "WHERE k NOT IN (SELECT x FROM", b, ") ORDER BY v"))
    # NOT IN over the empty set is TRUE for every row, null operand too
    assert list(r["v"]) == [1.0, 2.0]


def test_not_in_string_keys_on_device():
    a = pd.DataFrame({"s": ["x", "y", "z", None], "v": [1, 2, 3, 4]})
    b = pd.DataFrame({"t": ["y", "q"]})
    r = _both(("SELECT v FROM", a,
               "WHERE s NOT IN (SELECT t FROM", b, ") ORDER BY v"))
    assert list(r["v"]) == [1, 3]


def test_not_in_with_inner_where():
    rng = np.random.default_rng(9)
    a = pd.DataFrame({"k": rng.integers(0, 10, 80),
                      "v": rng.random(80)})
    b = pd.DataFrame({"k": rng.integers(0, 10, 30),
                      "w": rng.random(30)})
    _both(("SELECT k, v FROM", a,
           "AS t WHERE k NOT IN (SELECT k FROM", b,
           "AS q WHERE w > 0.5) ORDER BY v"))


# ---- scalar subqueries ----------------------------------------------------


def test_scalar_subquery_in_where():
    a = pd.DataFrame({"k": [1, 2, 3, 4], "v": [1.0, 2.0, 3.0, 4.0]})
    b = pd.DataFrame({"x": [2.0, 5.0]})
    r = _both(("SELECT v FROM", a,
               "WHERE v > (SELECT AVG(x) FROM", b, ") ORDER BY v"))
    assert list(r["v"]) == [4.0]


def test_scalar_subquery_as_select_item():
    a = pd.DataFrame({"k": [1, 2]})
    b = pd.DataFrame({"x": [2.0, 5.0]})
    r = _both(("SELECT k, (SELECT MAX(x) FROM", b, ") AS mx FROM", a,
               "ORDER BY k"))
    assert list(r["mx"]) == [5.0, 5.0]


def test_scalar_subquery_empty_is_null():
    a = pd.DataFrame({"v": [1.0, 2.0]})
    b = pd.DataFrame({"x": [1.0]})
    r = _both(("SELECT v, (SELECT MIN(x) FROM", b,
               "WHERE x > 100) AS m FROM", a, "ORDER BY v"))
    assert r["m"].isna().all()


def test_scalar_subquery_in_arithmetic():
    a = pd.DataFrame({"v": [1.0, 10.0]})
    b = pd.DataFrame({"x": [4.0, 6.0]})
    r = _both(("SELECT v + (SELECT SUM(x) FROM", b, ") AS w FROM", a,
               "ORDER BY w"))
    assert list(r["w"]) == [11.0, 20.0]


def test_scalar_subquery_multirow_errors_on_both():
    a = pd.DataFrame({"v": [1.0]})
    b = pd.DataFrame({"x": [1.0, 2.0]})
    for eng in ("jax", "native"):
        with pytest.raises(Exception, match="more than one row"):
            raw_sql("SELECT (SELECT x FROM", b, ") AS m FROM", a,
                    engine=eng, as_fugue=True).as_pandas()


# ---- dynamic LIKE ---------------------------------------------------------


def _like_frame() -> pd.DataFrame:
    rng = np.random.default_rng(11)
    df = pd.DataFrame(
        {
            "s": rng.choice(["apple", "apricot", "fig", "melon"], 64),
            "p": rng.choice(["a%", "%o_", "f__", "%e%"], 64),
            "v": rng.random(64),
        }
    )
    df.loc[::7, "s"] = None
    df.loc[::11, "p"] = None
    return df


def test_dynamic_like_projection_on_device():
    df = _like_frame()
    _both(("SELECT s, p, s LIKE p AS m, s NOT LIKE p AS nm FROM", df))


def test_dynamic_like_filter_on_device():
    df = _like_frame()
    _both(("SELECT v FROM", df, "WHERE s LIKE p ORDER BY v"))


def test_dynamic_like_over_transformed_operand():
    df = _like_frame()
    _both(("SELECT v FROM", df, "WHERE UPPER(s) LIKE UPPER(p) ORDER BY v"))


# ---- multi-column CONCAT --------------------------------------------------


def test_concat_two_columns_on_device():
    df = _like_frame()
    _both(("SELECT CONCAT(s, '-', p) AS c FROM", df))


def test_concat_three_columns_and_transforms():
    df = _like_frame()
    _both(("SELECT CONCAT(UPPER(s), p, TRIM(s)) AS c FROM", df))


def test_concat_null_propagates():
    df = pd.DataFrame({"a": ["x", None], "b": [None, "y"]})
    r = _both(("SELECT CONCAT(a, b) AS c FROM", df))
    assert r["c"].isna().all()


def test_concat_in_group_key():
    df = _like_frame()
    _both(("SELECT CONCAT(s, '|', p) AS g, COUNT(*) AS c FROM", df,
           "GROUP BY CONCAT(s, '|', p) ORDER BY g NULLS LAST"))


def test_scalar_subquery_cte_shadowing_uses_host_scope():
    # a CTE shadows the registered table name: inlining against the BASE
    # table would silently diverge from the host's CTE-scoped value
    # (review finding) — both engines must agree on the CTE value
    a = pd.DataFrame({"v": [1.0, 2.0, 3.0, 10.0]})
    parts = ("WITH a AS (SELECT v FROM", a,
             "WHERE v < 5) SELECT v FROM a WHERE v >"
             " (SELECT AVG(v) FROM a) ORDER BY v")
    # host scope: AVG over the CTE (1,2,3) = 2.0 -> rows 3.0
    # base-table scope would be AVG(1,2,3,10)=4 -> no rows: wrong
    r = _both(parts, expect_device=False)
    assert list(r["v"]) == [3.0]
