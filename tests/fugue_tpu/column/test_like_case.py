"""LIKE and CASE WHEN in the column algebra — pandas evaluation and the
device (dictionary-code) lowering must agree with SQL semantics
(reference column algebra: fugue/column/functions.py)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from fugue_tpu.column import col, lit, null
from fugue_tpu.column import functions as ff
from fugue_tpu.column.pandas_eval import eval_expr, like_pattern_to_regex
from fugue_tpu.schema import Schema


def _df() -> pd.DataFrame:
    return pd.DataFrame(
        {
            "s": ["apple", "apricot", "banana", None, "fig"],
            "x": [1, 2, 3, 4, 5],
        }
    )


def test_like_pattern_translation():
    assert like_pattern_to_regex("a%") == "a.*"
    assert like_pattern_to_regex("a_c") == "a.c"
    assert like_pattern_to_regex("10.5%") == "10\\.5.*"


def test_like_eval():
    r = eval_expr(_df(), ff.like(col("s"), "ap%"))
    assert list(r[:3]) == [True, True, False]
    assert pd.isna(r[3])  # NULL LIKE -> NULL
    r = eval_expr(_df(), ff.like(col("s"), "%an%", negated=True))
    assert list(r[:3]) == [True, True, False]
    assert pd.isna(r[3])


def test_like_requires_string_pattern():
    with pytest.raises(Exception):
        ff.like(col("s"), 5)  # type: ignore


def test_case_when_eval():
    e = ff.case_when(col("x") <= 2, lit(10), col("x") <= 4, lit(20), lit(0))
    r = eval_expr(_df(), e)
    assert list(r) == [10, 10, 20, 20, 0]


def test_case_when_first_match_wins():
    e = ff.case_when(col("x") > 0, lit(1), col("x") > 2, lit(2), lit(9))
    assert list(eval_expr(_df(), e)) == [1] * 5


def test_case_when_null_default():
    e = ff.case_when(col("x") < 2, lit(7), null())
    r = eval_expr(_df(), e)
    assert r.iloc[0] == 7
    assert r[1:].isna().all()


def test_case_when_infer_type():
    sch = Schema("s:str,x:long")
    assert ff.case_when(col("x") < 2, lit(7), null()).infer_type(
        sch
    ) == pa.int64()
    assert ff.case_when(
        col("x") < 2, lit(7), lit(1.5)
    ).infer_type(sch) == pa.float64()
    assert ff.like(col("s"), "a%").infer_type(sch) == pa.bool_()


def test_case_when_arity_validation():
    with pytest.raises(Exception):
        ff.case_when(col("x") > 1, lit(1))  # no default


def test_mod_truncated_semantics_column_algebra():
    # SQL MOD follows the dividend's sign: MOD(-7, 3) = -1 (not 2)
    from fugue_tpu.column import function

    df = pd.DataFrame({"x": [-7, 7, -8]})
    r = eval_expr(df, function("mod", col("x"), lit(3)))
    assert list(r) == [-1, 1, -2]
    r = eval_expr(df, function("mod", col("x"), lit(0)))
    assert r.isna().all()  # MOD(x, 0) is NULL, silently


def test_group_key_temp_name_no_clobber():
    # a real input column literally named _gk_0 must survive key
    # materialization for computed GROUP BY keys
    import fugue_tpu.column.functions as fff
    from fugue_tpu.column.pandas_eval import eval_select
    from fugue_tpu.column.sql import SelectColumns

    df = pd.DataFrame({"_gk_0": [10, 20, 30, 40], "x": [1, 1, 2, 2]})
    cols = SelectColumns(
        (col("x") + lit(0)).alias("g"),
        fff.sum(col("_gk_0")).alias("s"),
    )
    out = eval_select(df, cols).sort_values("g").reset_index(drop=True)
    assert list(out["g"]) == [1, 2]
    assert list(out["s"]) == [30, 70]


def test_like_regex_anchors_and_newlines():
    # One anchored helper for every LIKE evaluator.
    # "red\n" must NOT match 'red' ($ would accept the trailing newline),
    # and %/_ must match newlines (SQL semantics), hence DOTALL.
    from fugue_tpu.column.pandas_eval import compile_like_regex

    assert compile_like_regex("red").fullmatch("red\n") is None
    assert compile_like_regex("red").match("red\n") is None  # \Z anchored
    assert compile_like_regex("red").fullmatch("red")
    assert compile_like_regex("r%").fullmatch("red\nx")
    assert compile_like_regex("red_").fullmatch("red\n")


def test_like_trailing_newline_host_vs_device():
    # the exact divergence predicted: select_runner's old
    # ^...$ + str.match accepted "red\n" LIKE 'red'; device LUTs did not
    import numpy as np

    from fugue_tpu.execution import make_execution_engine
    from fugue_tpu.workflow.api import raw_sql

    df = pd.DataFrame(
        {
            "o": np.arange(4),
            "s": ["red", "red\n", "redx", None],
        }
    )
    parts = ("SELECT o, s LIKE 'red' AS m, s LIKE 'r%' AS m2 FROM", df)
    jx = raw_sql(*parts, engine=make_execution_engine("jax"),
                 as_fugue=True).as_pandas().sort_values("o")
    nt = raw_sql(*parts, engine="native",
                 as_fugue=True).as_pandas().sort_values("o")
    assert jx["m"].fillna(-1).tolist() == nt["m"].fillna(-1).tolist()
    assert jx["m2"].fillna(-1).tolist() == nt["m2"].fillna(-1).tolist()
    assert jx["m"].fillna(-1).tolist() == [True, False, False, -1]
    assert jx["m2"].fillna(-1).tolist() == [True, True, True, -1]
