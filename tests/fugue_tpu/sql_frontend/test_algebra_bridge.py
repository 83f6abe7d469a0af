"""JaxSQLEngine device routing: simple single-table SELECTs lower into
the column algebra (device projections / segment aggregates), everything
else falls back to the host SELECT runner — results identical to native."""

import numpy as np
import pandas as pd

from fugue_tpu.execution import make_execution_engine
from fugue_tpu.workflow.api import raw_sql


def _df() -> pd.DataFrame:
    rng = np.random.default_rng(1)
    return pd.DataFrame(
        {
            "k": (np.arange(200) % 7).astype(np.int64),
            "v": rng.random(200),
        }
    )


def _canon(df):
    rows = [
        tuple(
            round(v, 9) if isinstance(v, float) else v for v in r
        )
        for r in df.as_array()
    ]
    return sorted(rows, key=str)


def _both(sql_parts):
    e = make_execution_engine("jax")
    jx = raw_sql(*sql_parts, engine=e, as_fugue=True)
    nt = raw_sql(*sql_parts, engine="native", as_fugue=True)
    return e, _canon(jx), _canon(nt)


def test_groupby_routes_to_device():
    df = _df()
    e, jx, nt = _both(
        ("SELECT k, SUM(v) AS s, COUNT(*) AS c, AVG(v) AS m FROM", df,
         "GROUP BY k")
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks


def test_where_projection_on_device():
    df = _df()
    e, jx, nt = _both(
        ("SELECT k, v*2 AS w FROM", df, "WHERE v > 0.25 AND v < 0.75")
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks


def test_global_agg_on_device():
    df = _df()
    e, jx, nt = _both(
        ("SELECT COUNT(*) AS c, MIN(v) AS lo, MAX(v) AS hi FROM", df)
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks


def test_orderby_limit_routes_to_device():
    # round-3 verdict item 3: this shape used to fall back; now the whole
    # groupby+sort+limit pipeline stays on device
    df = _df()
    e, jx, nt = _both(
        ("SELECT k, SUM(v) AS s FROM", df, "GROUP BY k ORDER BY s DESC LIMIT 3")
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks


def test_case_when_routes_to_device():
    # CASE WHEN now lowers through the bridge (was a host fallback
    # before round 4)
    df = _df()
    e, jx, nt = _both(
        ("SELECT k, CASE WHEN v > 0.5 THEN 1 ELSE 0 END AS b FROM", df)
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks


def test_complex_query_falls_back_correctly():
    # round 5: uncorrelated scalar subqueries inline as device-computed
    # literals, so this shape now stays entirely on device; a CORRELATED
    # non-equi subquery remains the host runner's (counted)
    df = _df()
    e, jx, nt = _both(
        ("SELECT k, v FROM", df,
         "WHERE v > (SELECT AVG(v) FROM", df, ")")
    )
    assert jx == nt
    assert e.fallbacks == {}, e.fallbacks
    e2, jx2, nt2 = _both(
        ("SELECT k, v FROM", df,
         "AS t WHERE v > (SELECT AVG(v) FROM", df,
         "AS q WHERE q.k > t.k)")
    )
    assert jx2 == nt2
    assert sum(e2.fallbacks.values()) >= 1  # counted, not silent


def test_inline_scalar_subquery_decline_leaves_ast_untouched():
    # When the inline pass declines (here: run_plan raises),
    # the parsed tree must come out EXACTLY as parsed — no synthetic
    # __scalar__ alias left behind for the host runner to trip on
    import copy

    from fugue_tpu.sql_frontend.algebra_bridge import (
        inline_scalar_subqueries,
    )
    from fugue_tpu.sql_frontend.parser import parse_select

    q = parse_select("SELECT k FROM t WHERE v > (SELECT AVG(v) FROM t)")
    snapshot = copy.deepcopy(q)

    def boom(plan):
        raise RuntimeError("device refused")

    inline_scalar_subqueries(q, {"t": ["k", "v"]}, boom)
    assert q == snapshot, q
