"""SQL-level differential fuzzing: seeded random SELECT statements run
on the jax engine vs the native oracle (the same strategy the op-chain
fuzzer applies to engine primitives — this covers the SQL stack's
compositions: scalar functions, CASE, string predicates, group-bys with
DISTINCT aggregates, HAVING, window frames). Every divergence is a real
bug in one of the two paths."""

from typing import Any, List

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.execution import make_execution_engine
from fugue_tpu.workflow.api import raw_sql


def _frame(rng: np.random.Generator, n: int = 160) -> pd.DataFrame:
    v = np.round(rng.random(n) * 10, 3)
    v[rng.random(n) < 0.12] = np.nan
    # trailing-newline values exercise the LIKE anchor unification
    # (^...$ + str.match would accept "red\n" LIKE 'red')
    s = rng.choice(
        ["red", "green", "blue", "teal ", "red\n"], n
    ).astype(object)
    s[rng.random(n) < 0.1] = None
    p = rng.choice(["r%", "%e%", "b___", "%l", "te%", "red"], n).astype(
        object
    )
    p[rng.random(n) < 0.1] = None
    return pd.DataFrame(
        {
            "k": rng.integers(0, 5, n).astype(np.int64),
            "o": rng.permutation(n).astype(np.int64),  # unique order key
            "v": v,
            "i": rng.integers(-40, 40, n).astype(np.int64),
            "s": s,
            "p": p,  # dynamic LIKE patterns
        }
    )


def _num(rng: np.random.Generator, depth: int = 0) -> str:
    r = rng.random()
    if depth > 2 or r < 0.3:
        return rng.choice(["v", "i", "k", "1", "2.5", "-3"])
    if r < 0.5:
        fn = rng.choice(["ABS", "FLOOR", "CEIL", "SIGN", "ROUND"])
        inner = _num(rng, depth + 1)
        return f"{fn}({inner}, 1)" if fn == "ROUND" else f"{fn}({inner})"
    if r < 0.65:
        op = rng.choice(["+", "-", "*"])
        return f"({_num(rng, depth + 1)} {op} {_num(rng, depth + 1)})"
    if r < 0.8:
        return (
            f"CASE WHEN {_bool(rng, depth + 1)} THEN {_num(rng, depth + 1)}"
            f" ELSE {_num(rng, depth + 1)} END"
        )
    if r < 0.9:
        return f"COALESCE({_num(rng, depth + 1)}, 0)"
    return f"LENGTH({_str(rng, depth + 1)})"


def _str(rng: np.random.Generator, depth: int = 0) -> str:
    r = rng.random()
    if depth > 2 or r < 0.4:
        return "s"
    return rng.choice(
        [
            f"UPPER({_str(rng, depth + 1)})",
            f"TRIM({_str(rng, depth + 1)})",
            f"SUBSTRING({_str(rng, depth + 1)}, 2, 3)",
            f"CONCAT('x_', {_str(rng, depth + 1)})",
            f"CONCAT({_str(rng, depth + 1)}, '-', p)",  # multi-column
            f"REPLACE({_str(rng, depth + 1)}, 'e', 'E')",
        ]
    )


def _bool(rng: np.random.Generator, depth: int = 0) -> str:
    r = rng.random()
    if depth > 2 or r < 0.35:
        op = rng.choice(["<", "<=", ">", ">=", "=", "<>"])
        return f"{_num(rng, depth + 1)} {op} {_num(rng, depth + 1)}"
    if r < 0.5:
        return rng.choice(
            [
                "s = 'red'",
                "s <> 'blue'",
                "s LIKE '%e%'",
                "s LIKE 'red'",  # exact literal: the trailing-\n anchor case
                "s NOT LIKE 'r%'",
                "s LIKE p",  # dynamic (column-valued) pattern
                "s NOT LIKE p",
                "s IN ('red', 'teal ')",
                "s < 'green'",
            ]
        )
    if r < 0.65:
        return f"{rng.choice(['v', 's', 'i'])} IS " + rng.choice(
            ["NULL", "NOT NULL"]
        )
    op = rng.choice(["AND", "OR"])
    return f"({_bool(rng, depth + 1)} {op} {_bool(rng, depth + 1)})"


def _canon(df: pd.DataFrame) -> List[tuple]:
    """Raw rows sorted by their NON-float fields — every generated query
    carries enough integer/string identity to make that sort unique, so
    rows align exactly and floats compare unrounded with tolerance."""
    rows = []
    for r in df.to_dict("records"):
        rows.append(
            tuple(
                None
                if v is None or (isinstance(v, float) and v != v) or pd.isna(v)
                else v
                for v in r.values()
            )
        )
    return sorted(
        rows,
        key=lambda t: [
            "" if isinstance(x, float) else repr(x) for x in t
        ],
    )


def _rows_close(a: tuple, b: tuple) -> bool:
    if len(a) != len(b):
        return False
    for x, y in zip(a, b):
        if isinstance(x, float) and isinstance(y, float):
            if not np.isclose(x, y, rtol=1e-7, atol=1e-9):
                return False
        elif x != y:
            return False
    return True


_ORACLE = make_execution_engine("native")

# corpus-wide device-routing ledger, reported and asserted by
# test_zz_device_routed_fraction (file-order: keep that test LAST)
_COVERAGE = {"total": 0, "device": 0}


def _both(e, parts) -> bool:
    """Run on both engines, compare; returns True when the jax run was
    fallback-free (device-resident) so callers can assert coverage."""
    before = sum(e.fallbacks.values())
    rj = raw_sql(*parts, engine=e, as_fugue=True).as_pandas()
    on_device = sum(e.fallbacks.values()) == before
    rn = raw_sql(*parts, engine=_ORACLE, as_fugue=True).as_pandas()
    ca, cb = _canon(rj), _canon(rn)
    assert len(ca) == len(cb) and all(
        _rows_close(x, y) for x, y in zip(ca, cb)
    ), f"\nSQL: {parts[0]} ... {parts[-1]}\n{rj}\n{rn}"
    _COVERAGE["total"] += 1
    _COVERAGE["device"] += int(on_device)
    return on_device


def test_fuzz_plain_selects():
    rng = np.random.default_rng(101)
    df = _frame(rng)
    e = make_execution_engine("jax")
    on_device = 0
    for _ in range(40):
        items = ["o AS rid", f"{_num(rng)} AS a0", f"{_str(rng)} AS a1"]
        if rng.random() < 0.5:
            items.append(f"{_bool(rng)} AS a2")
        head = "SELECT " + ", ".join(items) + " FROM"
        tail = f"WHERE {_bool(rng)}" if rng.random() < 0.6 else ""
        on_device += _both(e, (head, df, tail))
    # the comparison must not silently degrade to host-vs-host
    assert on_device >= 30, (on_device, e.fallbacks)


def test_fuzz_groupby_aggregates():
    rng = np.random.default_rng(202)
    df = _frame(rng)
    aggs = ["SUM", "AVG", "MIN", "MAX", "COUNT", "STDDEV", "VAR_POP",
            "MEDIAN"]
    e = make_execution_engine("jax")
    on_device = 0
    for _ in range(40):
        key = rng.choice(["k", "s", "TRIM(s)", "k %% 2", "i %% 3"]).replace(
            "%%", "%"
        )
        parts_sel = [f"{key} AS g"]
        for j in range(rng.integers(1, 4)):
            fn = rng.choice(aggs)
            d = "DISTINCT " if rng.random() < 0.3 else ""
            star = fn == "COUNT" and not d and rng.random() < 0.3
            arg = "*" if star else (
                rng.choice(["v", "i"]) if d else _num(rng)
            )
            parts_sel.append(f"{fn}({d}{arg}) AS a{j}")
        head = "SELECT " + ", ".join(parts_sel) + " FROM"
        tail = f"GROUP BY {key}"
        if rng.random() < 0.4:
            tail += f" HAVING COUNT(*) > {rng.integers(1, 20)}"
        on_device += _both(e, (head, df, tail))
    assert on_device >= 30, (on_device, e.fallbacks)


def test_fuzz_window_functions():
    rng = np.random.default_rng(303)
    df = _frame(rng)
    ranks = ["ROW_NUMBER()", "RANK()", "DENSE_RANK()", "NTILE(3)",
             "PERCENT_RANK()", "CUME_DIST()"]
    frames = [
        "",
        " ROWS BETWEEN 2 PRECEDING AND CURRENT ROW",
        " ROWS BETWEEN 1 PRECEDING AND 1 FOLLOWING",
        " ROWS BETWEEN CURRENT ROW AND UNBOUNDED FOLLOWING",
        " ROWS BETWEEN UNBOUNDED PRECEDING AND UNBOUNDED FOLLOWING",
        " GROUPS BETWEEN 1 PRECEDING AND CURRENT ROW",
        " RANGE BETWEEN 20 PRECEDING AND 20 FOLLOWING",
    ]
    e = make_execution_engine("jax")
    on_device = 0
    for _ in range(30):
        over = "PARTITION BY k ORDER BY o" if rng.random() < 0.7 else \
            "ORDER BY o"
        items = ["k", "o"]
        if rng.random() < 0.5:
            items.append(f"{rng.choice(ranks)} OVER ({over}) AS r")
        fn = rng.choice(["SUM", "COUNT", "MIN", "MAX", "AVG"])
        fr = rng.choice(frames)
        items.append(f"{fn}(v) OVER ({over}{fr}) AS w")
        if rng.random() < 0.4:
            off = rng.integers(1, 3)
            items.append(
                f"{rng.choice(['LAG', 'LEAD'])}(v, {off}) OVER ({over})"
                " AS l"
            )
        if rng.random() < 0.3:
            items.append(f"FIRST_VALUE(v) OVER ({over}{fr}) AS fv")
        head = "SELECT " + ", ".join(items) + " FROM"
        on_device += _both(e, (head, df, ""))
    assert on_device >= 22, (on_device, e.fallbacks)


def test_fuzz_subquery_predicates():
    rng = np.random.default_rng(404)
    df = _frame(rng)
    e = make_execution_engine("jax")
    on_device = 0
    for _ in range(15):
        pred = _bool(rng)
        neg = "NOT " if rng.random() < 0.4 else ""
        parts = ("SELECT k, o, v FROM", df,
                 f"AS t2 WHERE k {neg}IN (SELECT k FROM", df,
                 f"AS q WHERE {pred})")
        on_device += _both(e, parts)
    # IN lowers to a device semi join, NOT IN to the 3VL anti variant
    assert on_device >= 14, (on_device, e.fallbacks)


def test_fuzz_scalar_subqueries():
    rng = np.random.default_rng(505)
    df = _frame(rng)
    e = make_execution_engine("jax")
    on_device = 0
    for _ in range(15):
        agg = rng.choice(["AVG", "MIN", "MAX", "SUM", "COUNT"])
        col_ = rng.choice(["v", "i"])
        inner = f"(SELECT {agg}({col_}) FROM"
        if rng.random() < 0.5:
            parts = ("SELECT k, o, v FROM", df,
                     f"AS t2 WHERE v > {inner}", df, "AS q) / 2")
        else:
            parts = (f"SELECT k, o, {inner}", df,
                     "AS q) AS m FROM", df, "AS t2")
        on_device += _both(e, parts)
    # uncorrelated scalar subqueries inline as device-computed literals
    assert on_device >= 14, (on_device, e.fallbacks)


def test_zz_device_routed_fraction():
    """The corpus-wide report: the differential
    fuzzer must KNOW how much of its corpus ran device-resident, not
    just per-test thresholds. Skips when the corpus didn't run in this
    process (-k selection, xdist sharding)."""
    total, dev = _COVERAGE["total"], _COVERAGE["device"]
    if total < 100:
        pytest.skip(f"fuzz corpus not (fully) run in this process: {total}")
    frac = dev / total
    print(f"\ndevice-routed fraction: {dev}/{total} = {frac:.1%}")
    assert frac >= 0.9, (_COVERAGE, frac)
