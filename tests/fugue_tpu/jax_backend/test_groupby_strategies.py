"""The segment-reduction strategy layer: every strategy kernel produces
host-oracle-identical results on the execution-suite group-by shapes
(masked columns, invalid rows with the out-of-range sentinel, DISTINCT
aggregates, int payloads), ``groupby.choose_strategy`` follows its
measured table and eligibility, and the engine exposes per-strategy
counters + XLA cost analysis."""

from typing import Any

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.column import col
from fugue_tpu.column import functions as ff
from fugue_tpu.column.expressions import function
from fugue_tpu.execution.native_execution_engine import NativeExecutionEngine
from fugue_tpu.jax_backend import JaxExecutionEngine, groupby

STRATS = ["matmul", "scatter", "sort", "masked"]


def make_engine(**conf: Any) -> JaxExecutionEngine:
    return JaxExecutionEngine(dict(test=True, **conf))


def _frame(n: int = 4000) -> pd.DataFrame:
    rng = np.random.default_rng(7)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 9, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
            "d": rng.random(n).astype(np.float64) * 10,
            "i": rng.integers(-1000, 1000, n).astype(np.int64),
        }
    )
    pdf.loc[rng.random(n) < 0.1, "k"] = None  # null keys group together
    pdf.loc[rng.random(n) < 0.12, "v"] = None
    pdf.loc[rng.random(n) < 0.1, "i"] = None
    pdf["k"] = pdf["k"].astype("Int64")
    pdf["i"] = pdf["i"].astype("Int64")
    return pdf


_AGGS = [
    ff.sum(col("v")).alias("s"),
    ff.avg(col("v")).alias("m"),
    ff.count(col("v")).alias("c"),
    ff.count(col("k", "*")).alias("cstar"),
    ff.sum(col("i")).alias("si"),
    ff.avg(col("i")).alias("mi"),
]


def _oracle_rows(pdf: pd.DataFrame) -> pd.DataFrame:
    native = NativeExecutionEngine(dict(test=True))
    out = native.aggregate(
        native.to_df(pdf), PartitionSpec(by=["k"]), list(_AGGS)
    ).as_pandas()
    return out.sort_values("k", na_position="last").reset_index(drop=True)


def _assert_matches(out: pd.DataFrame, oracle: pd.DataFrame, rtol: float):
    out = out.sort_values("k", na_position="last").reset_index(drop=True)
    assert len(out) == len(oracle)
    assert out["k"].astype("Float64").fillna(np.inf).tolist() == \
        oracle["k"].astype("Float64").fillna(np.inf).tolist()
    for c in ("c", "cstar", "si"):  # exact columns
        assert out[c].fillna(-1).tolist() == oracle[c].fillna(-1).tolist(), c
    for c in ("s", "m", "mi"):
        a = out[c].astype(float).to_numpy()
        b = oracle[c].astype(float).to_numpy()
        assert np.allclose(a, b, rtol=rtol, atol=1e-3, equal_nan=True), c


@pytest.mark.parametrize("strat", STRATS + ["auto"])
def test_strategy_oracle_identity(strat, force_strategy):
    """Each forced strategy (and the rule's own pick) matches the host
    oracle, including DISTINCT aggregates, masked columns and null keys."""
    pdf = _frame()
    oracle = _oracle_rows(pdf)
    if strat != "auto":
        force_strategy(strat)
    e = make_engine()
    out = e.aggregate(
        e.to_df(pdf), PartitionSpec(by=["k"]), list(_AGGS)
    ).as_pandas()
    assert e.fallbacks == {}, (strat, e.fallbacks)
    _assert_matches(out, oracle, rtol=1e-5)
    assert sum(e.strategy_counts.values()) >= 1, e.strategy_counts


@pytest.mark.parametrize("strat", STRATS)
def test_strategy_oracle_identity_filtered_rows(strat, force_strategy):
    """Invalid rows (masked layout with the out-of-range sentinel) stay
    excluded on every strategy."""
    pdf = _frame()
    native = NativeExecutionEngine(dict(test=True))
    filtered = pdf[pdf["d"] > 3.0]
    oracle = native.aggregate(
        native.to_df(filtered), PartitionSpec(by=["k"]),
        [ff.sum(col("v")).alias("s"), ff.count(col("k", "*")).alias("c")],
    ).as_pandas().sort_values("k", na_position="last").reset_index(drop=True)
    force_strategy(strat)
    e = make_engine()
    jdf = e.filter(e.to_df(pdf), col("d") > 3.0)
    out = e.aggregate(
        jdf, PartitionSpec(by=["k"]),
        [ff.sum(col("v")).alias("s"), ff.count(col("k", "*")).alias("c")],
    ).as_pandas().sort_values("k", na_position="last").reset_index(drop=True)
    assert e.fallbacks == {}, (strat, e.fallbacks)
    assert out["c"].tolist() == oracle["c"].tolist()
    assert np.allclose(
        out["s"].astype(float), oracle["s"].astype(float),
        rtol=1e-5, atol=1e-3, equal_nan=True,
    )
    # pure float sum/count: every strategy is packed-path eligible
    assert e.strategy_counts.get(strat, 0) >= 1, (strat, e.strategy_counts)


@pytest.mark.parametrize("strat", STRATS)
def test_distinct_aggregates_ride_packed_path(strat, force_strategy):
    """DISTINCT count/sum/avg fold their first-occurrence masks into the
    packed payloads and stay oracle-identical on every strategy (the
    native aggregate primitive has no DISTINCT — SQL is the oracle)."""
    from fugue_tpu.workflow.api import raw_sql

    pdf = _frame(1500)
    sql = (
        "SELECT k, COUNT(DISTINCT i) AS cd, SUM(DISTINCT i) AS sd, "
        "AVG(DISTINCT v) AS ad FROM"
    )
    native = NativeExecutionEngine(dict(test=True))
    exp = raw_sql(sql, pdf, "GROUP BY k", engine=native, as_fugue=True) \
        .as_pandas().sort_values("k", na_position="last") \
        .reset_index(drop=True)
    force_strategy(strat)
    e = make_engine()
    got = raw_sql(sql, e.to_df(pdf), "GROUP BY k", engine=e, as_fugue=True) \
        .as_pandas().sort_values("k", na_position="last") \
        .reset_index(drop=True)
    assert got["cd"].tolist() == exp["cd"].tolist()
    assert got["sd"].fillna(-1).tolist() == exp["sd"].fillna(-1).tolist()
    assert np.allclose(
        got["ad"].astype(float), exp["ad"].astype(float),
        rtol=1e-5, atol=1e-3, equal_nan=True,
    )
    if strat in ("scatter", "sort"):
        # int DISTINCT sums are packed-eligible on the exact strategies
        assert e.strategy_counts.get(strat, 0) >= 1, e.strategy_counts


def test_selector_tier_and_size_routing():
    """``groupby.choose_strategy`` routes by tier, shape and eligibility:
    CPU meshes scatter (counts too, at any segment count); accelerators
    follow the measured table; exact-int payloads never get matmul or
    masked; count reductions get matmul or scatter only; past every cap
    the packed path gets no kernel."""
    choose = groupby.choose_strategy
    mm_cap = groupby._MATMUL_MAX_SEGMENTS
    packed_cap = groupby._PACKED_MAX_SEGMENTS
    for dt in (np.float32, np.float64):
        assert choose("cpu", 10, dt) == "scatter"
        assert choose("cpu", 10**6, dt) == "scatter"
        assert choose("tpu", mm_cap + 1, dt) in ("scatter", "sort")
    assert choose("cpu", 10, need_int=True) == "scatter"
    assert choose("cpu", packed_cap + 1, count_only=True) == "scatter"
    for platform in ("cpu", "tpu"):
        assert choose(platform, packed_cap + 1) is None
        assert choose(platform, packed_cap + 1, need_int=True) is None
    for n in (12, 1024, mm_cap, mm_cap + 1, packed_cap):
        assert choose("tpu", n, need_int=True) in ("scatter", "sort")
        for dt in (np.float32, np.float64):
            assert choose("tpu", n, dt, count_only=True) in (
                "matmul", "scatter"
            )
    assert choose("tpu", mm_cap, count_only=True) == "matmul"
    assert choose("tpu", mm_cap + 1, count_only=True) == "scatter"
    # f64 keeps the emulated matmul while the segments fit one lane tile
    tile = groupby._MATMUL_F64_MAX_SEGMENTS
    assert choose("tpu", tile, np.float64) == "matmul"
    assert choose("tpu", tile + 1, np.float64) == "scatter"
    assert choose("tpu", packed_cap + 1, count_only=True) == "scatter"
    # every accelerator follows the one table
    assert choose("gpu", 1024, np.float64) == choose("tpu", 1024, np.float64)

    # on the engine's CPU mesh the packed path runs scatter
    e = make_engine()
    e.aggregate(
        e.to_df(_frame(64)), PartitionSpec(by=["k"]),
        [ff.sum(col("v")).alias("s"), ff.count(col("v")).alias("c")],
    ).as_pandas()
    assert e.strategy_counts == {"scatter": 1}, e.strategy_counts


def _q1_frame(n: int = 5000) -> pd.DataFrame:
    rng = np.random.default_rng(11)
    pdf = pd.DataFrame(
        {
            "flag": rng.choice(["A", "N", "R"], n),
            "status": rng.choice(["F", "O"], n),
            "qty": rng.integers(1, 51, n).astype(np.float64),
            "price": rng.random(n) * 1e5,
            "disc": rng.integers(0, 11, n) / 100.0,
            "ship": rng.integers(0, 100, n).astype(np.int64),
        }
    )
    pdf.loc[rng.random(n) < 0.05, "flag"] = None  # the null bucket
    return pdf


def test_masked_q1_shape_matches_numpy_f64(force_strategy):
    """A TPC-H Q1-shaped group-by on the masked kernel: two string keys
    (one with nulls), f64 sums and averages and COUNT(*), after a filter
    whose dropped rows carry the out-of-range sentinel — checked against
    numpy float64."""
    pdf = _q1_frame()
    force_strategy("masked")
    e = make_engine()
    jdf = e.filter(e.to_df(pdf), col("ship") <= 80)
    out = e.aggregate(
        jdf, PartitionSpec(by=["flag", "status"]),
        [
            ff.sum(col("qty")).alias("sum_qty"),
            ff.sum(col("price") * (1 - col("disc"))).alias("sum_disc"),
            ff.avg(col("price")).alias("avg_price"),
            ff.count(col("*")).alias("n"),
        ],
    ).as_pandas()
    assert e.fallbacks == {}, e.fallbacks
    assert e.strategy_counts.get("masked", 0) == 1, e.strategy_counts
    kept = pdf[pdf["ship"] <= 80].assign(
        flag=lambda d: d["flag"].fillna("~"),
        disc_price=lambda d: d["price"] * (1 - d["disc"]),
    )
    exp = kept.groupby(["flag", "status"]).agg(
        sum_qty=("qty", "sum"), sum_disc=("disc_price", "sum"),
        avg_price=("price", "mean"), n=("qty", "size"),
    ).reset_index().sort_values(["flag", "status"]).reset_index(drop=True)
    got = out.assign(flag=out["flag"].fillna("~")).sort_values(
        ["flag", "status"]
    ).reset_index(drop=True)
    assert got[["flag", "status"]].values.tolist() == \
        exp[["flag", "status"]].values.tolist()
    assert got["n"].tolist() == exp["n"].tolist()
    for c in ("sum_qty", "sum_disc", "avg_price"):
        np.testing.assert_allclose(
            got[c].to_numpy(np.float64), exp[c].to_numpy(np.float64),
            rtol=1e-12,
        )

    # the kernel itself: sentinel bins (>= num_segments) contribute nothing
    rng = np.random.default_rng(5)
    seg = rng.integers(0, 8, 1000).astype(np.int32)  # 6 bins + sentinels
    v = rng.random(1000) * 1e6
    f, c = groupby.masked_segment_sums([v], [np.ones(1000, bool)], seg, 6)
    assert f[0].dtype == np.float64 and c[0].dtype == np.int32
    np.testing.assert_allclose(
        np.asarray(f[0]),
        [v[seg == s].sum() for s in range(6)],
        rtol=1e-12,
    )
    assert np.asarray(c[0]).tolist() == [
        int((seg == s).sum()) for s in range(6)
    ]


def test_selector_masked_for_f64_only():
    """masked is the accelerator's kernel for f64 payloads over few bins;
    f32 keeps another kernel, CPU meshes keep scatter, and past the cap
    f64 takes another kernel. masked stays eligible for any float
    payloads (a test may force it), never for exact ints, count
    reductions or past its cap."""
    cap = groupby._MASKED_MAX_SEGMENTS
    choose = groupby.choose_strategy
    assert choose("tpu", 6, np.float64) == "masked"
    assert choose("tpu", cap, np.float64) == "masked"
    assert choose("tpu", 6, np.float32) != "masked"
    assert choose("tpu", 6) == choose("tpu", 6, np.float32)
    assert choose("cpu", 6, np.float64) == "scatter"
    assert choose("tpu", cap + 1, np.float64) != "masked"
    assert choose("tpu", 6, np.float64, count_only=True) != "masked"
    assert "masked" in groupby.eligible_strategies(6)
    assert "masked" not in groupby.eligible_strategies(6, need_int=True)
    assert "masked" not in groupby.eligible_strategies(6, count_only=True)
    assert "masked" not in groupby.eligible_strategies(cap + 1)


# The v5e table (PERF.md §6, and groupby.py's strategy comment): one chip,
# 1M rows, 8 payloads of the dtype and a count; "int" is the exact-integer
# case. The kernel is the one that measured fastest.
_V5E_TABLE = [
    ("float32", 12, "matmul"),
    ("float32", 64, "matmul"),
    ("float32", 65, "matmul"),
    ("float32", 1024, "matmul"),
    ("float32", 8192, "matmul"),
    ("float32", 8193, "scatter"),
    ("float64", 12, "masked"),
    ("float64", 64, "masked"),
    ("float64", 65, "matmul"),
    ("float64", 1024, "scatter"),
    ("float64", 8192, "scatter"),
    ("float64", 8193, "scatter"),
    ("int", 12, "scatter"),
    ("int", 1024, "scatter"),
    ("int", 8193, "scatter"),
]


@pytest.mark.parametrize("payloads,segments,kernel", _V5E_TABLE)
def test_choose_strategy_table(payloads, segments, kernel):
    need_int = payloads == "int"
    dtype = np.float64 if need_int else np.dtype(payloads)
    assert groupby.choose_strategy("tpu", segments, dtype, need_int) == kernel


@pytest.mark.parametrize("strat", ["matmul", "scatter", "sort"])
def test_join_side_counts_follow_strategy(strat, force_strategy):
    """Join-side count reductions share the strategy layer: results are
    identical to the host under every forced strategy."""
    rng = np.random.default_rng(3)
    left = pd.DataFrame(
        {
            "k": rng.integers(0, 12, 300).astype(np.int64),
            "v": rng.random(300),
        }
    )
    right = pd.DataFrame(
        {"k": np.arange(8, dtype=np.int64), "w": rng.random(8)}
    )
    native = NativeExecutionEngine(dict(test=True))
    force_strategy(strat)
    e = make_engine()
    for how in ("inner", "semi", "left_anti", "left_outer"):
        exp = native.join(
            native.to_df(left), native.to_df(right), how=how
        ).as_pandas()
        got = e.join(e.to_df(left), e.to_df(right), how=how).as_pandas()
        exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
        got = got.sort_values(list(got.columns)).reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_program_cost_analysis_reports_traffic(force_strategy):
    """The engine can AOT-lower the programs it just ran and read XLA's
    own flops/bytes accounting (the roofline's % of peak denominator)."""
    pdf = _frame(2000)
    force_strategy("scatter")
    e = make_engine()
    jdf = e.to_df(pdf)
    e.reset_program_log()
    e.aggregate(
        jdf, PartitionSpec(by=["k"]),
        [ff.sum(col("v")).alias("s"), ff.count(col("v")).alias("c")],
    ).as_pandas()
    ca = e.program_cost_analysis()
    assert ca["bytes_accessed"] > 0
    assert "bagg" in ca["programs"], ca["programs"]


def test_persist_forces_masks_and_row_valid():
    """persist()'s residency fetch covers column masks and row_valid too
    — and the persisted frame stays oracle-identical."""
    from fugue_tpu.jax_backend.blocks import residency_arrays

    pdf = _frame(500)
    e = make_engine()
    jdf = e.filter(e.to_df(pdf), col("d") > 2.0)  # masked layout
    arrs = residency_arrays(jdf.native)
    n_masks = sum(1 for c in jdf.native.columns.values() if c.mask is not None)
    n_data = sum(1 for c in jdf.native.columns.values() if c.on_device)
    assert len(arrs) == n_data + n_masks + 1  # + row_valid
    persisted = e.persist(jdf)
    pd.testing.assert_frame_equal(
        persisted.as_pandas().reset_index(drop=True),
        pdf[pdf["d"] > 2.0].reset_index(drop=True),
        check_dtype=False,
    )
