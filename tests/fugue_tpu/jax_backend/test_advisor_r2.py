"""Regressions for the round-2 advisor findings: the f64 sort-factorize
path must not use 64-bit bitcasts (XLA's TPU x64 rewriter cannot lower
them), the one-hot matmul transient must stay bounded, and empty-input
aggregates must give identical results whether the emptiness is known on
the host or pending on device."""

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.column import col
from fugue_tpu.column import functions as ff
from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.jax_backend import JaxExecutionEngine


def make_engine() -> JaxExecutionEngine:
    return JaxExecutionEngine(dict(test=True))


def test_f64_distinct_and_groupby_no_bitcast():
    # -0.0 groups with +0.0; every NaN lands in one null-style group; no
    # bitcast of 64-bit operands anywhere in the factorization
    e = make_engine()
    pdf = pd.DataFrame(
        {
            "a": [1.5, 1.5, -0.0, 0.0, np.nan, np.nan, 2.5],
            "b": [1, 1, 2, 2, 3, 3, 4],
        }
    )
    jdf = e.to_df(pdf)
    got = sorted(e.distinct(jdf).as_array(), key=str)
    assert got == [[0.0, 2], [1.5, 1], [2.5, 4], [None, 3]], got
    agg = e.aggregate(
        jdf, PartitionSpec(by=["a"]), [ff.sum(col("b")).alias("s")]
    )
    rows = sorted(agg.as_array(), key=str)
    assert rows == [[0.0, 4], [1.5, 2], [2.5, 4], [None, 6]], rows


def test_f64_groupby_two_float_keys():
    e = make_engine()
    pdf = pd.DataFrame(
        {
            "x": [1.25, 1.25, 1.25, 7.5],
            "y": [0.5, 0.5, 2.0, 2.0],
            "v": [1, 2, 4, 8],
        }
    )
    agg = e.aggregate(
        e.to_df(pdf),
        PartitionSpec(by=["x", "y"]),
        [ff.sum(col("v")).alias("s")],
    )
    rows = sorted(agg.as_array())
    assert rows == [[1.25, 0.5, 3], [1.25, 2.0, 4], [7.5, 2.0, 8]], rows


def test_matmul_chunk_bounded_at_segment_cap():
    from fugue_tpu.jax_backend import groupby

    import jax.numpy as jnp

    n = 1 << 18
    num_segments = groupby._MATMUL_MAX_SEGMENTS
    seg = jnp.arange(n, dtype=jnp.int32) % num_segments
    vals = jnp.ones((n,), dtype=jnp.float32)
    f_sums, c_sums = groupby.matmul_segment_sums(
        [vals], [jnp.ones((n,), dtype=jnp.bool_)], seg, num_segments
    )
    assert float(f_sums[0].sum()) == n
    assert int(c_sums[0].sum()) == n


def _agg_rows(e, df, keys):
    spec = PartitionSpec(by=keys) if keys else None
    res = e.aggregate(
        df,
        spec,
        [
            ff.sum(col("v")).alias("s"),
            ff.count(col("v")).alias("c"),
            ff.min(col("v")).alias("mn"),
        ],
    )
    return sorted(res.as_array(), key=str)


@pytest.mark.parametrize("keys", [[], ["k"]])
def test_empty_aggregate_conventions_identical(keys):
    # a known-empty frame and a lazily-emptied (filtered) frame must agree
    e = make_engine()
    pdf = pd.DataFrame({"k": [1, 2, 3], "v": [1.0, 2.0, 3.0]})
    known_empty = e.to_df(pdf.iloc[:0])
    lazy_empty = e.filter(e.to_df(pdf), col("v") > 100.0)
    assert _agg_rows(e, known_empty, keys) == _agg_rows(e, lazy_empty, keys)
