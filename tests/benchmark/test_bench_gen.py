"""The seeded lineitem generator keeps dbgen's rules (TPC-H v3 4.2.3)."""

import numpy as np
import pandas as pd
import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)
from benchmark.gen import lineitem

ROWS = 30_011
SF = 0.005


@pytest.fixture(scope="module")
def table():
    return lineitem.generate(ROWS, SF, 2**31 + 17)


def test_deterministic_per_seed(table):
    again = lineitem.generate(ROWS, SF, 2**31 + 17)
    other = lineitem.generate(ROWS, SF, 2**31 + 18)
    assert again.equals(table)
    assert not other.equals(table)
    assert other.num_rows == table.num_rows == ROWS  # same shapes for every seed


def test_flag_and_status_rules(table):
    df = table.to_pandas()
    received = df.l_receiptdate <= lineitem.CURRENTDATE
    assert set(df.l_returnflag[received]) == {"R", "A"}
    assert set(df.l_returnflag[~received]) == {"N"}
    shipped = df.l_shipdate > lineitem.CURRENTDATE
    assert (df.l_linestatus[shipped] == "O").all()
    assert (df.l_linestatus[~shipped] == "F").all()


def test_price_formula_and_value_domains(table):
    df = table.to_pandas()
    p = df.l_partkey.to_numpy()
    retail_cents = 90_000 + (p // 10) % 20_001 + 100 * (p % 1_000)
    cents = np.round(df.l_extendedprice.to_numpy() * 100).astype(np.int64)
    assert (cents == df.l_quantity.to_numpy().astype(np.int64) * retail_cents).all()
    assert set(df.l_quantity) <= set(range(1, 51))
    assert set(np.round(df.l_discount * 100)) <= set(range(0, 11))
    assert set(np.round(df.l_tax * 100)) <= set(range(0, 9))
    assert set(df.l_shipinstruct) <= set(lineitem.INSTRUCTIONS)
    assert set(df.l_shipmode) <= set(lineitem.MODES)
    n_supp = int(SF * 10_000)
    assert df.l_suppkey.between(1, n_supp).all()


def test_date_offsets_share_one_orderdate(table):
    df = table.to_pandas()
    assert ((df.l_receiptdate - df.l_shipdate).between(1, 30)).all()
    # every line of an order fits one O_ORDERDATE in [1992-01-01, 1998-08-02]:
    # shipdate - [1, 121] and commitdate - [30, 90]
    g = pd.DataFrame({
        "o": df.l_orderkey,
        "lo": np.maximum(df.l_shipdate - 121, df.l_commitdate - 90),
        "hi": np.minimum(df.l_shipdate - 1, df.l_commitdate - 30),
    }).groupby("o").agg(lo=("lo", "max"), hi=("hi", "min"))
    assert (g.lo <= g.hi).all()
    assert (g.hi >= lineitem.STARTDATE).all() and (g.lo <= lineitem.ENDDATE - 151).all()


def test_orders_and_line_numbers(table):
    df = table.to_pandas()
    sizes = df.groupby("l_orderkey").l_linenumber.agg(["min", "max", "count"])
    assert (sizes["min"] == 1).all() and (sizes["max"] == sizes["count"]).all()
    assert sizes["count"].iloc[:-1].between(1, 7).all()
    assert ((df.l_orderkey - 1) % 32 < 8).all()  # sparse keys: 8 of every 32


def test_comment_is_a_slice_of_the_text_pool(table):
    """dbgen's L_COMMENT: 10 to 43 characters of the seeded word pool,
    near one distinct value a row; the other columns do not depend on it."""
    comments = table.column("l_comment").to_pylist()
    lengths = np.array([len(c) for c in comments])
    assert lengths.min() >= 10 and lengths.max() <= 43
    assert 24 < lengths.mean() < 29  # uniform over [10, 43]: 26.5
    assert len(set(comments)) > 0.95 * len(comments)
    words = set(lineitem.WORDS) | {w.strip() for w in lineitem.WORDS}
    inner = [w for c in comments[:200] for w in c.split(" ")[1:-1] if w]
    assert set(inner) <= words  # whole words between the cut ends
