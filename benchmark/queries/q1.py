"""TPC-H Q1, the pricing summary report (specification v3, clause 2.4.1).

Substitution (clause 2.4.1.3): DELTA is drawn from [60, 120]; the date
``1998-12-01 - DELTA days`` is written as int32 days since 1970-01-01,
the form in which the comparison stays on the device (ROADMAP B2).
"""

TABLE = "lineitem"
# the columns the query must read: the byte count of its roofline share
READS = (
    "l_returnflag",
    "l_linestatus",
    "l_quantity",
    "l_extendedprice",
    "l_discount",
    "l_tax",
    "l_shipdate",
)
DATE_1998_12_01 = 10561
# widest relative gap of any float in an answer against the float64
# reference; set from the readings in PERF.md (Correctness)
MAX_REL_ERR = 1e-9


def draw(rng):
    return {"DELTA": int(rng.integers(60, 121))}


def literals(params):
    return {"SHIPDATE_MAX": DATE_1998_12_01 - params["DELTA"]}
