"""TPC-H Q6, the forecasting revenue change query (specification v3,
clause 2.4.6).

Substitution (clause 2.4.6.3): DATE is 1 January of a year in
[1993, 1997], DISCOUNT in [0.02, 0.09], QUANTITY in [24, 25]. Dates are
int32 days since 1970-01-01 (ROADMAP B2). ``DISCOUNT - 0.01`` and
``DISCOUNT + 0.01`` are written as the two-digit decimals they are in
the specification's exact arithmetic: computed in float64 inside the
query, 0.06 + 0.01 would round below the stored 0.07 and drop its rows.
"""

import datetime

TABLE = "lineitem"
READS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
MAX_REL_ERR = 1e-9


def _days(year):
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def draw(rng):
    return {
        "DATE": int(rng.integers(1993, 1998)),
        "DISCOUNT": int(rng.integers(2, 10)),  # hundredths
        "QUANTITY": int(rng.integers(24, 26)),
    }


def literals(params):
    d = params["DISCOUNT"]
    return {
        "SHIPDATE_MIN": _days(params["DATE"]),
        "SHIPDATE_END": _days(params["DATE"] + 1),
        "DISCOUNT_LO": f"0.{d - 1:02d}",
        "DISCOUNT_HI": f"0.{d + 1:02d}",
        "QUANTITY": params["QUANTITY"],
    }
