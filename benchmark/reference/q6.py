"""Plain numpy TPC-H Q6 (clause 2.4.6) over the generated ``lineitem``.

Applies the specification's predicate to the drawn DATE, DISCOUNT and
QUANTITY itself, with the discount bounds as exact two-digit decimals,
and sums with numpy's pairwise sum in ``dtype``.
"""

import datetime

import numpy as np
import pandas as pd


def _days(year):
    return (datetime.date(year, 1, 1) - datetime.date(1970, 1, 1)).days


def answer(table, params, dtype=np.float64):
    ship = table.column("l_shipdate").to_numpy()
    disc = table.column("l_discount").to_numpy().astype(dtype)
    qty = table.column("l_quantity").to_numpy().astype(dtype)
    price = table.column("l_extendedprice").to_numpy().astype(dtype)
    lo = dtype((params["DISCOUNT"] - 1) / 100)
    hi = dtype((params["DISCOUNT"] + 1) / 100)
    keep = (
        (ship >= _days(params["DATE"]))
        & (ship < _days(params["DATE"] + 1))
        & (disc >= lo)
        & (disc <= hi)
        & (qty < dtype(params["QUANTITY"]))
    )
    revenue = np.sum(price[keep] * disc[keep], dtype=dtype)
    return pd.DataFrame({"revenue": [revenue]})
