"""Plain numpy TPC-H Q1 (clause 2.4.1) over the generated ``lineitem``.

Independent of the program: it reads the arrow table that the generator
made, applies the specification's predicate to the drawn DELTA itself,
and sums each group with numpy's pairwise sum in ``dtype`` (float64 is
the reference; float32 is the control that must fail the comparison).
"""

import numpy as np
import pandas as pd

DATE_1998_12_01 = 10561  # days since 1970-01-01


def _codes(table, name):
    enc = table.column(name).combine_chunks().dictionary_encode()
    return enc.indices.to_numpy(), np.asarray(enc.dictionary.to_pylist(), dtype=object)


def answer(table, params, dtype=np.float64):
    keep = table.column("l_shipdate").to_numpy() <= DATE_1998_12_01 - params["DELTA"]
    rf, rf_dict = _codes(table, "l_returnflag")
    ls, ls_dict = _codes(table, "l_linestatus")
    qty = table.column("l_quantity").to_numpy().astype(dtype)
    price = table.column("l_extendedprice").to_numpy().astype(dtype)
    disc = table.column("l_discount").to_numpy().astype(dtype)
    tax = table.column("l_tax").to_numpy().astype(dtype)
    one = dtype(1)
    gid = rf.astype(np.int64) * len(ls_dict) + ls
    present = np.unique(gid[keep])
    rows = []
    for g in present:
        a, b = divmod(int(g), len(ls_dict))
        m = keep & (gid == g)
        q, p, d, t = qty[m], price[m], disc[m], tax[m]
        n = int(m.sum())
        disc_price = p * (one - d)
        rows.append(
            {
                "l_returnflag": rf_dict[a],
                "l_linestatus": ls_dict[b],
                "sum_qty": np.sum(q, dtype=dtype),
                "sum_base_price": np.sum(p, dtype=dtype),
                "sum_disc_price": np.sum(disc_price, dtype=dtype),
                "sum_charge": np.sum(disc_price * (one + t), dtype=dtype),
                "avg_qty": np.sum(q, dtype=dtype) / dtype(n),
                "avg_price": np.sum(p, dtype=dtype) / dtype(n),
                "avg_disc": np.sum(d, dtype=dtype) / dtype(n),
                "count_order": n,
            }
        )
    out = pd.DataFrame(rows)
    return out.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)
