"""Plain numpy TPC-H Q1 (clause 2.4.1) for the query ``q1_chunked``.

``reference/q1.py``'s arithmetic, value for value: the specification's
predicate applied to the drawn DELTA, and each group summed with numpy's
pairwise sum in ``dtype`` over its rows in table order (float64 is the
reference; float32 is the control that must fail the comparison). It
reads the table's columns chunk by chunk, finds the groups present by a
count instead of a sort, and reads the columns and sums the groups on
threads (numpy releases the GIL in them), so that 180M rows take seconds
where ``reference/q1.py`` takes most of a minute.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd

DATE_1998_12_01 = 10561  # days since 1970-01-01
MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def _codes(table, name):
    enc = table.column(name).dictionary_encode()
    codes = np.concatenate([c.indices.to_numpy() for c in enc.chunks])
    return codes, np.asarray(enc.chunk(0).dictionary.to_pylist(), dtype=object)


def answer(table, params, dtype=np.float64):
    one = dtype(1)
    with ThreadPoolExecutor(8) as ex:
        keep = ex.submit(
            lambda: table.column("l_shipdate").to_numpy() <= DATE_1998_12_01 - params["DELTA"]
        )
        rf = ex.submit(_codes, table, "l_returnflag")
        ls = ex.submit(_codes, table, "l_linestatus")
        money = [ex.submit(lambda c=c: table.column(c).to_numpy().astype(dtype)) for c in MONEY]
        keep = keep.result()
        (rf, rf_dict), (ls, ls_dict) = rf.result(), ls.result()
        qty, price, disc, tax = (m.result() for m in money)
        gid = rf.astype(np.int64) * len(ls_dict) + ls
        counts = np.bincount(gid[keep], minlength=len(rf_dict) * len(ls_dict))

        def group(g):
            a, b = divmod(int(g), len(ls_dict))
            m = keep & (gid == g)
            q, p, d, t = qty[m], price[m], disc[m], tax[m]
            n = int(m.sum())
            disc_price = p * (one - d)
            return {
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

        rows = list(ex.map(group, np.flatnonzero(counts)))
    out = pd.DataFrame(rows)
    return out.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)
