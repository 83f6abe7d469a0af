"""The comparison that decides ``correct``: one answer of the timed path
against the reference's answer for the same query and parameters.

Keys, strings and counts must be equal; each float may differ from the
reference by a relative gap, whose widest value over all answers is the
number held to the query's ``MAX_REL_ERR``.
"""

import math
from typing import Tuple

import numpy as np
import pandas as pd


def compare(got: pd.DataFrame, want: pd.DataFrame) -> Tuple[int, float]:
    """(exact mismatches, widest relative gap of a float) of ``got``
    against ``want``, row by row in the reference's order."""
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return 1, math.inf
    mismatches = 0
    widest = 0.0
    for c in want.columns:
        w = want[c].to_numpy()
        g = got[c].to_numpy()
        if w.dtype.kind == "f":
            try:
                g = g.astype(np.float64)
            except (TypeError, ValueError):
                mismatches += 1
                continue
            w = w.astype(np.float64)
            both_nan = np.isnan(g) & np.isnan(w)
            gap = np.where(both_nan, 0.0, np.abs(g - w))
            scale = np.maximum(np.abs(w), np.finfo(np.float64).tiny)
            rel = gap / scale
            rel = np.where(np.isnan(rel), math.inf, rel)
            widest = max(widest, float(rel.max(initial=0.0)))
        elif [str(x) for x in g.tolist()] != [str(x) for x in w.tolist()]:
            mismatches += 1
    return mismatches, widest
