"""TPC-H Q1 (clause 2.4.1) over the table ``lineitem_chunked``
(``gen/lineitem_chunked.py``): ``queries/q1.py``'s parameters, literals,
columns and limit, under that table's name."""

import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "q1.py")
_spec = importlib.util.spec_from_file_location("benchmark.queries.q1_chunked_base", _path)
_q1 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_q1)  # type: ignore[union-attr]

TABLE = "lineitem_chunked"
READS = _q1.READS
MAX_REL_ERR = _q1.MAX_REL_ERR
draw = _q1.draw
literals = _q1.literals
