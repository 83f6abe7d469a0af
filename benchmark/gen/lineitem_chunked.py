"""TPC-H ``lineitem`` from a seed, as ``gen/lineitem.py`` makes it, with
every string column cut into arrow chunks whose text stays under 2 GiB.

``gen/lineitem.py`` already cuts ``l_comment`` so (``CHUNK_BYTES``); its
fixed-vocabulary columns come out as one ``string`` array each, whose
int32 offsets overflow once a column's text passes 2 GiB: at SF30
``l_shipinstruct`` holds ~2.16 GB (12 bytes a row on average over
179,998,372 rows), and the table fails arrow's validation. This module
runs that generator's own code in a private copy of the module whose
``_dictionary`` builds such a column in chunks of whole rows, each under
``CHUNK_BYTES`` and at most ``BLOCK_ROWS`` rows, cast from its codes on
threads. ``l_comment``, which draws from a stream of its own, is built on
a thread while the other columns are drawn. Every value is the one
``gen/lineitem.py`` gives for the same rows, scale factor and seed; a
column that fits one chunk is the same array.
"""

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

# rows of one chunk of a fixed-vocabulary column: the chunks of a large
# table are cast on threads (arrow's cast releases the GIL)
BLOCK_ROWS = 1 << 24


def _private_lineitem():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lineitem.py")
    spec = importlib.util.spec_from_file_location("benchmark.gen.lineitem_chunked_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # type: ignore[union-attr]
    return mod


BASE = _private_lineitem()
_one_chunk = BASE._dictionary
_comments = BASE._comments


def _dictionary(codes: np.ndarray, values: list):
    """``values[codes]`` as arrow ``string``: one array while it is at most
    ``BLOCK_ROWS`` rows and its text under ``CHUNK_BYTES``, else chunks of
    whole rows within both, built on threads."""
    width = max(len(v.encode()) for v in values)
    step = min(BLOCK_ROWS, max(1, (BASE.CHUNK_BYTES - 1) // width))
    if len(codes) <= step:
        return _one_chunk(codes, values)
    with ThreadPoolExecutor(BASE.THREADS) as ex:
        chunks = list(ex.map(lambda a: _one_chunk(codes[a : a + step], values),
                             range(0, len(codes), step)))
    return pa.chunked_array(chunks, type=pa.string())


BASE._dictionary = _dictionary


def generate(rows: int, scale_factor: float, seed: int) -> pa.Table:
    """``gen/lineitem.py``'s ``generate``, its string columns in chunks and
    ``l_comment`` built on a thread from its own stream meanwhile."""
    rng = np.random.default_rng([seed % 2**64, BASE.COMMENT_STREAM])
    with ThreadPoolExecutor(1) as ex:
        ahead = ex.submit(_comments, rng, rows)
        BASE._comments = lambda _rng, _rows: ahead.result()
        try:
            return BASE.generate(rows, scale_factor, seed)
        finally:
            BASE._comments = _comments
