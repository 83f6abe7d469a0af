"""TPC-H ``lineitem`` from a seed, by dbgen's value rules.

TPC-H specification v3 clause 4.2.3 (``L_*`` and the ``O_ORDERDATE`` they
derive from), with money as float64 and dates as int32 days since
1970-01-01 (the configuration lists both under ``assumed``). The row
count is fixed by the configuration (dbgen's count at that scale
factor), so every seed gives the same array shapes and the same compiled
programs: the last order is cut short to land on it exactly.

Everything is vectorised numpy; the string flags are built directly as
dictionary arrays and cast to arrow ``string`` (the engine's device
string type), which arrow does in C++. ``l_comment`` is dbgen's
``dbg_text``: a slice of 10 to 43 characters at a random offset of a
300 MiB text pool, built as arrow offsets plus one gather. dbgen writes
its pool from a sentence grammar over its word lists; here the pool's
words are drawn uniformly from those lists (the same alphabet, word
lengths and near-unique slices; the configuration lists it under
``assumed``). The comment draws from a stream of its own, so the other
columns of a seed are what they were without it. Its text is one arrow
chunk while it is under ``CHUNK_BYTES`` (SF10 holds 1.6 GB) and several
chunks beyond, cut at row boundaries.
"""

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np
import pyarrow as pa

STARTDATE = 8035  # 1992-01-01
ENDDATE = 10591  # 1998-12-31
CURRENTDATE = 9298  # 1995-06-17
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
COMMENT_STREAM = 0x6C636D74  # "lcmt": the comment's own random stream
THREADS = 8  # numpy's gathers release the GIL: the comment is built in parallel
# dbgen's text: TEXT_POOL_SIZE, and L_COMMENT's [10, 43] characters
POOL_BYTES = 300 * 1024 * 1024
COMMENT_LEN = (10, 43)
# an arrow ``string`` array's text is under 2 GiB (int32 offsets): a larger
# ``l_comment`` (SF30 holds ~4.8 GB) comes out in chunks under this limit
CHUNK_BYTES = 2**31
# dbgen's word lists (dists.dss: nouns, verbs, adjectives, adverbs,
# prepositions, auxiliaries) and its sentence terminators
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses platelets "
    "asymptotes courts dolphins multipliers sauternes warthogs frets dinos attainments "
    "somas Tiresias patterns forges braids hockey players frays warhorses dugouts "
    "notornis epitaphs pearls tithes waters orbits gifts sheaves depths sentiments "
    "decoys realms pains grouches escapades packages requests accounts deposits "
    "sleep wake are cajole haggle nag use boost affix detect integrate maintain nod "
    "was lose sublate solve thrash promise engage hinder print x-ray breach eat grow "
    "impress mold poach serve run dazzle snooze doze unwind kindle play hang believe "
    "doubt furious sly careful blithe quick fluffy slow quiet ruthless thin close "
    "dogged daring brave stealthy permanent enticing idle busy regular final ironic "
    "even bold silent pending special express unusual fancy sometimes always never "
    "furiously slyly carefully blithely quickly fluffily slowly quietly ruthlessly "
    "thinly closely doggedly daringly bravely stealthily permanently enticingly idly "
    "busily regularly finally ironically evenly boldly silently about above across "
    "after against along among around at atop before behind beneath beside besides "
    "between beyond by despite during except for from inside into near of on outside "
    "over past since through throughout to toward under until up upon without with "
    "within do may might shall will would can could should must"
).split() + [". ", "; ", ": ", "? ", "! ", "-- "]


def _lines_per_order(rng: np.random.Generator, rows: int) -> np.ndarray:
    """1-7 lines per order (uniform), the last order cut so the lines add
    up to ``rows`` exactly."""
    m = rows // 4 + 16 * int(np.sqrt(rows)) + 64
    counts = rng.integers(1, 8, m, dtype=np.int32)
    total = np.cumsum(counts, dtype=np.int64)
    while total[-1] < rows:  # practically never: 16 standard deviations
        more = rng.integers(1, 8, m, dtype=np.int32)
        counts = np.concatenate([counts, more])
        total = np.cumsum(counts, dtype=np.int64)
    last = int(np.searchsorted(total, rows))
    counts = counts[: last + 1].astype(np.int64)
    counts[-1] -= total[last] - rows
    return counts


def _dictionary(codes: np.ndarray, values: list) -> pa.Array:
    """Arrow ``string`` column of ``values[codes]``."""
    if all(len(v) == 1 for v in values):  # one byte a row: build the buffers
        data = np.frombuffer("".join(values).encode(), dtype=np.uint8)[codes]
        offsets = np.arange(len(codes) + 1, dtype=np.int32)
        return pa.Array.from_buffers(
            pa.string(), len(codes), [None, pa.py_buffer(offsets), pa.py_buffer(data)]
        )
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32, copy=False), type=pa.int32()),
        pa.array(values, type=pa.string()),
    ).cast(pa.string())


def _text_pool(rng: np.random.Generator, size: int) -> np.ndarray:
    """``size`` bytes of words drawn from ``WORDS``, one space after each."""
    words = [w if w.endswith(" ") else w + " " for w in WORDS]
    width = max(len(w) for w in words)
    table = np.array([w.encode() for w in words], dtype=f"S{width}")
    mean = sum(len(w) for w in words) / len(words)
    picks = rng.integers(0, len(words), int(size / mean * 1.02) + 4096, dtype=np.int32)

    def text(part: np.ndarray) -> np.ndarray:
        cells = table[part].view(np.uint8).reshape(len(part), width)
        return cells[cells != 0]  # the zero padding of the fixed width goes

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(text, np.array_split(picks, 4 * THREADS)))
    pool = np.concatenate(parts)
    if len(pool) < size:  # 1.02 times the mean is many deviations of room
        raise ValueError("text pool came out short")
    return pool[:size]


def _string_chunks(offsets: np.ndarray, data: np.ndarray) -> pa.ChunkedArray:
    """Arrow ``string`` chunks over ``data`` cut at row ``offsets`` (int64),
    each chunk's text under ``CHUNK_BYTES``: one chunk whenever it all fits."""
    rows = len(offsets) - 1
    chunks = []
    a = 0
    while a < rows:
        # the last row end that keeps this chunk's text under the limit
        b = min(int(np.searchsorted(offsets, offsets[a] + CHUNK_BYTES)) - 1, rows)
        if b <= a:
            raise ValueError(f"row {a} alone exceeds the chunk limit of {CHUNK_BYTES} bytes")
        lo, hi = int(offsets[a]), int(offsets[b])
        chunks.append(pa.Array.from_buffers(
            pa.string(), b - a,
            [None, pa.py_buffer((offsets[a : b + 1] - lo).astype(np.int32)),
             pa.py_buffer(data[lo:hi])],
        ))
        a = b
    return pa.chunked_array(chunks, type=pa.string())


def _comments(rng: np.random.Generator, rows: int) -> pa.ChunkedArray:
    """dbgen's ``dbg_text``: ``pool[start : start + length]`` for a length
    uniform in ``COMMENT_LEN`` and a start uniform over the pool."""
    lo, hi = COMMENT_LEN
    pool = np.concatenate([_text_pool(rng, POOL_BYTES), np.zeros(hi, np.uint8)])
    lengths = rng.integers(lo, hi + 1, rows, dtype=np.int32)
    starts = rng.integers(0, POOL_BYTES - lengths + 1, dtype=np.int64)
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    # row ``s`` of ``windows`` is the ``hi`` bytes of the pool from ``s``
    windows = np.lib.stride_tricks.as_strided(pool, (POOL_BYTES, hi), (1, 1), writeable=False)
    keep = np.arange(hi, dtype=np.int32)
    data = np.empty(int(offsets[-1]), dtype=np.uint8)

    def fill(a: int) -> None:
        b = min(a + 1_000_000, rows)
        block = windows[starts[a:b]]
        data[offsets[a] : offsets[b]] = block[keep < lengths[a:b, None]]

    with ThreadPoolExecutor(THREADS) as ex:
        list(ex.map(fill, range(0, rows, 1_000_000)))
    return _string_chunks(offsets, data)


def _numeric(values: np.ndarray) -> pa.Array:
    # an explicit type makes the conversion zero-copy (no inference pass)
    return pa.array(values, type=pa.from_numpy_dtype(values.dtype))


def generate(rows: int, scale_factor: float, seed: int) -> pa.Table:
    """``rows`` lines of ``lineitem`` at ``scale_factor`` (which sets the
    part and supplier key ranges), reproducible from ``seed``."""
    rng = np.random.default_rng(seed % 2**64)
    counts = _lines_per_order(rng, rows)
    n_orders = len(counts)
    order_idx = np.repeat(np.arange(n_orders, dtype=np.int64), counts)
    # the first line of each order sits at the running total before it
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    linenumber = (np.arange(rows, dtype=np.int64) - starts + 1).astype(np.int32)
    # sparse order keys: the first 8 of every 32 (clause 4.2.3, O_ORDERKEY)
    orderkey = ((order_idx >> 3) << 5) + (order_idx & 7) + 1
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders, dtype=np.int32)[
        order_idx
    ]

    n_parts = max(int(scale_factor * 200_000), 1)
    n_supp = max(int(scale_factor * 10_000), 4)
    partkey = rng.integers(1, n_parts + 1, rows, dtype=np.int64)
    i = rng.integers(0, 4, rows, dtype=np.int32)
    pk = partkey.astype(np.int32)  # int32 arithmetic is twice as fast
    suppkey = ((pk + i * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp + 1).astype(np.int64)
    quantity = rng.integers(1, 51, rows, dtype=np.int32)
    retail_cents = (90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)).astype(np.int64)
    extendedprice = (quantity * retail_cents) / 100.0
    discount = rng.integers(0, 11, rows, dtype=np.int32) / 100.0
    tax = rng.integers(0, 9, rows, dtype=np.int32) / 100.0

    shipdate = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
    commitdate = orderdate + rng.integers(30, 91, rows, dtype=np.int32)
    receiptdate = shipdate + rng.integers(1, 31, rows, dtype=np.int32)
    # "R" or "A" at random once received by CURRENTDATE, else "N"
    ra = rng.integers(0, 2, rows, dtype=np.int32)  # 0 -> A, 2 -> R
    returnflag = np.where(receiptdate <= CURRENTDATE, ra * 2, 1)
    linestatus = (shipdate <= CURRENTDATE).astype(np.int32)  # 0 -> O, 1 -> F
    shipinstruct = rng.integers(0, len(INSTRUCTIONS), rows, dtype=np.int32)
    shipmode = rng.integers(0, len(MODES), rows, dtype=np.int32)

    cols: Dict[str, pa.Array] = {
        "l_orderkey": _numeric(orderkey),
        "l_partkey": _numeric(partkey),
        "l_suppkey": _numeric(suppkey),
        "l_linenumber": _numeric(linenumber),
        "l_quantity": _numeric(quantity.astype(np.float64)),
        "l_extendedprice": _numeric(extendedprice),
        "l_discount": _numeric(discount),
        "l_tax": _numeric(tax),
        "l_returnflag": _dictionary(returnflag, ["A", "N", "R"]),
        "l_linestatus": _dictionary(linestatus, ["O", "F"]),
        "l_shipdate": _numeric(shipdate),
        "l_commitdate": _numeric(commitdate),
        "l_receiptdate": _numeric(receiptdate),
        "l_shipinstruct": _dictionary(shipinstruct, INSTRUCTIONS),
        "l_shipmode": _dictionary(shipmode, MODES),
        "l_comment": _comments(np.random.default_rng([seed % 2**64, COMMENT_STREAM]), rows),
    }
    return pa.table(cols)
