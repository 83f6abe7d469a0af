"""Device group-by: key factorization + segment reductions.

The TPU lowering of SQL GROUP BY (BASELINE: "group-by aggregates lower to
segment_sum/segment_max scans on device"). Two factorization strategies:

- **Static binning (the hot path, zero host syncs):** when every key is
  integer-like with host-known bounds (column ``stats`` captured at ingest
  and propagated through the pipeline), segment ids are a mixed-radix
  combination of ``key - min`` — one fused O(n) pass, no sort, and the
  segment COUNT is the static bin count, so downstream segment ops and
  output shapes need no device readback. Empty bins are dropped lazily via
  an occupancy mask (the frame's ``row_valid``).

- **Sort-based (general fallback):** lexicographic factorization via
  stable sorts for float/wide/unbounded keys. Costs two host syncs (group
  count) — acceptable off the hot path.

Everything computes in int32: int64 is EMULATED on TPU (~10x slower), and
row positions/bin codes fit int32 by construction.
"""

from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from fugue_tpu.column.functions import VARIANCE_FUNCS
from fugue_tpu.jax_backend.blocks import JaxBlocks, JaxColumn
from fugue_tpu.utils.assertion import assert_or_throw


def row_validity(blocks: JaxBlocks) -> jnp.ndarray:
    """True for real rows, False for mesh padding / filtered-out rows."""
    return blocks.validity()


def materialize_validity(
    row_valid: Optional[jnp.ndarray], pad_n: int, nrows_s: Any
) -> jnp.ndarray:
    """Traced helper: the one validity convention, shared by every device
    program — a masked frame passes its mask; a prefix frame materializes
    ``arange < nrows`` in-program (int32: int64 is emulated on TPU)."""
    if row_valid is not None:
        return row_valid
    return jnp.arange(pad_n, dtype=jnp.int32) < nrows_s


class BinSpec(NamedTuple):
    """Static description of a mixed-radix key binning: everything needed
    to compute segment ids INSIDE a traced program (no separate factorize
    dispatch) and to DECODE key values arithmetically from bin indices
    (no representative-row gather, no segment_min scatter)."""

    names: Tuple[str, ...]
    mins: Tuple[int, ...]
    spans: Tuple[int, ...]  # includes the +1 null bucket where masked
    masked: Tuple[bool, ...]
    total: int


def bin_spec(blocks: JaxBlocks, keys: List[str]) -> Optional[BinSpec]:
    """BinSpec for `keys` when all are integer-like with host-known bounds
    (stats from ingest / propagation, else ONE device min/max readback,
    cached on the column); None for float/unbounded keys."""
    missing: List[str] = []
    for k in keys:
        col = blocks.columns.get(k)
        if col is None or not col.on_device:
            return None
        if jnp.issubdtype(col.data.dtype, jnp.floating):
            return None
        if col.stats is None:
            missing.append(k)
    if missing:
        _fill_stats_from_device(blocks, missing)
    spans: List[int] = []
    mins: List[int] = []
    masked: List[bool] = []
    for k in keys:
        col = blocks.columns[k]
        lo, hi = col.stats  # type: ignore[misc]
        span = int(hi) - int(lo) + 1
        if span <= 0 or span > _MAX_BINS:
            return None
        has_mask = col.mask is not None
        if has_mask:
            span += 1  # null bucket
        spans.append(span)
        mins.append(int(lo))
        masked.append(has_mask)
    total = 1
    for r in spans:
        total *= r
        if total > _MAX_BINS:
            return None
    return BinSpec(tuple(keys), tuple(mins), tuple(spans), tuple(masked), total)


@jax.jit
def _minmax_prog(datas: Tuple[jnp.ndarray, ...]) -> Tuple[jnp.ndarray, ...]:
    return tuple(
        jnp.stack([jnp.min(d), jnp.max(d)]).astype(jnp.int64) for d in datas
    )


def _fill_stats_from_device(blocks: JaxBlocks, names: List[str]) -> None:
    """Backfill missing int-key stats with one jitted min/max program and a
    single batched readback, cached on the columns (a one-sync fallback so
    computed keys — e.g. from assign() — still reach the binned fast path
    instead of the ~10x sort factorization)."""
    datas = tuple(blocks.columns[k].data for k in names)
    bounds = jax.device_get(_minmax_prog(datas))
    for k, b in zip(names, bounds):
        blocks.columns[k].stats = (int(b[0]), int(b[1]))


def inline_seg(
    spec: BinSpec,
    key_data: Dict[str, jnp.ndarray],
    key_masks: Dict[str, Optional[jnp.ndarray]],
    valid_rows: jnp.ndarray,
) -> jnp.ndarray:
    """Traced helper: mixed-radix segment ids per row; invalid rows get the
    out-of-range sentinel ``spec.total`` (dropped by one-hot and segment
    ops alike)."""
    n = valid_rows.shape[0]
    combined = jnp.zeros((n,), dtype=jnp.int32)
    for name, kmin, span, has_mask in zip(
        spec.names, spec.mins, spec.spans, spec.masked
    ):
        code = (key_data[name] - kmin).astype(jnp.int32)
        if has_mask:
            code = jnp.where(key_masks[name], code, span - 1)
        combined = combined * jnp.int32(span) + code
    return jnp.where(valid_rows, combined, jnp.int32(spec.total))


def decode_bin_keys(
    spec: BinSpec, dtypes: Dict[str, Any]
) -> Dict[str, Tuple[jnp.ndarray, Optional[jnp.ndarray]]]:
    """Traced helper: key (values, mask) per bin index — pure arithmetic
    over ``arange(total)``, replacing the representative-row gather."""
    b = jnp.arange(spec.total, dtype=jnp.int32)
    out: Dict[str, Tuple[jnp.ndarray, Optional[jnp.ndarray]]] = {}
    stride = spec.total
    for name, kmin, span, has_mask in zip(
        spec.names, spec.mins, spec.spans, spec.masked
    ):
        stride //= span
        code = (b // stride) % span
        if has_mask:
            mask = code != span - 1
            value = jnp.where(mask, code, 0) + kmin
        else:
            mask = None
            value = code + kmin
        out[name] = (value.astype(dtypes[name]), mask)
    return out


# ---------------------------------------------------------------------------
# segment-reduction STRATEGY KERNELS
#
# All sum-type reductions (sum/avg/count payloads for every aggregated
# column) are packed into one multi-row operand so the per-row segment
# work — one-hot materialization, scatter index handling, or the sort —
# is amortized across every output. Four interchangeable strategies
# compute the identical contract; ``choose_strategy`` picks one from the
# shape alone (placement tier, segment count, payload dtype) by the table
# at the end of this block.
#
# - "matmul": chunked one-hot matmul over the MXU. The fastest measured
#   on TPU for f32 up to its segment cap. Benchmarked at 100M rows x 1024
#   segments, f32, honest device_get endpoint (r3):
#     one-hot matmul (this design)            ~204ms  (~490M rows/s)
#     hierarchical (OH_hi*v)^T @ OH_lo split  ~490ms  (2.4x worse: two
#         one-hots materialize; XLA fuses the flat pattern better)
#     sort + segment_sum                      ~3.7s   (18x worse)
#     jax.ops.segment_sum (scatter)           ~10.0s  (50x worse: scatter
#         serializes on TPU; the MXU does not)
#   Chunk-size sweeps (2^16..2^20) move the time <15%, so the cost is
#   the inherent n*num_segments one-hot work, not scan-step overhead — a
#   pallas kernel was evaluated and offers no algorithmic advantage here
#   (VPU compare-accumulate is the same n*S work at lower throughput in
#   f32; in f64 it is the other way round, see "masked").
# - "scatter": ONE packed (rows, n_payload) jax.ops.segment_sum. On CPU
#   meshes (the host placement tier) the one-hot transient is pure
#   memory-bandwidth waste while scatter-adds are cheap — measured
#   10M rows x 256 segments = 1.28s matmul vs 0.048s scatter — so CPU
#   meshes always scatter. Exact integer accumulation (matmul would lose
#   low bits in its float accumulator).
# - "sort": argsort by segment id, then the packed scatter with
#   ``indices_are_sorted=True``, trading the n*S one-hot work for an
#   n*log(n) sort. It lost to scatter at every shape measured below.
# - "masked": one masked select-and-sum per (segment, payload) on the
#   VPU, each payload's bins fused into one pass over its column — no
#   one-hot reaches HBM and no matmul runs. The f64 kernel: the MXU has
#   no float64, so XLA emulates an f64 one-hot dot_general, and the
#   (rows, S) one-hot pads S out to a full lane tile. On one v5e, TPC-H
#   Q1 at SF10 (60M rows, 12 bins, 6 payloads, f64): the whole Q1
#   program 58ms against 3.15s on the f64 matmul. Its work is
#   rows * S * P, so it is capped at _MASKED_MAX_SEGMENTS.
#
# The table, on accelerator meshes. One v5e, 1M rows, 8 payloads of the
# dtype and one count, each kernel compiled, warmed, then best of 2 (ms;
# PERF.md §6 names the run; "int" is the exact-integer case, int64
# payloads):
#   segs  f32: matmul scatter   sort  f64: masked  matmul scatter   sort
#   12           1.53   18.60 223.76          2.58   66.56  105.63 263.65
#   64           1.70   18.70 164.56          4.64   77.11  107.93 265.78
#   65           1.51   18.70 164.35             -   77.27  106.63 264.22
#   1024         3.68   18.69 145.02             -  558.98  108.37 266.16
#   8192         8.87   16.40 145.39             - 4342.39  106.43 266.18
#   8193            -   16.56 145.29             -       -  106.44 264.65
#   int: scatter 90.61 / sort 248.84 at 12, 91.45 / 248.64 at 1024,
#   88.95 / 248.81 at 8193.
# So: f32 matmul up to its cap, then scatter; f64 masked up to its cap,
# the emulated matmul while the segments fit one 128-lane tile (65 bins,
# 1.4x faster than scatter; 1,024 bins, eight tiles, 5.2x slower), then
# scatter; exact ints scatter. An earlier v5e run agrees at 10M rows x
# 1,024 segments, f64: matmul 5.265s, scatter 0.956s, sort 1.398s. No two
# kernels came within 10% of each other.
# ---------------------------------------------------------------------------

STRATEGIES = ("matmul", "scatter", "sort", "masked")

_MATMUL_MAX_SEGMENTS = 8192
# masked work grows as rows * num_segments * n_payload: past a few dozen
# bins one pass per bin loses to a single scatter or sort
_MASKED_MAX_SEGMENTS = 64
# scatter/sort have no one-hot transient: the packed path stays viable up
# to the bin cap itself (output is (num_segments, n_payload))
_PACKED_MAX_SEGMENTS = 1 << 20
_MATMUL_CHUNK = 1 << 17
# cap on chunk*num_segments: the (chunk, num_segments) one-hot is the
# scan-step transient; 2^26 elements = 256MB f32, safe on 16GB parts even
# if XLA fails to fuse it into the matmul (advisor r2)
_MATMUL_ONEHOT_BUDGET = 1 << 26

# The MXU has no float64: XLA emulates an f64 one-hot dot with the segments
# padded to 128-lane tiles, so its cost grows by whole tiles. One tile
# beats the packed scatter, eight lose five-fold (table above)
_MATMUL_F64_MAX_SEGMENTS = 128


def eligible_strategies(
    num_segments: int, need_int: bool = False, count_only: bool = False
) -> Tuple[str, ...]:
    """The kernels that can run one reduction shape. Exact integer sums
    rule out the float-accumulating matmul and masked; matmul is capped by
    its one-hot transient, masked by its rows * segments work, scatter and
    sort by the packed output. ``count_only`` is the single-payload shape
    of join-side counts and the generic aggregate path: matmul where its
    cap allows, else scatter, which has no cap there."""
    out: List[str] = []
    if not need_int and num_segments <= _MATMUL_MAX_SEGMENTS:
        out.append("matmul")
    if count_only:
        return tuple(out) + ("scatter",)
    if not need_int and num_segments <= _MASKED_MAX_SEGMENTS:
        out.append("masked")
    if num_segments <= _PACKED_MAX_SEGMENTS:
        out += ["scatter", "sort"]
    return tuple(out)


def choose_strategy(
    platform: str,
    num_segments: int,
    float_dtype: Any = np.float32,
    need_int: bool = False,
    count_only: bool = False,
) -> Optional[str]:
    """The segment-reduction kernel for one shape on a mesh of ``platform``
    devices, by the table above, or None when no kernel is eligible (the
    caller then takes the generic per-aggregate path). ``float_dtype`` is
    the float payloads' accumulator dtype; ``need_int`` says exact integer
    sums are among the payloads. The sort kernel won no measured shape, so
    the rule never picks it."""
    eligible = eligible_strategies(num_segments, need_int, count_only)
    if platform == "cpu" or need_int:
        order: Tuple[str, ...] = ("scatter",)
    elif np.dtype(float_dtype) != np.float64:
        order = ("matmul", "scatter")
    elif num_segments <= _MATMUL_F64_MAX_SEGMENTS:
        order = ("masked", "matmul", "scatter")
    else:
        order = ("scatter",)
    return next((s for s in order if s in eligible), None)


def segment_sums(
    float_payloads: List[jnp.ndarray],
    count_payloads: List[jnp.ndarray],
    seg: jnp.ndarray,
    num_segments: int,
    strategy: str = "matmul",
    int_payloads: Optional[List[jnp.ndarray]] = None,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray], List[jnp.ndarray]]:
    """Traced helper: every sum-type reduction through ONE strategy kernel.

    ``float_payloads`` accumulate in the widest float dtype present;
    ``count_payloads`` (bool/0-1 valued) accumulate exactly in int32;
    ``int_payloads`` accumulate exactly in int64 (scatter/sort only — the
    float accumulators of matmul and masked would drop low bits).
    ``seg`` values >= num_segments contribute nothing on every strategy.
    Returns (float_sums, count_sums, int_sums) as per-payload lists."""
    ints = int_payloads or []
    assert_or_throw(
        strategy in STRATEGIES,
        ValueError(f"unknown segment-reduction strategy {strategy!r}"),
    )
    if strategy == "masked":
        assert_or_throw(
            len(ints) == 0,
            ValueError("the masked strategy cannot sum integer payloads"),
        )
        f, c = masked_segment_sums(
            float_payloads, count_payloads, seg, num_segments
        )
        return f, c, []
    if strategy == "matmul":
        assert_or_throw(
            len(ints) == 0,
            ValueError("the matmul strategy cannot sum integer payloads"),
        )
        f, c = matmul_segment_sums(
            float_payloads, count_payloads, seg, num_segments
        )
        return f, c, []
    return _packed_scatter_sums(
        float_payloads, count_payloads, ints, seg, num_segments,
        presort=strategy == "sort",
    )


def segment_count(
    vec: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
    strategy: str = "scatter",
) -> jnp.ndarray:
    """Traced helper: ONE 0/1-valued int32 count reduction routed through
    the strategy layer — the join-side/window count shape. ``vec`` must be
    bool or 0/1 (matmul accumulates chunk partials in f32; 0/1 sums below
    the chunk size are exact)."""
    if strategy != "scatter" and num_segments > 0:
        _, c, _ = segment_sums([], [vec], seg, num_segments, strategy)
        return c[0]
    return jax.ops.segment_sum(
        vec.astype(jnp.int32), seg, num_segments=num_segments
    )


def _float_acc_dtype(float_payloads: List[jnp.ndarray]) -> Any:
    """The accumulation dtype the strategy kernels share: the widest float
    dtype present (f64 stays f64 for CPU fidelity; pure-f32 TPU pipelines
    ride the fast path), f32 when there are no float payloads."""
    acc_dtype = (
        jnp.result_type(*[p.dtype for p in float_payloads])
        if len(float_payloads) > 0
        else jnp.float32
    )
    if not jnp.issubdtype(acc_dtype, jnp.floating):
        acc_dtype = jnp.float32
    return acc_dtype


def _packed_scatter_sums(
    float_payloads: List[jnp.ndarray],
    count_payloads: List[jnp.ndarray],
    int_payloads: List[jnp.ndarray],
    seg: jnp.ndarray,
    num_segments: int,
    presort: bool,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray], List[jnp.ndarray]]:
    """The scatter/sort strategies: same-kind payloads packed into one
    (rows, n_payload) operand per accumulator dtype, ONE segment_sum per
    pack (index handling amortized across every output). ``presort``
    reorders rows by segment id first so XLA lowers the scatter with
    ``indices_are_sorted=True``."""
    acc_dtype = _float_acc_dtype(float_payloads)
    if presort:
        order = jnp.argsort(seg).astype(jnp.int32)
        seg = seg[order]

        def _g(p: jnp.ndarray) -> jnp.ndarray:
            return p[order]
    else:

        def _g(p: jnp.ndarray) -> jnp.ndarray:
            return p

    def _reduce(payloads: List[jnp.ndarray], dtype: Any) -> List[jnp.ndarray]:
        if not payloads:
            return []
        pack = jnp.stack([_g(p.astype(dtype)) for p in payloads], axis=1)
        sums = jax.ops.segment_sum(
            pack, seg, num_segments=num_segments, indices_are_sorted=presort
        )
        return [sums[:, i] for i in range(len(payloads))]

    return (
        _reduce(float_payloads, acc_dtype),
        _reduce(count_payloads, jnp.int32),
        _reduce(int_payloads, jnp.int64),
    )


def masked_segment_sums(
    float_payloads: List[jnp.ndarray],
    count_payloads: List[jnp.ndarray],
    seg: jnp.ndarray,
    num_segments: int,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Traced helper: every sum-type reduction as masked sums on the VPU.
    Each payload reduces ``where(seg == s, p, 0)`` over the rows for every
    bin ``s``, as one (num_segments, rows) select-and-sum that XLA fuses
    into its reduction: the bin mask is recomputed in registers, so a
    payload's column is read once for all bins and no one-hot reaches
    HBM. Rows run along the lanes and bins along the sublanes (on the
    TPU, XLA first splits an f64 column into two f32 halves).
    ``float_payloads`` accumulate in the widest float dtype present (f64
    stays f64); ``count_payloads`` (bool/0-1 valued) accumulate exactly
    in int32. ``seg`` values >= num_segments match no bin and contribute
    nothing."""
    acc_dtype = _float_acc_dtype(float_payloads)
    eq = seg[None, :] == jnp.arange(num_segments, dtype=seg.dtype)[:, None]

    def _sum(p: jnp.ndarray, dtype: Any) -> jnp.ndarray:
        # dtype= keeps int32 counts int32 (jnp.sum would widen to int64,
        # which the TPU emulates as a pair of 32-bit words)
        return jnp.sum(
            jnp.where(eq, p.astype(dtype)[None, :], jnp.zeros((), dtype)),
            axis=1,
            dtype=dtype,
        )

    return (
        [_sum(p, acc_dtype) for p in float_payloads],
        [_sum(p, jnp.int32) for p in count_payloads],
    )


def matmul_segment_sums(
    float_payloads: List[jnp.ndarray],
    count_payloads: List[jnp.ndarray],
    seg: jnp.ndarray,
    num_segments: int,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Traced helper: all sum-type reductions in ONE chunked one-hot matmul
    over the MXU. ``float_payloads`` accumulate in f32/f64; ``count_payloads``
    (bool/0-1 valued) accumulate exactly in int32 (f32 partials per chunk
    are exact below the chunk size). ``seg`` values >= num_segments
    contribute nothing (their one-hot row is all zeros)."""
    n = seg.shape[0]
    ch = min(
        _MATMUL_CHUNK,
        max(256, _MATMUL_ONEHOT_BUDGET // max(1, num_segments)),
        n,
    )
    pad = (-n) % ch
    acc_dtype = _float_acc_dtype(float_payloads)
    nf = len(float_payloads)
    payloads = [p.astype(acc_dtype) for p in float_payloads + count_payloads]
    if pad:
        seg = jnp.concatenate(
            [seg, jnp.full((pad,), num_segments, dtype=seg.dtype)]
        )
        payloads = [
            jnp.concatenate([p, jnp.zeros((pad,), dtype=p.dtype)])
            for p in payloads
        ]
    a = len(payloads)
    kc = seg.reshape(-1, ch)
    pc = jnp.stack(payloads, axis=0).reshape(a, -1, ch)
    iota = jnp.arange(num_segments, dtype=seg.dtype)

    def body(carry: Tuple[Any, Any], kv: Any) -> Tuple[Tuple[Any, Any], None]:
        f_acc, c_acc = carry
        kk, vv = kv
        oh = (kk[:, None] == iota[None, :]).astype(acc_dtype)
        part = jax.lax.dot_general(
            vv, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
        )  # (a, num_segments), accumulated in acc_dtype
        f_acc = f_acc + part[:nf]
        c_acc = c_acc + part[nf:].astype(jnp.int32)
        return (f_acc, c_acc), None

    init = (
        jnp.zeros((nf, num_segments), acc_dtype),
        jnp.zeros((a - nf, num_segments), jnp.int32),
    )
    (f_acc, c_acc), _ = jax.lax.scan(
        body, init, (kc, jnp.moveaxis(pc, 0, 1))
    )
    return list(f_acc), list(c_acc)


class Factorized(NamedTuple):
    """Result of key factorization over a frame's padded rows.

    - ``seg``: int32 segment id per padded row; invalid rows carry the
      out-of-range sentinel ``num_segments`` (dropped by segment ops).
    - ``num_segments``: STATIC segment-id space size (bin count on the
      binned path; exact group count on the sort path). Some segments may
      be empty on the binned path.
    - ``first_idx``: representative (first valid) row index per segment,
      shape (num_segments,); garbage where a segment is empty.
    - ``occupied``: bool (num_segments,) marking non-empty segments, or
      None when every segment is occupied (sort path).
    - ``num_groups_dev``: device int32 scalar = true group count (lazy).
    """

    seg: jnp.ndarray
    num_segments: int
    first_idx: jnp.ndarray
    occupied: Optional[jnp.ndarray]
    num_groups_dev: Any


def factorize_keys(blocks: JaxBlocks, keys: List[str]) -> Factorized:
    """Factorize `keys` into segment ids. Null keys form their own groups
    (SQL GROUP BY semantics). Results are cached per frame (repeated ops
    on the same keys — transform then aggregate — pay once)."""
    cache_key = tuple(keys)
    if cache_key in blocks.factorize_cache:
        return blocks.factorize_cache[cache_key]
    res = _try_bin_factorize(blocks, keys)
    if res is None:
        res = _sort_factorize(blocks, keys)
    blocks.factorize_cache[cache_key] = res
    return res


_MAX_BINS = 1 << 22  # static-binning cap (16MB of int32 per scratch array)


def _try_bin_factorize(
    blocks: JaxBlocks, keys: List[str]
) -> Optional[Factorized]:
    """Sort-free, sync-free factorization for integer-like keys with
    host-known bounds."""
    spec = bin_spec(blocks, keys)
    if spec is None:
        return None
    seg, first_idx, occupied, num_dev = _bin_core(
        tuple(blocks.columns[k].data for k in keys),
        tuple(blocks.columns[k].mask for k in keys),
        blocks.row_valid,
        _nrows_scalar_arg(blocks),
        spec,
    )
    return Factorized(seg, spec.total, first_idx, occupied, num_dev)


def _nrows_scalar_arg(blocks: JaxBlocks) -> Any:
    """Known row count as a traced-arg scalar (np, so no eager dispatch);
    -1 when the frame is mask-layout (programs then use row_valid)."""
    if blocks._nrows is not None:
        return np.int32(blocks._nrows)
    return np.int32(-1)


@partial(jax.jit, static_argnames=("spec",))
def _bin_core(
    datas: Tuple[jnp.ndarray, ...],
    masks: Tuple[Optional[jnp.ndarray], ...],
    valid_rows: Optional[jnp.ndarray],
    nrows_s: Any,
    spec: "BinSpec",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    n = datas[0].shape[0]
    total = spec.total
    valid_rows = materialize_validity(valid_rows, n, nrows_s)
    seg = inline_seg(
        spec,
        dict(zip(spec.names, datas)),
        dict(zip(spec.names, masks)),
        valid_rows,
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    # first valid row index per bin (n = "empty bin" sentinel)
    first_pos = jax.ops.segment_min(
        jnp.where(valid_rows, pos, n), seg, num_segments=total
    )
    occupied = first_pos < n
    first_idx = jnp.clip(first_pos, 0, n - 1)
    return seg, first_idx, occupied, occupied.sum().astype(jnp.int32)


def float_sort_codes(v: jnp.ndarray) -> List[jnp.ndarray]:
    """Traced helper: a float key's sort codes. Floats are their OWN sort
    codes: argsort orders them and the equality-based boundary detection
    works once the two identity-hostile values are canonicalized — -0.0
    -> +0.0 (groups with +0.0, host parity) and NaN -> 0.0 with a
    separate isnan flag code (NaN != NaN would otherwise split every NaN
    row into its own group). No bitcast of the float: XLA's TPU x64
    rewriter has refused 64-bit float bitcast-convert operands."""
    isnan = jnp.isnan(v)
    v = jnp.where(v == 0, jnp.zeros_like(v), v)
    v = jnp.where(isnan, jnp.zeros_like(v), v)
    return [isnan.astype(jnp.int32), v]


def _sort_factorize(blocks: JaxBlocks, keys: List[str]) -> Factorized:
    """Lexicographic factorization via repeated stable sorts (general keys:
    floats, wide ints). One host sync for the group count."""
    codes: List[jnp.ndarray] = []
    for k in keys:
        col = blocks.columns[k]
        assert_or_throw(col.on_device, ValueError(f"key {k} not on device"))
        v = col.data
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        if jnp.issubdtype(v.dtype, jnp.floating):
            pair = float_sort_codes(v)
        elif v.dtype in (jnp.int64, jnp.uint64):
            words = jax.lax.bitcast_convert_type(v, jnp.uint32)
            pair = [words[:, 0].astype(jnp.int32),
                    words[:, 1].astype(jnp.int32)]
        else:
            pair = [v.astype(jnp.int32)]
        if col.mask is not None:
            # a separate null-flag key avoids any sentinel collision with
            # legitimate values: (is_null, value...) is the composite key
            codes.append((~col.mask).astype(jnp.int32))
            pair = [jnp.where(col.mask, p, 0) for p in pair]
        codes.extend(pair)
    seg_sorted, order, valid_rows, num_arr = _sort_factorize_core(
        tuple(codes), blocks.row_valid, _nrows_scalar_arg(blocks)
    )
    num = int(num_arr)  # host sync (general path only)
    seg, first_idx = _sort_factorize_finish(
        seg_sorted, order, valid_rows, num
    )
    return Factorized(seg, num, first_idx, None, jnp.int32(num))


@jax.jit
def _sort_factorize_core(
    codes: Tuple[jnp.ndarray, ...],
    valid_in: Optional[jnp.ndarray],
    nrows_s: Any,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    n = codes[0].shape[0]
    valid_rows = materialize_validity(valid_in, n, nrows_s)
    order = jnp.arange(n, dtype=jnp.int32)
    for c in reversed(codes):
        order = order[jnp.argsort(c[order], stable=True)]
    # validity as the final primary key (stable: preserves code order);
    # invalid rows sort last
    order = order[jnp.argsort(~valid_rows[order], stable=True)]
    sorted_valid = valid_rows[order]
    boundary = jnp.zeros((n,), dtype=jnp.bool_)
    for c in codes:
        sc = c[order]
        boundary = boundary | jnp.concatenate(
            [jnp.ones((1,), dtype=jnp.bool_), sc[1:] != sc[:-1]]
        )
    # only valid rows open groups; invalid rows (all trailing) get sentinel
    boundary = boundary & sorted_valid
    seg_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num = jnp.max(jnp.where(sorted_valid, seg_sorted, -1)) + 1
    return seg_sorted, order, valid_rows, num


@partial(jax.jit, static_argnames=("num",))
def _sort_factorize_finish(
    seg_sorted: jnp.ndarray,
    order: jnp.ndarray,
    valid_rows: jnp.ndarray,
    num: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = order.shape[0]
    sorted_valid = valid_rows[order]
    seg_sorted = jnp.where(sorted_valid, seg_sorted, num)
    seg = (
        jnp.zeros((n,), dtype=jnp.int32).at[order].set(seg_sorted)
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    first_pos = jax.ops.segment_min(
        jnp.where(sorted_valid, pos, n), seg_sorted, num_segments=num
    )
    first_idx = order[jnp.clip(first_pos, 0, n - 1)]
    return seg, first_idx


def avg_dtype(dtype: Any) -> Any:
    """Result dtype of AVG over a ``dtype`` column: float32 stays float32;
    integers average in float64, as the host engine does (an int column's
    mean in float32 keeps only ~7 digits)."""
    return jnp.float32 if dtype == jnp.float32 else jnp.float64


def _segment_agg_impl(
    func: str,
    values: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    seg: jnp.ndarray,
    num_segments: int,
    valid_rows: jnp.ndarray,
    strategy: str = "scatter",
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One aggregation as a segment reduction (trace-time building block);
    returns (values[num_segments], mask[num_segments]). Sum-type reductions
    (count/sum/avg) route through the strategy layer; order-based ones
    (min/max/median/...) are scatter-native on every platform."""
    effective = valid_rows if mask is None else (mask & valid_rows)
    f = func.lower()
    if f == "count":
        return segment_count(effective, seg, num_segments, strategy), None
    if f == "sum" or f in ("avg", "mean"):
        filled = jnp.where(effective, values, 0)
        use = strategy
        if not jnp.issubdtype(filled.dtype, jnp.floating):
            if use == "matmul":
                use = "scatter"  # exact int sums can't ride a float acc
            _, cs, is_ = segment_sums(
                [], [effective], seg, num_segments, use,
                int_payloads=[filled],
            )
            total, count = is_[0], cs[0]
        else:
            fs, cs, _ = segment_sums(
                [filled], [effective], seg, num_segments, use
            )
            total, count = fs[0], cs[0]
        if f == "sum":
            return total, count > 0  # all-null group -> NULL (SQL)
        avg = total / jnp.maximum(count, 1)
        return avg.astype(avg_dtype(values.dtype)), count > 0
    # int32 accumulation: int64 is emulated on TPU; counts fit int32 (<2B
    # rows); callers cast the output to the schema type
    count = jax.ops.segment_sum(
        effective.astype(jnp.int32), seg, num_segments=num_segments
    )
    if f == "min":
        big = _type_max(values.dtype)
        filled = jnp.where(effective, values, big)
        res = jax.ops.segment_min(filled, seg, num_segments=num_segments)
        return res, count > 0
    if f == "max":
        small = _type_min(values.dtype)
        filled = jnp.where(effective, values, small)
        res = jax.ops.segment_max(filled, seg, num_segments=num_segments)
        return res, count > 0
    if f in VARIANCE_FUNCS:
        if num_segments == 0:  # empty factorization: no groups at all
            z = jnp.zeros((0,), dtype=jnp.float64)
            return z, jnp.zeros((0,), dtype=jnp.bool_)
        # stable two-pass: mean per segment, then squared deviations.
        # NaN payloads (non-null computed NaNs, e.g. SQRT of a negative)
        # are skipped like pandas std/var skips them (review finding)
        eff = effective
        if jnp.issubdtype(values.dtype, jnp.floating):
            eff = eff & ~jnp.isnan(values)
        vcnt = jax.ops.segment_sum(
            eff.astype(jnp.int32), seg, num_segments=num_segments
        )
        fv = jnp.where(eff, values.astype(jnp.float64), 0.0)
        tot = jax.ops.segment_sum(fv, seg, num_segments=num_segments)
        cnt = vcnt.astype(jnp.float64)
        mean = tot / jnp.maximum(cnt, 1.0)
        segc = jnp.clip(seg, 0, num_segments - 1)
        dev = jnp.where(
            eff, values.astype(jnp.float64) - mean[segc], 0.0
        )
        ss = jax.ops.segment_sum(dev * dev, seg, num_segments=num_segments)
        pop = f in ("stddev_pop", "var_pop")
        denom = jnp.maximum(cnt if pop else cnt - 1.0, 1.0)
        var = ss / denom
        res = jnp.sqrt(var) if f.startswith("stddev") else var
        # sample forms need >= 2 rows (pandas ddof=1 gives NaN on one)
        return res, vcnt > (0 if pop else 1)
    if f == "median":
        if num_segments == 0:  # empty factorization: no groups at all
            z = jnp.zeros((0,), dtype=jnp.float64)
            return z, jnp.zeros((0,), dtype=jnp.bool_)
        # sorted-space selection: stable sort by value, re-sort by
        # segment (stability keeps the value order inside each segment),
        # then pick the middle position(s) per segment
        n = values.shape[0]
        fv = values.astype(jnp.float64)
        eff = effective
        if jnp.issubdtype(values.dtype, jnp.floating):
            eff = eff & ~jnp.isnan(values)
        mcount = jax.ops.segment_sum(
            eff.astype(jnp.int32), seg, num_segments=num_segments
        )
        keyv = jnp.where(eff, fv, jnp.inf)
        order = jnp.argsort(keyv, stable=True)
        segv = jnp.where(eff, seg, num_segments)
        order = order[jnp.argsort(segv[order], stable=True)]
        starts = jnp.cumsum(mcount) - mcount
        sortedv = fv[order]
        lo = starts + (mcount - 1) // 2
        hi = starts + mcount // 2
        med = (
            sortedv[jnp.clip(lo, 0, n - 1)]
            + sortedv[jnp.clip(hi, 0, n - 1)]
        ) * 0.5
        return med, mcount > 0
    if f in ("first", "last"):
        n = values.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        if f == "first":
            pick = jnp.where(valid_rows, idx, n)
            best = jax.ops.segment_min(pick, seg, num_segments=num_segments)
        else:
            pick = jnp.where(valid_rows, idx, -1)
            best = jax.ops.segment_max(pick, seg, num_segments=num_segments)
        best = jnp.clip(best, 0, n - 1)
        out_v = values[best]
        out_m = None if mask is None else mask[best]
        return out_v, out_m
    raise NotImplementedError(f"aggregation {func} on device")


def _type_max(dtype: Any) -> Any:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    if dtype == jnp.bool_:
        return True
    return jnp.iinfo(dtype).max


def _type_min(dtype: Any) -> Any:
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    if dtype == jnp.bool_:
        return False
    return jnp.iinfo(dtype).min
