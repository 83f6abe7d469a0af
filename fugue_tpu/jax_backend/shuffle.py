"""All-to-all shuffle repartition: co-locate matching keys per device.

The row-sharded layout (blocks.py) places rows on devices by POSITION,
not by key: rows of one group-by segment (or one join key) are spread
over every shard, so a naive sharded segment reduction makes XLA insert
a full cross-device combine of the (num_segments,) partials — or worse,
gather the rows. This module is the classic distributed-relational
answer (Spark's exchange, the repartition before every hash join):
shuffle rows so that segment ``g`` lands wholly on device ``g % ndev``,
then reduce LOCALLY with zero cross-device traffic in the reduction
itself.

Mechanics (everything is shape-stable so the one-trace invariant and
the zero-recompile counters survive):

- Each device routes its ``L`` local rows by ``dest = seg % ndev``
  (invalid rows get a sentinel and travel nowhere), packs them into a
  padded ``(ndev, L)`` send buffer — per-device send COUNTS are data,
  the buffer shape is not — and exchanges buffers with one
  ``jax.lax.all_to_all`` over the ``"p"`` mesh axis inside
  ``shard_map``.
- Received chunks concatenate in SOURCE-device order and each source
  packs its rows in original order (stable sort by destination), so
  within any segment the shuffled row order equals the global row
  order — order-sensitive aggregates (first/last) stay exact.
- The local reduction runs on local segment ids ``seg // ndev`` over
  ``S_local = ceil(S / ndev)`` local segments; the per-device outputs
  concatenate to a ``(ndev * S_local,)`` array whose position
  ``d * S_local + l`` holds global segment ``l * ndev + d``. A STATIC
  permutation gather restores canonical segment order, so results are
  byte-identical to the unshuffled path.
- Collective/compute overlap: with ``overlap`` the segment space is
  split into key-range chunks; the trace issues chunk ``i+1``'s
  all-to-all before chunk ``i``'s reduction so XLA's latency-hiding
  scheduler runs the next shuffle behind the current reduction on
  accelerators with async collectives. Chunks own disjoint segment
  ranges, so merging is a static range select — no arithmetic combine,
  no accuracy terms.

The price of shape stability is a padded receive: every device
receives ``ndev`` chunks of ``L`` rows, an ``ndev``-fold row blowup
carried only through the (streaming, mask-aware) local reduction.
That is the standard padded-all-to-all tradeoff; the decision of WHEN
it pays lives in :func:`choose_shuffle` (the devices-aware strategy
column).

For COMBINABLE aggregates (count/sum/avg/min/max/first/last) the row
shuffle is overkill: :func:`preagg_segment_aggs` is the map-side
combine (Spark's partial aggregation before the exchange): each device
reduces its OWN rows into per-segment partials, one all-to-all
exchanges partials in reduce-scatter layout (device ``d`` receives
every source's partials for segment range ``[d*S_local, (d+1)*S_local)``),
and a tiny ``(ndev, S_local)`` combine finishes each segment. Traffic
is ``O(S * ndev)`` values instead of ``O(rows * ndev)`` — the asymptotic
win whenever ``S << rows``, which is the common group-by shape. Only
non-combinable aggregates (median, variance family) and true
materializing repartitions (:func:`shuffle_rows`) need the row path.
"""

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fugue_tpu.jax_backend import groupby

__all__ = [
    "PREAGG_FUNCS",
    "choose_overlap",
    "choose_shuffle",
    "estimate_preagg_bytes",
    "estimate_shuffle_bytes",
    "grouped_sort",
    "local_segments",
    "preagg_ok",
    "preagg_segment_aggs",
    "preagg_segment_count",
    "sharded_cumsum",
    "sharded_expand_rows",
    "sharded_grouped_order",
    "shuffle_rows",
    "shuffled_segment_aggs",
    "shuffled_segment_count",
    "shuffle_mode",
]

# below this many padded rows the all-to-all shuffle's ndev-fold padded
# receive costs more than the cross-device combine it removes
_SHUFFLE_MIN_ROWS = 1 << 15


def shuffle_mode(conf_value: Any, conf_key: str) -> str:
    """Normalize a shuffle conf value to ``auto`` / ``on`` / ``off``.
    Shared by ``fugue.jax.shuffle`` and ``fugue.jax.shuffle.overlap``;
    a misspelled opt-out must not silently keep shuffling."""
    v = conf_value
    if isinstance(v, bool):
        return "on" if v else "off"
    if v is None:
        return "auto"
    s = str(v).strip().lower()
    if s in ("true", "1", "always", "on"):
        return "on"
    if s in ("false", "0", "never", "off"):
        return "off"
    if s == "auto":
        return "auto"
    raise ValueError(
        f"{conf_key}={conf_value!r} is not one of auto/on/off"
    )


def choose_shuffle(
    mode: str, mesh: Any, rows: int, num_segments: int
) -> bool:
    """The devices-aware strategy column: should this segment reduction
    repartition rows by key (the all-to-all shuffle) so each device
    reduces only its own segments?

    Single-device meshes never shuffle (there is nothing to co-locate).
    ``on`` forces it on any multi-device mesh; ``auto`` additionally
    requires the frame to be large enough to amortize the padded
    receive and enough segments that every device owns some."""
    ndev = int(mesh.devices.size)
    if mode == "off" or ndev <= 1 or num_segments < 1:
        return False
    if mode == "on":
        return True
    return rows >= _SHUFFLE_MIN_ROWS and num_segments >= 2 * ndev


def choose_overlap(mode: str, mesh: Any, num_segments: int) -> bool:
    """Collective/compute overlap: double-buffer the next key-range's
    all-to-all behind the current range's local reduction. Worth it
    only where collectives are asynchronous (accelerator meshes — CPU
    runs them inline, so the second pass is pure overhead) and when the
    segment space splits into two non-trivial ranges."""
    ndev = int(mesh.devices.size)
    if mode == "off" or ndev <= 1 or num_segments < 2 * ndev:
        return False
    if mode == "on":
        return True
    return mesh.devices.flat[0].platform != "cpu"


def sharded_cumsum(mesh: Optional[Mesh], x: Any) -> Any:
    """Prefix sum that stays fast on a sharded axis (trace-time building
    block). GSPMD's partitioning of ``jnp.cumsum`` over a sharded array
    degenerates into a serialized cross-device scan (measured: 800k i32
    rows, 2 forced host devices — 149 s vs 8 ms unsharded), which made
    every multi-device join pay for its start-offset scans. The classic
    two-level scan fixes it: each device cumsums its OWN chunk, one
    all-gather of the ``ndev`` chunk totals computes each device's
    exclusive offset, one streaming add applies it. On one device this
    is exactly ``jnp.cumsum``."""
    ndev = 1 if mesh is None else int(mesh.devices.size)
    if ndev <= 1:
        return jnp.cumsum(x)
    n = x.shape[0]
    pad = (-n) % ndev
    xp = jnp.pad(x, (0, pad)) if pad else x

    def _body(xl: Any) -> Any:
        local = jnp.cumsum(xl)
        totals = jax.lax.all_gather(local[-1], "p")  # (ndev,)
        k = jax.lax.axis_index("p")
        offset = jnp.sum(
            jnp.where(jnp.arange(ndev) < k, totals, 0),
            dtype=local.dtype,
        )
        return local + offset

    out = shard_map(
        _body, mesh=mesh, in_specs=P("p"), out_specs=P("p"),
        check_vma=False,
    )(xp)
    return out[:n] if pad else out


def _scatter_max_exchange(ndev: int, out_n: int, idx: Any, vals: Any) -> Any:
    """Shared kernel of the sharded scatter patterns below (call INSIDE a
    ``shard_map`` body): every device scatter-maxes its LOCAL
    ``(idx, vals)`` pairs into a full-size ``(out_n,)`` buffer (init
    ``-1``), then ONE all-to-all in reduce-scatter layout hands device
    ``d`` every source's partials for output chunk ``d`` and a streaming
    max combines them. Total scatter work stays O(n) across the mesh —
    GSPMD's own partitioning of the same scatter all-reduces ndev
    full-output partial copies instead (measured ndev-fold cost). Returns
    this device's combined ``(out_n // ndev,)`` chunk; slots no index
    hit hold ``-1``."""
    buf = jnp.full((out_n,), -1, jnp.int32).at[idx].max(vals, mode="drop")
    part = buf.reshape(ndev, out_n // ndev)
    ex = jax.lax.all_to_all(part, "p", split_axis=0, concat_axis=0)
    return jnp.max(ex, axis=0)


def sharded_expand_rows(mesh: Mesh, start: Any, out_n: int) -> Any:
    """Expansion row indices ``i[t] = index of the last start <= t`` for
    a SORTED (nondecreasing) ``start`` — the multi-device form of the
    scatter-marks + prefix-sum expansion (relational.expand_join). The
    single-device scatter+scan beats binary search there, but its GSPMD
    partitioning scatters into per-device copies of the FULL output and
    all-reduces them (ndev-fold work), and a per-chunk replicated
    scatter of ALL starts is O(p1 * ndev). Sharded: each device
    scatter-maxes only its OWN rows' ids at their start offsets, a
    reduce-scatter-layout all-to-all combines the partials per output
    chunk, and a local running max plus a scalar carry (all-gather of
    chunk maxima) finishes the prefix — ``cummax`` of scattered row ids
    IS ``cumsum(marks) - 1`` when starts are sorted (each row
    contributes exactly one mark, so the count of starts <= t minus one
    equals the largest row id with start <= t)."""
    ndev = int(mesh.devices.size)
    p1 = start.shape[0]
    pad = (-p1) % ndev
    st = start.astype(jnp.int32)
    if pad:
        # synthetic rows scatter at out_n -> dropped, never selected
        st = jnp.pad(st, (0, pad), constant_values=out_n)
    l1 = (p1 + pad) // ndev

    def _body(st_l: Any) -> Any:
        k = jax.lax.axis_index("p")
        ids = k.astype(jnp.int32) * l1 + jnp.arange(l1, dtype=jnp.int32)
        mine = _scatter_max_exchange(ndev, out_n, st_l, ids)
        run = jax.lax.cummax(mine)
        top = jax.lax.all_gather(run[-1], "p")  # (ndev,) chunk maxima
        carry = jnp.max(jnp.where(jnp.arange(ndev) < k, top, -1))
        return jnp.maximum(run, carry)

    body = shard_map(
        _body, mesh=mesh, in_specs=(P("p"),), out_specs=P("p"),
        check_vma=False,
    )
    return body(st)


def grouped_sort(seg: Any, s_hi: int, length: int) -> Tuple[Any, Any]:
    """Stable sort-by-segment as ONE value sort of a fused
    ``segment * length + row`` composite key — XLA CPU's value sort is
    ~5x the speed of the pair sort behind stable ``argsort`` (measured
    2.5ms vs 15.6ms at 50k rows), and the composite is stable by
    construction. ``seg`` values must lie in ``[0, s_hi]``. Returns
    ``(order, seg_sorted)``. Falls back to stable argsort when the
    composite cannot fit the widest available integer (x64 disabled and
    ``(s_hi + 1) * length`` past int32)."""
    if jax.config.jax_enable_x64:
        dt = jnp.int64
    elif (int(s_hi) + 1) * int(length) <= np.iinfo(np.int32).max:
        dt = jnp.int32
    else:  # pragma: no cover - engine always enables x64 (blocks.py)
        order = jnp.argsort(seg, stable=True).astype(jnp.int32)
        return order, seg[order]
    keys = seg.astype(dt) * length + jnp.arange(length, dtype=dt)
    ks = jnp.sort(keys)
    return (ks % length).astype(jnp.int32), (ks // length).astype(jnp.int32)


def sharded_grouped_order(
    mesh: Mesh, seg: Any, num_segments: int
) -> Tuple[Any, Any, Any]:
    """Fused grouped-by-segment metadata for ONE sharded segment vector:
    returns ``(counts, cstart, order)`` where ``counts[s]`` is the global
    row count of segment ``s``, ``cstart`` its exclusive prefix sum, and
    ``order[p]`` the row index at grouped output position ``p`` (segment
    ``s`` occupies positions ``cstart[s]..``; rows within a segment keep
    global row order) — the sharded replacement for ``segment_count`` +
    ``cumsum`` + ``argsort(seg, stable)``. GSPMD partitions that argsort
    by replicating the FULL sort onto every device (measured ~linear
    slowdown in device count); here each device stable-sorts only its
    LOCAL rows, and ONE all-gather of per-device partial segment counts
    feeds all three outputs (the count/cumsum/order pipeline would
    otherwise exchange the same partials three times: the map-side
    combine, the two-level scan, and the rank bases). The reduce-scatter
    max-combine (:func:`_scatter_max_exchange`) delivers the inverse
    permutation directly — no replicated work, no GSPMD scatter
    all-reduce. Positions of rows with ``seg >= num_segments`` are never
    emitted; uncovered output slots hold ``-1`` (callers mask those
    rows, and XLA's OOB gather clamp keeps the index harmless)."""
    ndev = int(mesh.devices.size)
    n = seg.shape[0]
    s_cap = max(int(num_segments), 1)
    L = n // ndev

    def _body(seg_: Any) -> Tuple[Any, Any, Any]:
        valid = seg_ < s_cap
        segc = jnp.where(valid, seg_, s_cap).astype(jnp.int32)
        order_l, s_sorted = grouped_sort(segc, s_cap, L)
        # rank within segment run: distance to the run's first slot (a
        # streaming cummax; binary search here costs log(L) gather
        # passes)
        t = jnp.arange(L, dtype=jnp.int32)
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), s_sorted[1:] != s_sorted[:-1]]
        )
        rank_sorted = t - jax.lax.cummax(jnp.where(is_start, t, 0))
        cnt = jax.ops.segment_sum(
            valid.astype(jnp.int32), segc, num_segments=s_cap + 1
        )[:s_cap]
        counts = jax.lax.all_gather(cnt, "p")  # (ndev, s_cap)
        c = jnp.sum(counts, axis=0)
        cstart_ = jnp.cumsum(c) - c
        k = jax.lax.axis_index("p")
        base = jnp.sum(
            jnp.where((jnp.arange(ndev) < k)[:, None], counts, 0), axis=0
        )
        sg = jnp.clip(s_sorted, 0, s_cap - 1)
        # global grouped position per SORTED slot (no inverse scatter
        # back to row order: the row ids travel with the sorted slots)
        posg = jnp.where(
            s_sorted < s_cap, cstart_[sg] + base[sg] + rank_sorted, n
        )
        rows_g = k.astype(jnp.int32) * L + order_l
        return c, cstart_, _scatter_max_exchange(ndev, n, posg, rows_g)

    body = shard_map(
        _body, mesh=mesh, in_specs=(P("p"),),
        out_specs=(P(), P(), P("p")), check_vma=False,
    )
    return body(seg.astype(jnp.int32))

#: Aggregates with an exact distributive/algebraic decomposition: a
#: per-device partial plus a tiny cross-device combine reproduces the
#: global result. median needs co-located raw values and the variance
#: family's two-pass form needs the global mean, so they stay on the
#: row shuffle.
PREAGG_FUNCS = frozenset(
    {"count", "sum", "avg", "mean", "min", "max", "first", "last"}
)


def preagg_ok(funcs: List[str]) -> bool:
    """True when EVERY aggregate in the plan set can ride the map-side
    combine (partial aggregation) path."""
    return all(f.lower() in PREAGG_FUNCS for f in funcs)


def local_segments(num_segments: int, ndev: int) -> int:
    """``S_local``: local segments per device after repartition."""
    return max(1, -(-max(num_segments, 1) // ndev))


def _canon_perm(num_segments: int, ndev: int) -> np.ndarray:
    """Static gather restoring canonical segment order: local output
    position ``d * S_local + l`` holds global segment ``l * ndev + d``,
    so ``canon[g] = (g % ndev) * S_local + g // ndev``."""
    s_local = local_segments(num_segments, ndev)
    g = np.arange(max(num_segments, 1), dtype=np.int32)
    return (g % ndev) * s_local + g // ndev


def estimate_shuffle_bytes(pad_n: int, ndev: int, payload_widths: int) -> int:
    """Static transported-byte estimate for the metrics surface: every
    device ships a full padded ``(ndev, L)`` buffer per transported
    array (seg codes: 4B, receive marker: 1B, plus the payload widths).
    ``payload_widths`` is the per-row byte sum of value/mask arrays."""
    return int(pad_n) * int(ndev) * (5 + int(payload_widths))


def estimate_preagg_bytes(
    num_segments: int, ndev: int, partial_widths: int
) -> int:
    """Static transported-byte estimate for the map-side-combine path:
    every device ships its full padded ``(ndev, S_local)`` partial table
    per partial array; ``partial_widths`` is the per-segment byte sum of
    the partial arrays (value + nonempty marker per aggregate)."""
    s_pad = local_segments(num_segments, ndev) * ndev
    return int(s_pad) * int(ndev) * int(partial_widths)


def _send(buf_rows: int, slot: Any, ok: Any, arr: Any) -> Any:
    """Scatter ``arr`` (already dest-sorted) into a flat send buffer of
    ``buf_rows`` slots; rows not being sent target an out-of-bounds slot
    and are dropped."""
    idx = jnp.where(ok, slot, buf_rows)
    return (
        jnp.zeros((buf_rows,), arr.dtype).at[idx].set(arr, mode="drop")
    )


def _exchange(ndev: int, buf: Any) -> Any:
    """One padded all-to-all: ``(ndev * L,)`` send buffer -> ``(ndev * L,)``
    receive buffer whose chunk ``i`` came from source device ``i``."""
    rows = buf.shape[0] // ndev
    out = jax.lax.all_to_all(
        buf.reshape(ndev, rows), "p", split_axis=0, concat_axis=0,
        tiled=False,
    )
    return out.reshape(-1)


def _shuffle_local(
    ndev: int,
    seg: Any,
    route: Any,
    payloads: List[Optional[Any]],
) -> Tuple[Any, Any, List[Optional[Any]]]:
    """Per-shard body: route local rows (``route`` True = participate)
    to device ``seg % ndev``. Returns (seg_sh, received_marker,
    payloads_sh), each ``(ndev * L,)``; ``received_marker`` is True on
    slots that carry a real row."""
    L = seg.shape[0]
    dest = jnp.where(route, seg % ndev, ndev).astype(jnp.int32)
    # stable: within one destination chunk rows keep original order
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)
    d_sorted = dest[order]
    pos = jnp.arange(L, dtype=jnp.int32) - jnp.searchsorted(
        d_sorted, d_sorted, side="left"
    ).astype(jnp.int32)
    slot = jnp.clip(d_sorted, 0, ndev - 1) * L + pos
    ok = d_sorted < ndev
    buf_rows = ndev * L
    seg_sh = _exchange(ndev, _send(buf_rows, slot, ok, seg[order]))
    marker = _exchange(
        ndev,
        _send(
            buf_rows, slot, ok, jnp.ones((L,), jnp.uint8)[order]
        ),
    ).astype(jnp.bool_)
    outs: List[Optional[Any]] = []
    for p in payloads:
        if p is None:
            outs.append(None)
            continue
        v = p[order]
        if v.dtype == jnp.bool_:
            v = _exchange(
                ndev, _send(buf_rows, slot, ok, v.astype(jnp.uint8))
            ).astype(jnp.bool_)
        else:
            v = _exchange(ndev, _send(buf_rows, slot, ok, v))
        outs.append(v)
    return seg_sh, marker, outs


def shuffled_segment_aggs(
    mesh: Mesh,
    funcs: List[str],
    seg: Any,
    valid: Any,
    values: List[Optional[Any]],
    masks: List[Optional[Any]],
    num_segments: int,
    strategy: str = "scatter",
    overlap: bool = False,
) -> List[Tuple[Any, Optional[Any]]]:
    """Shuffle-repartitioned segment aggregation (trace-time building
    block; call INSIDE a jitted program whose row arrays are sharded on
    ``mesh``).

    For each ``funcs[i]`` computes the same result as
    ``groupby._segment_agg_impl(funcs[i], values[i], masks[i], seg,
    num_segments, valid, strategy)`` but with rows repartitioned so each
    device reduces only its own segments. ``values[i]`` may be None for
    ``count`` (nothing but the segment codes travels). Returns
    ``(value, mask)`` pairs of shape ``(num_segments,)`` in canonical
    segment order — byte-identical to the unshuffled path."""
    ndev = int(mesh.devices.size)
    S = max(int(num_segments), 1)
    s_local = local_segments(S, ndev)
    n_chunks = 2 if (overlap and S >= 2 * ndev) else 1
    # chunk boundaries on GLOBAL segment ids, aligned to ndev so each
    # chunk's local segment range is contiguous: seg g is in chunk
    # (g // ndev) >= split_local
    split_local = s_local // 2 if n_chunks == 2 else s_local
    n_payload = len(funcs)

    def _body(seg_: Any, valid_: Any, vals_: Any, masks_: Any) -> Any:
        chunk_outs: List[List[Tuple[Any, Optional[Any]]]] = []
        shuffled: List[Tuple[Any, Any, List[Optional[Any]]]] = []
        # issue EVERY chunk's all-to-all before the first reduction:
        # chunk i+1's shuffle is independent of chunk i's reduce, so
        # the latency-hiding scheduler overlaps them on hardware with
        # async collectives
        for c in range(n_chunks):
            if n_chunks == 1:
                route = valid_
            else:
                lseg = seg_ // ndev
                in_range = (
                    (lseg < split_local) if c == 0 else (lseg >= split_local)
                )
                route = valid_ & in_range
            payloads: List[Optional[Any]] = []
            for i in range(n_payload):
                payloads.append(vals_.get(i))
                payloads.append(masks_.get(i))
            shuffled.append(_shuffle_local(ndev, seg_, route, payloads))
        for c in range(n_chunks):
            seg_sh, marker, payloads_sh = shuffled[c]
            seg_loc = jnp.where(
                marker, seg_sh // ndev, s_local
            ).astype(jnp.int32)
            outs: List[Tuple[Any, Optional[Any]]] = []
            for i, func in enumerate(funcs):
                v_sh = payloads_sh[2 * i]
                m_sh = payloads_sh[2 * i + 1]
                if v_sh is None:  # count: only the marker matters
                    v_sh = jnp.zeros(marker.shape, jnp.int32)
                outs.append(
                    groupby._segment_agg_impl(
                        func, v_sh, m_sh, seg_loc, s_local, marker,
                        strategy=strategy,
                    )
                )
            chunk_outs.append(outs)
        if n_chunks == 1:
            merged = chunk_outs[0]
        else:
            # chunks own DISJOINT local segment ranges: merge is a
            # static range select, exact for every aggregate kind
            lidx = jnp.arange(s_local, dtype=jnp.int32)
            take1 = lidx >= split_local
            merged = []
            for (v0, m0), (v1, m1) in zip(chunk_outs[0], chunk_outs[1]):
                v = jnp.where(take1, v1, v0)
                if m0 is None and m1 is None:
                    m = None
                else:
                    z = jnp.zeros((s_local,), jnp.bool_)
                    m = jnp.where(
                        take1, z if m1 is None else m1,
                        z if m0 is None else m0,
                    )
                merged.append((v, m))
        flat: List[Any] = []
        for v, m in merged:
            flat.append(v)
            flat.append(jnp.zeros((0,), jnp.bool_) if m is None else m)
        return tuple(flat)

    vals_in = {i: v for i, v in enumerate(values) if v is not None}
    masks_in = {i: m for i, m in enumerate(masks) if m is not None}
    has_mask = [
        masks[i] is not None or funcs[i].lower() in ("first", "last")
        for i in range(n_payload)
    ]
    # first/last return a gathered mask only when the input had one;
    # every other func returns a validity mask. Compute the exact
    # out-mask presence the unshuffled path would produce:
    out_has_mask = []
    for i, func in enumerate(funcs):
        f = func.lower()
        if f == "count":
            out_has_mask.append(False)
        elif f in ("first", "last"):
            out_has_mask.append(masks[i] is not None)
        else:
            out_has_mask.append(True)
    body = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("p"), P("p"), P("p"), P("p")),
        out_specs=P("p"),
        check_vma=False,
    )
    flat = body(seg.astype(jnp.int32), valid, vals_in, masks_in)
    canon = jnp.asarray(_canon_perm(num_segments, ndev))
    results: List[Tuple[Any, Optional[Any]]] = []
    for i in range(n_payload):
        v_g = flat[2 * i][canon]
        m_flat = flat[2 * i + 1]
        m_g = m_flat[canon] if out_has_mask[i] else None
        results.append((v_g, m_g))
    return results


def shuffled_segment_count(
    mesh: Mesh,
    vec: Any,
    seg: Any,
    num_segments: int,
    strategy: str = "scatter",
) -> Any:
    """Shuffle-repartitioned drop-in for :func:`groupby.segment_count`
    (the join-side / window count shape): ``vec`` is the bool
    participation vector. Only segment codes + the receive marker
    travel."""
    (res,) = shuffled_segment_aggs(
        mesh,
        ["count"],
        seg,
        vec,
        [None],
        [None],
        num_segments,
        strategy=strategy,
    )
    v, _ = res
    return v


def _exchange_partials(ndev: int, part: Any) -> Any:
    """Reduce-scatter layout: each device's ``(S_pad,)`` partial table,
    viewed as ``(ndev, S_local)`` chunks, is exchanged so device ``d``
    receives row ``s`` = source ``s``'s partials for ``d``'s segment
    range. Bool partials transit as uint8 (all_to_all payload rule)."""
    s_local = part.shape[0] // ndev
    if part.dtype == jnp.bool_:
        out = jax.lax.all_to_all(
            part.astype(jnp.uint8).reshape(ndev, s_local),
            "p", split_axis=0, concat_axis=0, tiled=False,
        )
        return out.astype(jnp.bool_)
    return jax.lax.all_to_all(
        part.reshape(ndev, s_local), "p",
        split_axis=0, concat_axis=0, tiled=False,
    )


def preagg_segment_aggs(
    mesh: Mesh,
    funcs: List[str],
    seg: Any,
    valid: Any,
    values: List[Optional[Any]],
    masks: List[Optional[Any]],
    num_segments: int,
    strategy: str = "scatter",
) -> List[Tuple[Any, Optional[Any]]]:
    """Map-side combine (trace-time building block; call INSIDE a jitted
    program whose row arrays are sharded on ``mesh``): same contract and
    results as :func:`shuffled_segment_aggs`, but each device first
    reduces its OWN rows into per-segment partials and only the
    ``(ndev, S_local)`` partial tables cross the wire — ``O(S * ndev)``
    traffic instead of ``O(rows * ndev)``. Every func must be in
    :data:`PREAGG_FUNCS`.

    Per-aggregate decomposition (partials -> combine):

    - ``count``: partial counts -> sum
    - ``sum``: partial sums + nonempty markers -> sum / any
    - ``avg``: partial sums + partial counts -> sum, then one divide
      (averages themselves don't combine; their components do)
    - ``min``/``max``: identity-filled partial extrema -> min/max
    - ``first``/``last``: per-device candidate + has-rows marker; rows
      are position-sharded in device order, so the global first (last)
      is the candidate from the lowest (highest) device with rows
    """
    bad = [f for f in funcs if f.lower() not in PREAGG_FUNCS]
    if bad:
        raise ValueError(f"non-combinable aggregates for preagg: {bad}")
    ndev = int(mesh.devices.size)
    S = max(int(num_segments), 1)
    s_local = local_segments(S, ndev)
    s_pad = s_local * ndev
    n_payload = len(funcs)

    def _body(seg_: Any, valid_: Any, vals_: Any, masks_: Any) -> Any:
        partials: List[Tuple[str, List[Any]]] = []
        for i, func in enumerate(funcs):
            f = func.lower()
            if f == "mean":
                f = "avg"
            v = vals_.get(i)
            m = masks_.get(i)
            eff = valid_ if m is None else (m & valid_)
            if f == "count":
                cnt = groupby.segment_count(eff, seg_, s_pad, strategy)
                partials.append(("count", [cnt]))
            elif f == "sum":
                tot, ne = groupby._segment_agg_impl(
                    "sum", v, m, seg_, s_pad, valid_, strategy=strategy
                )
                partials.append(("sum", [tot, ne]))
            elif f == "avg":
                tot, _ = groupby._segment_agg_impl(
                    "sum", v, m, seg_, s_pad, valid_, strategy=strategy
                )
                cnt = groupby.segment_count(eff, seg_, s_pad, strategy)
                partials.append(("avg", [tot, cnt]))
            elif f in ("min", "max"):
                pv, ne = groupby._segment_agg_impl(
                    f, v, m, seg_, s_pad, valid_, strategy=strategy
                )
                partials.append((f, [pv, ne]))
            else:  # first / last: candidate value + has-valid-rows
                pv, pm = groupby._segment_agg_impl(
                    f, v, m, seg_, s_pad, valid_, strategy=strategy
                )
                has = jax.ops.segment_sum(
                    valid_.astype(jnp.int32), seg_, num_segments=s_pad
                ) > 0
                arrs = [pv, has]
                if pm is not None:
                    arrs.append(pm)
                partials.append((f, arrs))
        exchanged = [
            (tag, [_exchange_partials(ndev, a) for a in arrs])
            for tag, arrs in partials
        ]
        flat: List[Any] = []
        for i, (tag, R) in enumerate(exchanged):
            if tag == "count":
                v_o: Any = jnp.sum(R[0], axis=0)
                m_o: Optional[Any] = None
            elif tag == "sum":
                v_o = jnp.sum(R[0], axis=0)
                m_o = jnp.any(R[1], axis=0)
            elif tag == "avg":
                tot = jnp.sum(R[0], axis=0)
                cnt = jnp.sum(R[1], axis=0)
                av = tot / jnp.maximum(cnt, 1)
                v_o = av.astype(groupby.avg_dtype(vals_[i].dtype))
                m_o = cnt > 0
            elif tag == "min":
                v_o = jnp.min(R[0], axis=0)
                m_o = jnp.any(R[1], axis=0)
            elif tag == "max":
                v_o = jnp.max(R[0], axis=0)
                m_o = jnp.any(R[1], axis=0)
            else:  # first / last
                H = R[1]
                if tag == "first":
                    # argmax returns the FIRST max: lowest device with rows
                    src = jnp.argmax(H, axis=0)
                else:
                    src = (ndev - 1) - jnp.argmax(H[::-1], axis=0)
                v_o = jnp.take_along_axis(R[0], src[None, :], axis=0)[0]
                m_o = (
                    jnp.take_along_axis(R[2], src[None, :], axis=0)[0]
                    if len(R) > 2
                    else None
                )
            flat.append(v_o)
            flat.append(jnp.zeros((0,), jnp.bool_) if m_o is None else m_o)
        return tuple(flat)

    vals_in = {i: v for i, v in enumerate(values) if v is not None}
    masks_in = {i: m for i, m in enumerate(masks) if m is not None}
    out_has_mask = []
    for i, func in enumerate(funcs):
        f = func.lower()
        if f == "count":
            out_has_mask.append(False)
        elif f in ("first", "last"):
            out_has_mask.append(masks[i] is not None)
        else:
            out_has_mask.append(True)
    body = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("p"), P("p"), P("p"), P("p")),
        out_specs=P("p"),
        check_vma=False,
    )
    flat = body(seg.astype(jnp.int32), valid, vals_in, masks_in)
    # reduce-scatter layout is ALREADY canonical: global position
    # d * S_local + l IS global segment d * S_local + l
    results: List[Tuple[Any, Optional[Any]]] = []
    for i in range(n_payload):
        v_g = flat[2 * i][:S]
        m_g = flat[2 * i + 1][:S] if out_has_mask[i] else None
        results.append((v_g, m_g))
    return results


def preagg_segment_count(
    mesh: Mesh,
    vec: Any,
    seg: Any,
    num_segments: int,
    strategy: str = "scatter",
) -> Any:
    """Map-side-combine drop-in for :func:`groupby.segment_count` (the
    join-side / window count shape): each device counts its own rows,
    one ``(ndev, S_local)`` all-to-all, one sum."""
    (res,) = preagg_segment_aggs(
        mesh,
        ["count"],
        seg,
        vec,
        [None],
        [None],
        num_segments,
        strategy=strategy,
    )
    v, _ = res
    return v


def shuffle_rows(
    mesh: Mesh,
    seg: Any,
    valid: Any,
    arrays: Dict[str, Any],
) -> Tuple[Any, Any, Dict[str, Any]]:
    """The raw repartition primitive (trace-time): route every valid row
    to device ``seg % ndev``, returning ``(seg_sh, row_valid_sh,
    arrays_sh)`` with ``ndev * pad_n`` global rows (the padded receive).
    Used by relational.repartition_by_key to materialize a key
    co-located frame."""
    ndev = int(mesh.devices.size)
    names = sorted(arrays)

    def _body(seg_: Any, valid_: Any, arrs_: Any) -> Any:
        seg_sh, marker, outs = _shuffle_local(
            ndev, seg_, valid_, [arrs_[n] for n in names]
        )
        return (seg_sh, marker) + tuple(outs)

    body = shard_map(
        _body,
        mesh=mesh,
        in_specs=(P("p"), P("p"), P("p")),
        out_specs=P("p"),
        check_vma=False,
    )
    out = body(seg.astype(jnp.int32), valid, dict(arrays))
    seg_sh, marker = out[0], out[1]
    return seg_sh, marker, {n: out[2 + i] for i, n in enumerate(names)}
