"""Device relational ops: joins and set operations on mesh-sharded blocks.

TPU-first design (replaces the reference's engine-delegated joins,
fugue/execution/execution_engine.py:547-741, which lower to Spark/Dask
shuffles): both sides' key columns are factorized into ONE shared segment
space using the group-by machinery (groupby.py), then

- **semi / anti** are mask-only: flip the left frame's row validity by a
  per-segment occupancy test — no gather, no shuffle, zero host syncs.
- **inner / left / right / full / cross** expand matches with a
  counts -> exclusive-cumsum -> searchsorted enumeration entirely on
  device; ONE host sync reads the output row count (joins change
  cardinality, so a static output shape needs exactly one readback).
- **union** concatenates padded blocks (validity masks make the seam
  invisible); **intersect / subtract** are mask-only occupancy tests over
  a full-row factorization (SQL set-op semantics: NULLs compare equal,
  which the factorizer's null buckets give for free).

String keys join by dictionary code after re-encoding both sides into a
shared dictionary (host work proportional to the dictionaries, not the
data). Null JOIN keys never match (SQL): rows with any null key get the
out-of-range sentinel segment, so every occupancy/count test skips them.
"""

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from fugue_tpu.jax_backend import groupby, shuffle
from fugue_tpu.jax_backend.blocks import (
    JaxBlocks,
    JaxColumn,
    jit_row_sharded,
    on_mesh,
    padded_len,
    place_rows,
)
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw


def _common_dtype(d1: Any, d2: Any) -> Any:
    return jnp.result_type(d1, d2)


def _mesh_scoped(pos: int) -> Any:
    """Run the decorated function under ``on_mesh(args[pos].mesh)`` so its
    EAGER jnp creations (zeros/arange/asarray fed into jitted programs)
    stay on the frame's backend instead of the process default device —
    on a TPU process with host-tier frames the default device is across
    a network link (see blocks.on_mesh)."""
    import functools

    def deco(fn: Any) -> Any:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> Any:
            with on_mesh(args[pos].mesh):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def harmonize_string_keys(
    c1: JaxColumn, c2: JaxColumn, mesh: Any
) -> Tuple[JaxColumn, JaxColumn, np.ndarray]:
    """Re-encode two dictionary columns into one shared dictionary.
    Side 1 keeps its codes (the union dictionary extends side 1's);
    side 2's codes are remapped with one device table-gather (a
    row-sharded jitted program: multihost-safe)."""
    d1, d2 = c1.dictionary, c2.dictionary
    if d1 is d2 or (len(d1) == len(d2) and (d1 == d2).all()):
        return c1, c2, d1
    index1 = {v: i for i, v in enumerate(d1)}
    map2 = np.empty(max(len(d2), 1), dtype=np.int32)
    extra: List[Any] = []
    for i, v in enumerate(d2):
        j = index1.get(v)
        if j is None:
            j = len(d1) + len(extra)
            extra.append(v)
        map2[i] = j
    union = (
        np.concatenate([d1, np.asarray(extra, dtype=object)])
        if extra
        else d1
    )
    p2 = int(c2.data.shape[0])
    hi2 = max(len(d2) - 1, 0)
    remap = jit_row_sharded(
        mesh,
        ("dict_remap", p2, len(map2), hi2),
        lambda m, c: m[jnp.clip(c, 0, hi2)],
    )
    new_codes2 = remap(map2, c2.data)
    hi = max(len(union) - 1, 0)
    out1 = JaxColumn(c1.pa_type, c1.data, c1.mask, union, (0, hi))
    out2 = JaxColumn(c2.pa_type, new_codes2, c2.mask, union, (0, hi))
    return out1, out2, union


def _merged_stats(
    c1: JaxColumn, c2: JaxColumn
) -> Optional[Tuple[int, int]]:
    if c1.stats is None or c2.stats is None:
        return None
    return (min(c1.stats[0], c2.stats[0]), max(c1.stats[1], c2.stats[1]))


@_mesh_scoped(0)
def concat_key_blocks(
    b1: JaxBlocks, b2: JaxBlocks, keys: List[str]
) -> Tuple[JaxBlocks, int, int]:
    """A combined frame holding both sides' key columns stacked along the
    row axis (side 1 rows first). Padding rows of each side stay invalid,
    so no compaction is needed — factorization sees them as non-rows.
    Returns (combined, p1, p2) where p1/p2 are each side's padded length.

    All arrays are built inside ONE row-sharded jitted program
    (multihost-safe: eager concatenates would commit to a process-local
    device and device_put can't reshard across hosts)."""
    mesh = b1.mesh
    p1, p2 = b1.padded_nrows, b2.padded_nrows
    pairs: Dict[str, Tuple[JaxColumn, JaxColumn]] = {}
    for k in keys:
        c1, c2 = b1.columns[k], b2.columns[k]
        if c1.is_string:
            c1, c2, _ = harmonize_string_keys(c1, c2, mesh)
        pairs[k] = (c1, c2)
    dts = {
        k: _common_dtype(c1.data.dtype, c2.data.dtype)
        for k, (c1, c2) in pairs.items()
    }
    masked = tuple(
        sorted(
            k
            for k, (c1, c2) in pairs.items()
            if c1.mask is not None or c2.mask is not None
        )
    )

    def _prog(
        d1: Dict[str, Any],
        d2: Dict[str, Any],
        m1: Dict[str, Any],
        m2: Dict[str, Any],
        rv1: Optional[Any],
        n1: Any,
        rv2: Optional[Any],
        n2: Any,
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Any]:
        data = {
            k: jnp.concatenate(
                [d1[k].astype(dts[k]), d2[k].astype(dts[k])]
            )
            for k in d1
        }
        mask = {
            k: jnp.concatenate(
                [
                    m1.get(k, jnp.ones((p1,), dtype=bool)),
                    m2.get(k, jnp.ones((p2,), dtype=bool)),
                ]
            )
            for k in masked
        }
        v1 = groupby.materialize_validity(rv1, p1, n1)
        v2 = groupby.materialize_validity(rv2, p2, n2)
        return data, mask, jnp.concatenate([v1, v2])

    prog = jit_row_sharded(
        mesh,
        (
            "concat_keys", p1, p2, tuple(sorted(pairs)), masked,
            tuple(str(dts[k]) for k in sorted(dts)),
        ),
        _prog,
    )
    data, mask, row_valid = prog(
        {k: c1.data for k, (c1, _) in pairs.items()},
        {k: c2.data for k, (_, c2) in pairs.items()},
        {k: c1.mask for k, (c1, _) in pairs.items() if c1.mask is not None},
        {k: c2.mask for k, (_, c2) in pairs.items() if c2.mask is not None},
        b1.row_valid,
        _nrows_arg(b1),
        b2.row_valid,
        _nrows_arg(b2),
    )
    cols: Dict[str, JaxColumn] = {}
    for k, (c1, c2) in pairs.items():
        cols[k] = JaxColumn(
            c1.pa_type,
            data[k],
            mask.get(k),
            c1.host_dict,
            _merged_stats(c1, c2),
        )
    combined = JaxBlocks(None, cols, mesh, row_valid=row_valid)
    return combined, p1, p2


class SharedFactorization:
    """Both sides' keys in one segment space."""

    def __init__(
        self,
        seg1: Any,
        seg2: Any,
        num_segments: int,
        b1: JaxBlocks,
        b2: JaxBlocks,
        keys: List[str],
    ):
        self.seg1 = seg1  # int32[p1], sentinel num_segments for non-rows
        self.seg2 = seg2
        self.num_segments = num_segments
        self.b1 = b1
        self.b2 = b2
        self.keys = keys


def shared_factorize(
    b1: JaxBlocks, b2: JaxBlocks, keys: List[str]
) -> SharedFactorization:
    combined, p1, p2 = concat_key_blocks(b1, b2, keys)
    fr = groupby.factorize_keys(combined, keys)
    # split through a row-sharded program: an eager slice of a
    # process-spanning array is not multihost-safe
    split = jit_row_sharded(
        b1.mesh,
        ("seg_split", p1, p2),
        lambda s: (
            jax.lax.slice(s, (0,), (p1,)),
            jax.lax.slice(s, (p1,), (p1 + p2,)),
        ),
    )
    seg1, seg2 = split(fr.seg)
    return SharedFactorization(
        seg1, seg2, fr.num_segments, b1, b2, keys
    )


def _null_any_mask(b: JaxBlocks, keys: List[str]) -> Optional[Any]:
    """True where ANY key is null (such rows never match in a JOIN)."""
    masks = [
        b.columns[k].mask for k in keys if b.columns[k].mask is not None
    ]
    if not masks:
        return None
    nn = masks[0]
    for m in masks[1:]:
        nn = nn & m
    return ~nn


def device_joinable(
    b1: JaxBlocks, b2: JaxBlocks, names1: List[str], names2: List[str]
) -> bool:
    return all(
        n in b1.columns and b1.columns[n].on_device for n in names1
    ) and all(n in b2.columns and b2.columns[n].on_device for n in names2)


# ---------------------------------------------------------------------------
# semi / anti: mask-only
# ---------------------------------------------------------------------------


@_mesh_scoped(1)
def semi_anti_join(
    engine: Any, b1: JaxBlocks, b2: JaxBlocks, keys: List[str], anti: bool
) -> JaxBlocks:
    sf = shared_factorize(b1, b2, keys)
    S = max(sf.num_segments, 1)
    null1 = _null_any_mask(b1, keys)
    null2 = _null_any_mask(b2, keys)
    p1 = b1.padded_nrows
    # join-side count reductions share the group-by strategy layer
    strat = engine._count_reduce_strategy(b1, S)

    def _prog(
        seg1: Any,
        seg2: Any,
        v2: Any,
        n2m: Optional[Any],
        rv1: Optional[Any],
        n1m: Optional[Any],
        nrows1: Any,
    ) -> Tuple[Any, Any]:
        valid1 = groupby.materialize_validity(rv1, p1, nrows1)
        match2 = v2 if n2m is None else (v2 & ~n2m)
        # out-of-range seg ids contribute nothing on any strategy
        c2 = groupby.segment_count(
            match2, jnp.where(match2, seg2, S), S, strat
        )
        hit = c2[jnp.clip(seg1, 0, S - 1)] > 0
        matchable1 = valid1 if n1m is None else (valid1 & ~n1m)
        if anti:
            keep = valid1 & (~matchable1 | ~hit)
        else:
            keep = matchable1 & hit
        return keep, jnp.sum(keep).astype(jnp.int32)

    keep, cnt = engine._jit_cached(
        ("semi_anti", anti, S, p1, b2.padded_nrows, tuple(keys), strat),
        _prog,
    )(
        sf.seg1,
        sf.seg2,
        b2.validity(),
        null2,
        b1.row_valid,
        null1,
        _nrows_arg(b1),
    )
    return JaxBlocks(
        None, dict(b1.columns), b1.mesh, row_valid=keep, nrows_dev=cnt
    )


@_mesh_scoped(1)
def not_in_join(
    engine: Any, b1: JaxBlocks, b2: JaxBlocks, keys: List[str]
) -> JaxBlocks:
    """``WHERE x NOT IN (SELECT y ...)`` as a mask-only device op with
    SQL's three-valued semantics (the host oracle:
    select_runner._in_subquery): an EMPTY right side keeps every row
    (even a NULL x); ANY null right value keeps none (the comparison is
    never TRUE); otherwise keep non-null, non-matching rows. Zero host
    syncs — the count stays lazy like semi/anti."""
    sf = shared_factorize(b1, b2, keys)
    S = max(sf.num_segments, 1)
    null1 = _null_any_mask(b1, keys)
    null2 = _null_any_mask(b2, keys)
    p1 = b1.padded_nrows
    strat = engine._count_reduce_strategy(b1, S)

    def _prog(
        seg1: Any,
        seg2: Any,
        v2: Any,
        n2m: Optional[Any],
        rv1: Optional[Any],
        n1m: Optional[Any],
        nrows1: Any,
    ) -> Tuple[Any, Any]:
        valid1 = groupby.materialize_validity(rv1, p1, nrows1)
        empty2 = jnp.sum(v2.astype(jnp.int32)) == 0
        if n2m is None:
            any_null2 = jnp.asarray(False)
            match2 = v2
        else:
            any_null2 = jnp.sum((v2 & n2m).astype(jnp.int32)) > 0
            match2 = v2 & ~n2m
        c2 = groupby.segment_count(
            match2, jnp.where(match2, seg2, S), S, strat
        )
        hit = c2[jnp.clip(seg1, 0, S - 1)] > 0
        notnull1 = valid1 if n1m is None else (valid1 & ~n1m)
        keep = valid1 & (empty2 | (notnull1 & ~any_null2 & ~hit))
        return keep, jnp.sum(keep).astype(jnp.int32)

    keep, cnt = engine._jit_cached(
        ("not_in", S, p1, b2.padded_nrows, tuple(keys), strat), _prog
    )(
        sf.seg1,
        sf.seg2,
        b2.validity(),
        null2,
        b1.row_valid,
        null1,
        _nrows_arg(b1),
    )
    return JaxBlocks(
        None, dict(b1.columns), b1.mesh, row_valid=keep, nrows_dev=cnt
    )


# ---------------------------------------------------------------------------
# inner / left_outer (right/full build on these)
# ---------------------------------------------------------------------------


@_mesh_scoped(1)
def expand_join(
    engine: Any,
    b1: JaxBlocks,
    b2: JaxBlocks,
    keys: List[str],
    how: str,  # "inner" | "leftouter" | "fullouter" | "cross"
    schema1: Schema,
    schema2: Schema,
    out_schema: Schema,
) -> JaxBlocks:
    """Match-enumerating join. Phase 1 (device): per-left-row match counts
    and the sorted-by-segment ordering of the right side. One host sync
    reads the output size(s). Phase 2 (device): enumerate output rows by
    searchsorted over the exclusive cumsum, gather both sides."""
    mesh = b1.mesh
    p1, p2 = b1.padded_nrows, b2.padded_nrows
    is_cross = how == "cross"
    if is_cross:
        S = 1
        seg1 = jnp.zeros((p1,), dtype=jnp.int32)
        seg2 = jnp.zeros((p2,), dtype=jnp.int32)
        null1 = null2 = None
    else:
        sf = shared_factorize(b1, b2, keys)
        S, seg1, seg2 = sf.num_segments, sf.seg1, sf.seg2
        null1 = _null_any_mask(b1, keys)
        null2 = _null_any_mask(b2, keys)
    S = max(S, 1)
    outer_left = how in ("leftouter", "fullouter")
    if (
        how in ("inner", "leftouter")
        and len(keys) == 1
        and b2.columns[keys[0]].unique
    ):
        # each left row matches AT MOST ONE right row (host-proven at
        # ingest): no expansion, no output-cardinality readback — the
        # output is the left frame with right columns gathered in and a
        # validity mask. ZERO host syncs (the general path's one count
        # sync stalls dispatch until the device drains).
        return _unique_right_join(
            engine, b1, b2, how, S, seg1, seg2, null1, null2,
            schema1, schema2, out_schema,
        )

    # per-side match counts share the group-by strategy layer (matmul on
    # accelerator tiers below the segment cap, scatter otherwise); on
    # multi-device meshes the shuffle column of the strategy decision
    # runs them as a map-side combine: each device counts its own rows
    # and one reduce-scatter-layout all-to-all of partial counts gives
    # every device its own segment range
    strat = engine._count_reduce_strategy(b1, S)
    shuf = not is_cross and engine._join_shuffle(mesh, max(p1, p2), S)

    def _count_prog(
        seg1_: Any,
        seg2_: Any,
        rv1: Optional[Any],
        n1: Any,
        v2: Any,
        n1m: Optional[Any],
        n2m: Optional[Any],
    ) -> Tuple[Any, Any, Any, Any, Any, Any, Any]:
        valid1 = groupby.materialize_validity(rv1, p1, n1)
        match2 = v2 if n2m is None else (v2 & ~n2m)
        seg2s = jnp.where(match2, seg2_, S)
        # right-side metadata (per-segment counts, exclusive starts,
        # grouped order: stable, non-rows last). Multi-device shuffle:
        # GSPMD replicates a global argsort onto every device; the fused
        # local-sort + one-all-gather construction yields the identical
        # enumeration with only local sorts and ONE partial-counts
        # exchange feeding counts, starts and order alike
        if shuf:
            c2, cstart2, order2 = shuffle.sharded_grouped_order(
                mesh, seg2s, S
            )
        else:
            c2 = groupby.segment_count(match2, seg2s, S, strat)
            cstart2 = shuffle.sharded_cumsum(mesh, c2) - c2
            order2, _ = shuffle.grouped_sort(seg2s, S, p2)
        matchable1 = valid1 if n1m is None else (valid1 & ~n1m)
        m = jnp.where(matchable1, c2[jnp.clip(seg1_, 0, S - 1)], 0)
        reps = jnp.where(
            valid1, jnp.maximum(m, 1) if outer_left else m, 0
        )
        total = jnp.sum(reps)
        # sharded-axis prefix sum rides the two-level scan: GSPMD's own
        # cumsum partitioning serializes across devices (see
        # shuffle.sharded_cumsum)
        start = shuffle.sharded_cumsum(mesh, reps) - reps
        if how != "fullouter":
            # the right-unmatched tail exists only for full outer — an
            # O(p1) segment_sum the other join types shouldn't pay
            zero = jnp.zeros((), jnp.int32)
            return m, start, order2, cstart2, total, zero, order2
        seg1s = jnp.where(matchable1, seg1_, S)
        c1 = (
            shuffle.preagg_segment_count(mesh, matchable1, seg1s, S, strat)
            if shuf
            else groupby.segment_count(matchable1, seg1s, S, strat)
        )
        un2 = v2 & (
            ~match2 | (c1[jnp.clip(seg2_, 0, S - 1)] == 0)
        )
        r_total = jnp.sum(un2.astype(jnp.int32))
        order_un2 = jnp.argsort(~un2, stable=True).astype(jnp.int32)
        return m, start, order2, cstart2, total, r_total, order_un2

    t0 = time.perf_counter() if shuf else 0.0
    m, start, order2, cstart2, total, r_total, order_un2 = engine._jit_cached(
        ("join_count", how, S, p1, p2, tuple(keys), strat, shuf), _count_prog
    )(
        seg1,
        seg2,
        b1.row_valid,
        _nrows_arg(b1),
        b2.validity(),
        null1,
        null2,
    )
    if shuf:
        # join counts are combinable: they ride the map-side-combine
        # exchange (i32 partial counts), not the row shuffle
        ndev_ = int(mesh.devices.size)
        nbytes = shuffle.estimate_preagg_bytes(S, ndev_, 4)
        if how == "fullouter":
            nbytes *= 2
        engine._count_shuffle("join", nbytes, time.perf_counter() - t0, False)
    # THE one host sync of the join: output cardinality
    M = int(total)
    R = int(r_total) if how == "fullouter" else 0
    ndev = int(mesh.devices.size)
    out_pad = padded_len(M, ndev)

    d1 = {n: b1.columns[n] for n in schema1.names}
    other2 = [n for n in schema2.names if n not in schema1.names]
    d2 = {n: b2.columns[n] for n in other2}
    # harmonize output string columns BEFORE gathering so full-outer's
    # appended right rows share dictionaries (keys only; non-key columns
    # come from exactly one side)
    key_cols2: Dict[str, JaxColumn] = {}
    if how == "fullouter":
        for k in keys:
            c1h, c2h, _ = (
                harmonize_string_keys(d1[k], b2.columns[k], mesh)
                if d1[k].is_string
                else (d1[k], b2.columns[k], None)
            )
            d1[k] = c1h
            key_cols2[k] = c2h

    # expansion index algorithm: scatter marks at each left row's start
    # offset, then cumsum. This beats searchsorted ~7x on BOTH backends
    # (CPU: 417ms vs 57ms at 5M; TPU: 69ms vs 492ms — binary search over
    # 5M boundaries serializes into log(n) dependent gather passes, while
    # scatter+scan is two streaming sweeps)

    def _gather_prog(
        datas1: Dict[str, Any],
        masks1: Dict[str, Any],
        datas2: Dict[str, Any],
        masks2: Dict[str, Any],
        m_: Any,
        start_: Any,
        order2_: Any,
        cstart2_: Any,
        seg1_: Any,
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any], Dict[str, Any], Any]:
        t = jnp.arange(out_pad, dtype=jnp.int32)
        if int(mesh.devices.size) > 1:
            # the scatter+scan's GSPMD partitioning all-reduces full
            # output copies; per-shard binary search is collective-free
            i = shuffle.sharded_expand_rows(mesh, start_, out_pad)
        else:
            # rows with zero matches scatter onto the NEXT row's start
            # (same offset), so the duplicate marks accumulate and
            # cumsum skips them — "drop" discards starts beyond the
            # output (tail rows with zero matches)
            marks = jnp.zeros((out_pad,), jnp.int32).at[start_].add(
                1, mode="drop"
            )
            i = jnp.cumsum(marks) - 1
        i = jnp.clip(i, 0, p1 - 1)
        j_local = t - start_[i]
        matched = j_local < m_[i]
        s = jnp.clip(seg1_[i], 0, S - 1)
        rpos = jnp.clip(cstart2_[s] + j_local, 0, p2 - 1)
        ridx = order2_[rpos]
        out1 = {k: v[i] for k, v in datas1.items()}
        om1 = {k: v[i] for k, v in masks1.items()}
        out2 = {k: v[ridx] for k, v in datas2.items()}
        om2 = {k: v[ridx] & matched for k, v in masks2.items()}
        for k in datas2:
            if k not in om2:
                om2[k] = matched
        return out1, om1, out2, om2, matched

    g1, gm1, g2, gm2, _matched = engine._jit_cached(
        (
            "join_gather",
            how,
            S,
            p1,
            p2,
            out_pad,
            tuple(sorted(d1)),
            tuple(sorted(d2)),
            tuple(sorted(n for n, c in d1.items() if c.mask is not None)),
            tuple(sorted(n for n, c in d2.items() if c.mask is not None)),
        ),
        _gather_prog,
    )(
        {n: c.data for n, c in d1.items()},
        {n: c.mask for n, c in d1.items() if c.mask is not None},
        {n: c.data for n, c in d2.items()},
        {n: c.mask for n, c in d2.items() if c.mask is not None},
        m,
        start,
        order2,
        cstart2,
        seg1,
    )
    g1, gm1, g2, gm2 = place_rows(
        (g1, gm1, g2, gm2), mesh, engine._host_reshards
    )
    cols: Dict[str, JaxColumn] = {}
    for f in out_schema.fields:
        n = f.name
        if n in g1:
            src, data, mask = d1[n], g1[n], gm1.get(n)
        else:
            src, data, mask = d2[n], g2[n], gm2.get(n)
        cols[n] = JaxColumn(f.type, data, mask, src.host_dict, src.stats)
    out = JaxBlocks(M, cols, mesh)
    if how == "fullouter" and R > 0:
        right_part = _gather_right_unmatched(
            engine, b1, b2, keys, key_cols2, order_un2, R, out_schema
        )
        out = union_all_blocks(out, right_part)
    return out


def _unique_right_join(
    engine: Any,
    b1: JaxBlocks,
    b2: JaxBlocks,
    how: str,  # "inner" | "leftouter"
    S: int,
    seg1: Any,
    seg2: Any,
    null1: Optional[Any],
    null2: Optional[Any],
    schema1: Schema,
    schema2: Schema,
    out_schema: Schema,
) -> JaxBlocks:
    """Join against a right side whose (single) key is host-proven
    unique: one program scatters each right row's position into its
    segment slot, gathers right columns by the left rows' segments, and
    flips validity — left columns pass through UNTOUCHED (stats, dicts
    and uniqueness intact), the row count stays lazy."""
    mesh = b1.mesh
    p1, p2 = b1.padded_nrows, b2.padded_nrows
    other2 = [n for n in schema2.names if n not in schema1.names]
    d2 = {n: b2.columns[n] for n in other2}
    inner = how == "inner"

    def _prog(
        seg1_: Any,
        seg2_: Any,
        rv1: Optional[Any],
        n1: Any,
        v2: Any,
        n1m: Optional[Any],
        n2m: Optional[Any],
        datas2: Dict[str, Any],
        masks2: Dict[str, Any],
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Any, Any]:
        valid1 = groupby.materialize_validity(rv1, p1, n1)
        match2 = v2 if n2m is None else (v2 & ~n2m)
        pos2 = (
            jnp.full((S,), -1, dtype=jnp.int32)
            .at[jnp.where(match2, seg2_, S)]
            .max(jnp.arange(p2, dtype=jnp.int32), mode="drop")
        )
        matchable1 = valid1 if n1m is None else (valid1 & ~n1m)
        r = pos2[jnp.clip(seg1_, 0, S - 1)]
        matched = matchable1 & (r >= 0)
        ridx = jnp.clip(r, 0, p2 - 1)
        out2 = {k: v[ridx] for k, v in datas2.items()}
        om2 = {k: v[ridx] & matched for k, v in masks2.items()}
        for k in datas2:
            if k not in om2:
                om2[k] = matched
        keep = matched if inner else valid1
        return out2, om2, keep, jnp.sum(keep).astype(jnp.int32)

    g2, gm2, keep, cnt = engine._jit_cached(
        (
            "join_unique_right",
            how,
            S,
            p1,
            p2,
            tuple(sorted(d2)),
            tuple(sorted(n for n, c in d2.items() if c.mask is not None)),
        ),
        _prog,
    )(
        seg1,
        seg2,
        b1.row_valid,
        _nrows_arg(b1),
        b2.validity(),
        null1,
        null2,
        {n: c.data for n, c in d2.items()},
        {n: c.mask for n, c in d2.items() if c.mask is not None},
    )
    g2, gm2 = place_rows((g2, gm2), mesh, engine._host_reshards)
    cols: Dict[str, JaxColumn] = {}
    for f in out_schema.fields:
        n = f.name
        if n in g2:
            src = d2[n]
            cols[n] = JaxColumn(
                f.type,
                g2[n],
                gm2[n],
                src.host_dict,
                src.stats,
            )
        else:
            src = b1.columns[n]
            cols[n] = JaxColumn(
                f.type, src.data, src.mask, src.host_dict, src.stats,
                unique=src.unique,
            )
    return JaxBlocks(
        None, cols, mesh, row_valid=keep, nrows_dev=cnt
    )


@_mesh_scoped(1)
def _gather_right_unmatched(
    engine: Any,
    b1: JaxBlocks,
    b2: JaxBlocks,
    keys: List[str],
    key_cols2: Dict[str, JaxColumn],
    order_un2: Any,
    R: int,
    out_schema: Schema,
) -> JaxBlocks:
    """Full-outer tail: df2 rows with no df1 match; df1-only columns NULL.
    Key columns take df2's values (already dictionary-harmonized)."""
    mesh = b2.mesh
    ndev = int(mesh.devices.size)
    out_pad = padded_len(R, ndev)
    src_cols: Dict[str, JaxColumn] = {}
    left_only: List[str] = []
    for f in out_schema.fields:
        n = f.name
        if n in keys:
            src_cols[n] = key_cols2.get(n, b2.columns[n])
        elif n in b2.columns and n not in b1.columns:
            src_cols[n] = b2.columns[n]
        else:
            left_only.append(n)

    def _prog(
        datas: Dict[str, Any], masks: Dict[str, Any], order_: Any
    ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        idx = order_[
            jnp.clip(
                jnp.arange(out_pad, dtype=jnp.int32),
                0,
                order_.shape[0] - 1,
            )
        ]
        return (
            {k: v[idx] for k, v in datas.items()},
            {k: v[idx] for k, v in masks.items()},
        )

    g, gm = engine._jit_cached(
        (
            "join_right_tail",
            out_pad,
            b2.padded_nrows,
            tuple(sorted(src_cols)),
            tuple(
                sorted(
                    n for n, c in src_cols.items() if c.mask is not None
                )
            ),
        ),
        _prog,
    )(
        {n: c.data for n, c in src_cols.items()},
        {n: c.mask for n, c in src_cols.items() if c.mask is not None},
        order_un2,
    )
    g, gm = place_rows((g, gm), mesh, engine._host_reshards)
    cols: Dict[str, JaxColumn] = {}
    for f in out_schema.fields:
        n = f.name
        if n in src_cols:
            src = src_cols[n]
            cols[n] = JaxColumn(
                f.type,
                g[n],
                gm.get(n),
                src.host_dict,
                src.stats,
            )
        else:
            # left-only column: all NULL, made in the row sharding
            dt = np.dtype(_null_device_dtype(f.type))
            data, mask = jit_row_sharded(
                mesh,
                ("null_column", out_pad, dt.str),
                lambda dt=dt: (
                    jnp.zeros((out_pad,), dtype=dt),
                    jnp.zeros((out_pad,), dtype=bool),
                ),
            )()
            cols[n] = JaxColumn(
                f.type,
                data,
                mask,
                np.asarray([], dtype=object) if _is_str(f.type) else None,
                None,
            )
    return JaxBlocks(R, cols, mesh)


def _is_str(tp: pa.DataType) -> bool:
    return pa.types.is_string(tp) or pa.types.is_large_string(tp)


def _null_device_dtype(tp: pa.DataType) -> Any:
    if _is_str(tp):
        return jnp.int32
    if pa.types.is_timestamp(tp):
        return jnp.int64
    if pa.types.is_date32(tp):
        return jnp.int32
    if pa.types.is_boolean(tp):
        return jnp.bool_
    return tp.to_pandas_dtype()


# ---------------------------------------------------------------------------
# set operations
# ---------------------------------------------------------------------------


@_mesh_scoped(0)
def repartition_by_key(
    engine: Any, blocks: JaxBlocks, keys: List[str]
) -> Optional[JaxBlocks]:
    """Explicit shuffle repartition: materialize a copy of ``blocks``
    where every valid row lives on device ``segment(keys) % ndev``, via
    ONE padded all-to-all (shuffle.shuffle_rows). Joins, group-bys and
    distincts on the same keys then reduce purely device-locally —
    matching keys are co-located per shard.

    Row count, column dtypes, dictionaries and stats are preserved; only
    placement and padded length change (the receive is padded to
    ``ndev * padded_nrows``). Returns None when there is nothing to
    co-locate (single-device mesh) or the frame is not fully on device —
    callers fall back to the unshuffled frame."""
    mesh = blocks.mesh
    ndev = int(mesh.devices.size)
    if ndev <= 1 or not blocks.all_on_device:
        return None
    for k in keys:
        if k not in blocks.columns:
            return None
    fr = groupby.factorize_keys(blocks, keys)
    pad_n = blocks.padded_nrows
    names = sorted(blocks.columns)
    mask_names = tuple(
        n for n in names if blocks.columns[n].mask is not None
    )

    def _prog(
        seg_: Any,
        row_valid: Optional[Any],
        nrows_s: Any,
        datas_: Dict[str, Any],
        masks_: Dict[str, Any],
    ) -> Dict[str, Any]:
        valid_ = groupby.materialize_validity(row_valid, pad_n, nrows_s)
        arrays: Dict[str, Any] = {}
        for n in names:
            arrays[f"d:{n}"] = datas_[n]
        for n in mask_names:
            arrays[f"m:{n}"] = masks_[n]
        _, marker, out = shuffle.shuffle_rows(mesh, seg_, valid_, arrays)
        out["_valid"] = marker
        return out

    dtypes = tuple(str(blocks.columns[n].data.dtype) for n in names)
    t0 = time.perf_counter()
    outs = engine._jit_cached(
        ("repartition", tuple(names), mask_names, dtypes, tuple(keys),
         pad_n, ndev),
        _prog,
    )(
        fr.seg,
        blocks.row_valid,
        _nrows_arg(blocks),
        {n: blocks.columns[n].data for n in names},
        {n: blocks.columns[n].mask for n in mask_names},
    )
    widths = sum(
        blocks.columns[n].data.dtype.itemsize for n in names
    ) + len(mask_names)
    engine._count_shuffle(
        "repartition",
        shuffle.estimate_shuffle_bytes(pad_n, ndev, widths),
        time.perf_counter() - t0,
        False,
    )
    outs = place_rows(outs, mesh, engine._host_reshards)
    out_cols: Dict[str, JaxColumn] = {}
    for n in names:
        src = blocks.columns[n]
        out_cols[n] = JaxColumn(
            src.pa_type,
            outs[f"d:{n}"],
            outs.get(f"m:{n}"),
            src.host_dict,
            src.stats,
        )
    return JaxBlocks(
        blocks._nrows,
        out_cols,
        mesh,
        row_valid=outs["_valid"],
        nrows_dev=blocks._nrows_dev,
    )


def union_all_blocks(b1: JaxBlocks, b2: JaxBlocks) -> JaxBlocks:
    """Concatenate two frames along the row axis. Padding rows of each side
    remain invalid under the combined mask — no compaction, no sync. All
    arrays come from one row-sharded jitted program (multihost-safe —
    see concat_key_blocks)."""
    mesh = b1.mesh
    p1, p2 = b1.padded_nrows, b2.padded_nrows
    pairs: Dict[str, Tuple[JaxColumn, JaxColumn]] = {}
    for n, c1 in b1.columns.items():
        c2 = b2.columns[n]
        if c1.is_string:
            c1, c2, _ = harmonize_string_keys(c1, c2, mesh)
        pairs[n] = (c1, c2)
    dts = {
        n: _common_dtype(c1.data.dtype, c2.data.dtype)
        for n, (c1, c2) in pairs.items()
    }
    masked = tuple(
        sorted(
            n
            for n, (c1, c2) in pairs.items()
            if c1.mask is not None or c2.mask is not None
        )
    )

    def _prog(
        d1: Dict[str, Any],
        d2: Dict[str, Any],
        m1: Dict[str, Any],
        m2: Dict[str, Any],
        rv1: Optional[Any],
        n1: Any,
        rv2: Optional[Any],
        n2: Any,
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Any]:
        data = {
            n: jnp.concatenate(
                [d1[n].astype(dts[n]), d2[n].astype(dts[n])]
            )
            for n in d1
        }
        mask = {
            n: jnp.concatenate(
                [
                    m1.get(n, jnp.ones((p1,), dtype=bool)),
                    m2.get(n, jnp.ones((p2,), dtype=bool)),
                ]
            )
            for n in masked
        }
        v1 = groupby.materialize_validity(rv1, p1, n1)
        v2 = groupby.materialize_validity(rv2, p2, n2)
        return data, mask, jnp.concatenate([v1, v2])

    prog = jit_row_sharded(
        mesh,
        (
            "union_all", p1, p2, tuple(sorted(pairs)), masked,
            tuple(str(dts[n]) for n in sorted(dts)),
        ),
        _prog,
    )
    data, mask, row_valid = prog(
        {n: c1.data for n, (c1, _) in pairs.items()},
        {n: c2.data for n, (_, c2) in pairs.items()},
        {n: c1.mask for n, (c1, _) in pairs.items() if c1.mask is not None},
        {n: c2.mask for n, (_, c2) in pairs.items() if c2.mask is not None},
        b1.row_valid,
        _nrows_arg(b1),
        b2.row_valid,
        _nrows_arg(b2),
    )
    cols: Dict[str, JaxColumn] = {}
    for n, (c1, c2) in pairs.items():
        cols[n] = JaxColumn(
            c1.pa_type,
            data[n],
            mask.get(n),
            c1.host_dict,
            _merged_stats(c1, c2),
        )
    nrows = (
        b1._nrows + b2._nrows
        if b1.nrows_known and b2.nrows_known
        else None
    )
    nrows_dev = None
    if nrows is None:
        nrows_dev = b1.nrows_scalar + b2.nrows_scalar
    return JaxBlocks(
        nrows, cols, mesh, row_valid=row_valid, nrows_dev=nrows_dev
    )


@_mesh_scoped(1)
def intersect_subtract(
    engine: Any,
    b1: JaxBlocks,
    b2: JaxBlocks,
    names: List[str],
    subtract: bool,
    distinct: bool = True,
) -> JaxBlocks:
    """INTERSECT / EXCEPT: keep df1 rows whose full-row key {is, is not}
    present in df2 — first occurrence only when ``distinct``; multiset
    (... ALL) semantics otherwise: EXCEPT ALL keeps each row whose
    occurrence ordinal within its key is >= df2's count of that key,
    INTERSECT ALL those below it. Mask-only; NULLs compare equal (null
    buckets)."""
    sf = shared_factorize(b1, b2, names)
    S = max(sf.num_segments, 1)
    p1 = b1.padded_nrows
    # S + 1: the multiset branch reduces over the sentinel bucket too —
    # the selector must see the LARGEST segment count the program uses
    strat = engine._count_reduce_strategy(b1, S + 1)

    def _prog(
        seg1: Any,
        seg2: Any,
        rv1: Optional[Any],
        n1: Any,
        v2: Any,
    ) -> Tuple[Any, Any]:
        valid1 = groupby.materialize_validity(rv1, p1, n1)
        c2 = groupby.segment_count(v2, jnp.where(v2, seg2, S), S, strat)
        pos = jnp.arange(p1, dtype=jnp.int32)
        if distinct:
            hit = c2[jnp.clip(seg1, 0, S - 1)] > 0
            present = valid1 & (~hit if subtract else hit)
            # first occurrence among the kept df1 rows
            firsts = jax.ops.segment_min(
                jnp.where(present, pos, p1),
                jnp.where(present, seg1, S),
                num_segments=S,
            )
            keep = present & (firsts[jnp.clip(seg1, 0, S - 1)] == pos)
            return keep, jnp.sum(keep).astype(jnp.int32)
        # multiset: occurrence ordinal per key via a segment-sorted scan
        segv1 = jnp.where(valid1, seg1, S)
        order = jnp.argsort(segv1, stable=True)
        c1 = groupby.segment_count(valid1, segv1, S + 1, strat)[:S]
        starts = shuffle.sharded_cumsum(b1.mesh, c1) - c1
        sseg = segv1[order]
        ordinal_sorted = pos - starts[jnp.clip(sseg, 0, S - 1)]
        ordinal = jnp.zeros((p1,), dtype=jnp.int32).at[order].set(
            ordinal_sorted
        )
        rc = c2[jnp.clip(seg1, 0, S - 1)]
        keep = valid1 & (ordinal >= rc if subtract else ordinal < rc)
        return keep, jnp.sum(keep).astype(jnp.int32)

    keep, cnt = engine._jit_cached(
        (
            "intersect_subtract",
            subtract,
            distinct,
            S,
            p1,
            b2.padded_nrows,
            tuple(names),
            strat,
        ),
        _prog,
    )(sf.seg1, sf.seg2, b1.row_valid, _nrows_arg(b1), b2.validity())
    return JaxBlocks(
        None, dict(b1.columns), b1.mesh, row_valid=keep, nrows_dev=cnt
    )


def _nrows_arg(blocks: JaxBlocks) -> Any:
    if blocks._nrows is not None:
        return np.int32(blocks._nrows)
    if blocks._nrows_dev is not None:
        return blocks._nrows_dev
    return np.int32(-1)


# ---------------------------------------------------------------------------
# fillna / take / sample (mask-only where possible)
# ---------------------------------------------------------------------------


def _encode_fill_value(col: JaxColumn, value: Any) -> Optional[Any]:
    """The fill value in the column's device representation, or None if it
    cannot be represented (caller falls back)."""
    tp = col.pa_type
    try:
        if col.is_string:
            if not isinstance(value, str):
                return None
            hits = np.nonzero(col.dictionary == value)[0]
            if len(hits) > 0:
                return np.int32(hits[0])
            # append to the dictionary (host-side, small)
            col.dictionary = np.concatenate(
                [col.dictionary, np.asarray([value], dtype=object)]
            )
            if col.stats is not None:
                col.stats = (col.stats[0], len(col.dictionary) - 1)
            return np.int32(len(col.dictionary) - 1)
        if pa.types.is_timestamp(tp):
            ts = np.datetime64(value, "us")
            return np.int64((ts - np.datetime64(0, "us")).astype(np.int64))
        if pa.types.is_date32(tp):
            d = np.datetime64(value, "D")
            return np.int32(
                (d - np.datetime64(0, "D")).astype(np.int64)
            )
        v = np.asarray(value, dtype=col.data.dtype)[()]
        # the host oracle REJECTS inexact fills (e.g. 2.5 into int64);
        # a silently truncating device path would diverge from it
        if not np.issubdtype(col.data.dtype, np.floating) and v != value:
            return None
        return v
    except (ValueError, TypeError):
        return None


@_mesh_scoped(1)
def device_fillna(
    engine: Any,
    blocks: JaxBlocks,
    schema: Schema,
    targets: Dict[str, Any],
) -> Optional[JaxBlocks]:
    """Fill nulls in `targets` columns in ONE jitted dispatch; the filled
    columns drop their masks. Returns None when any target column is
    host-resident or the value can't be encoded."""
    enc: Dict[str, Any] = {}
    float_cols: List[str] = []
    for name, value in targets.items():
        col = blocks.columns[name]
        if not col.on_device:
            return None
        is_float = jnp.issubdtype(col.data.dtype, jnp.floating)
        if col.mask is None and not is_float:
            continue  # nothing to fill
        v = _encode_fill_value(col, value)
        if v is None:
            return None
        enc[name] = v
        if is_float:
            float_cols.append(name)
    if not enc:
        return blocks
    names = sorted(enc)

    def _prog(
        datas: Dict[str, Any], masks: Dict[str, Any], fills: Dict[str, Any]
    ) -> Dict[str, Any]:
        outs: Dict[str, Any] = {}
        for nm in names:
            d = datas[nm]
            m = masks.get(nm)
            eff_null = jnp.zeros(d.shape, dtype=bool) if m is None else ~m
            if nm in float_cols:
                eff_null = eff_null | jnp.isnan(d)
            outs[nm] = jnp.where(eff_null, fills[nm].astype(d.dtype), d)
        return outs

    outs = engine._jit_cached(
        (
            "fillna",
            blocks.padded_nrows,
            tuple(names),
            tuple(sorted(float_cols)),
            tuple(nm for nm in names if blocks.columns[nm].mask is not None),
        ),
        _prog,
    )(
        {nm: blocks.columns[nm].data for nm in names},
        {
            nm: blocks.columns[nm].mask
            for nm in names
            if blocks.columns[nm].mask is not None
        },
        {nm: jnp.asarray(enc[nm]) for nm in names},
    )
    outs = place_rows(outs, blocks.mesh, engine._host_reshards)
    new_cols = dict(blocks.columns)
    for nm in names:
        src = blocks.columns[nm]
        new_cols[nm] = JaxColumn(
            src.pa_type,
            outs[nm],
            None,
            src.host_dict,
            src.stats,
        )
    return JaxBlocks(
        blocks._nrows,
        new_cols,
        blocks.mesh,
        row_valid=blocks.row_valid,
        nrows_dev=blocks._nrows_dev,
    )


@_mesh_scoped(0)
def _sort_code_columns(
    blocks: JaxBlocks, sorts: List[Tuple[str, bool]]
) -> Optional[List[Tuple[Any, Optional[Any], bool]]]:
    """Per sort item IN ORDER (duplicates kept): (device code array,
    effective-null mask or None, ascending). String columns sort by
    LEXICOGRAPHIC rank (a host argsort of the small dictionary builds the
    rank table), not by code order."""
    out: List[Tuple[Any, Optional[Any], bool]] = []
    for name, asc in sorts:
        col = blocks.columns.get(name)
        if col is None or not col.on_device:
            return None
        data = col.data
        if col.is_string:
            order = np.argsort(col.dictionary.astype(str), kind="stable")
            rank = np.empty(max(len(order), 1), dtype=np.int32)
            rank[order] = np.arange(len(order), dtype=np.int32)
            data = jnp.asarray(rank)[
                jnp.clip(col.data, 0, max(len(order) - 1, 0))
            ]
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
        null = None if col.mask is None else ~col.mask
        if jnp.issubdtype(data.dtype, jnp.floating):
            nan = jnp.isnan(data)
            null = nan if null is None else (null | nan)
            data = jnp.where(nan, jnp.zeros_like(data), data)
        out.append((data, null, bool(asc)))
    return out


def _stable_sort_order(
    code_arrs: Tuple[Any, ...],
    null_arrs: Dict[int, Any],
    ascs: List[bool],
    na_first: List[bool],
    valid: Any,
    invalid_last: bool = True,
) -> Any:
    """Traced helper shared by device_take/device_sort: row order under a
    stable multi-key sort (keys applied least-significant outward), per-key
    NULLS FIRST/LAST, then (unless the caller re-sorts, e.g. by segment)
    invalid rows last. ``descending=True`` (not value negation) because
    negating unsigned or INT_MIN values wraps and silently misorders
    (review finding)."""
    p = valid.shape[0]
    order = jnp.arange(p, dtype=jnp.int32)
    for i in reversed(range(len(code_arrs))):
        sc = code_arrs[i]
        if i in null_arrs:
            # null slots hold fill garbage (join gathers especially):
            # neutralize them so null rows TIE on the value key and keep
            # the less-significant key order (review finding)
            sc = jnp.where(null_arrs[i], jnp.zeros_like(sc), sc)
        sc = sc[order]
        order = order[jnp.argsort(sc, stable=True, descending=not ascs[i])]
        if i in null_arrs:
            nf = null_arrs[i][order]
            # nulls first -> sort by NOT-null; nulls last -> by null
            flag = ~nf if na_first[i] else nf
            order = order[jnp.argsort(flag, stable=True)]
    if invalid_last:
        order = order[jnp.argsort(~valid[order], stable=True)]
    return order


@_mesh_scoped(1)
def device_take(
    engine: Any,
    blocks: JaxBlocks,
    schema: Schema,
    n: int,
    sorts: Dict[str, bool],
    na_position: str,
    partition_by: List[str],
) -> Optional[JaxBlocks]:
    """Mask-only take: rows keep their storage order; validity flips to
    the first `n` rows per partition (or globally) under the presort
    order. Zero host syncs; the row count becomes a lazy device scalar."""
    codes = _sort_code_columns(blocks, list(sorts.items()))
    if codes is None:
        return None
    for k in partition_by:
        col = blocks.columns.get(k)
        if col is None or not col.on_device:
            return None
    p = blocks.padded_nrows
    if partition_by:
        fr = groupby.factorize_keys(blocks, partition_by)
        seg, S = fr.seg, max(fr.num_segments, 1)
    else:
        seg, S = None, 1
    na_first = na_position == "first"

    def _prog(
        code_arrs: Tuple[Any, ...],
        null_arrs: Dict[int, Any],
        seg_: Optional[Any],
        row_valid: Optional[Any],
        nrows_s: Any,
    ) -> Tuple[Any, Any]:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        order = _stable_sort_order(
            code_arrs, null_arrs,
            [asc for _, _, asc in codes],
            [na_first] * len(codes),
            valid,
            invalid_last=seg_ is None,
        )
        if seg_ is not None:
            order = order[jnp.argsort(seg_[order], stable=True)]
            # invalid rows last (their sentinel seg already sorts high,
            # but keep the explicit guarantee)
            order = order[jnp.argsort(~valid[order], stable=True)]
        invrank = jnp.zeros((p,), dtype=jnp.int32).at[order].set(
            jnp.arange(p, dtype=jnp.int32)
        )
        if seg_ is not None:
            cnt = jax.ops.segment_sum(
                valid.astype(jnp.int32),
                jnp.where(valid, seg_, S),
                num_segments=S,
            )
            starts = jnp.cumsum(cnt) - cnt
            local = invrank - starts[jnp.clip(seg_, 0, S - 1)]
            keep = valid & (local < n)
        else:
            keep = valid & (invrank < n)
        return keep, jnp.sum(keep).astype(jnp.int32)

    keep, cnt = engine._jit_cached(
        (
            "take",
            n,
            p,
            S,
            tuple(partition_by),
            tuple((nm, asc) for nm, asc in sorts.items()),
            tuple(i for i in range(len(codes)) if codes[i][1] is not None),
            na_position,
        ),
        _prog,
    )(
        tuple(c for c, _, _ in codes),
        {i: nl for i, (_, nl, _) in enumerate(codes) if nl is not None},
        seg,
        blocks.row_valid,
        _nrows_arg(blocks),
    )
    return JaxBlocks(
        None, dict(blocks.columns), blocks.mesh, row_valid=keep, nrows_dev=cnt
    )


@_mesh_scoped(1)
def device_sort(
    engine: Any,
    blocks: JaxBlocks,
    schema: Schema,
    sorts: List[Tuple[str, bool, Optional[bool]]],
    limit: Optional[int] = None,
    offset: Optional[int] = None,
) -> Optional[JaxBlocks]:
    """ORDER BY [LIMIT/OFFSET] as a device ROW REORDER: stable multi-key
    argsort on device (per-key NULLS FIRST/LAST; default LAST to match the
    host SELECT runner), then one gather of the surviving window. Pays one
    host sync for the row count — ORDER BY sits at a query's export
    boundary, where that sync happens anyway. With ``sorts == []`` this is
    plain LIMIT/OFFSET in storage order."""
    code_cols = _sort_code_columns(
        blocks, [(name, asc) for name, asc, _ in sorts]
    )
    if code_cols is None:
        return None
    if not all(c.on_device for c in blocks.columns.values()):
        return None
    p = blocks.padded_nrows
    na_first = [
        (nulls if nulls is not None else False) for _, _, nulls in sorts
    ]

    def _prog(
        code_arrs: Tuple[Any, ...],
        null_arrs: Dict[int, Any],
        row_valid: Optional[Any],
        nrows_s: Any,
    ) -> Any:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        return _stable_sort_order(
            code_arrs, null_arrs,
            [asc for _, _, asc in code_cols],
            na_first,
            valid,
        )

    order = engine._jit_cached(
        (
            "sort",
            p,
            tuple(
                (nm, asc, nf) for (nm, asc, _), nf in zip(sorts, na_first)
            ),
            tuple(
                i for i in range(len(code_cols))
                if code_cols[i][1] is not None
            ),
        ),
        _prog,
    )(
        tuple(c for c, _, _ in code_cols),
        {i: nl for i, (_, nl, _) in enumerate(code_cols) if nl is not None},
        blocks.row_valid,
        _nrows_arg(blocks),
    )
    n = blocks.nrows  # the one host sync
    start = min(offset or 0, n)
    stop = n if limit is None else min(n, start + limit)
    from fugue_tpu.jax_backend.blocks import gather_indices

    return gather_indices(
        blocks, order[start:stop], schema, engine._host_reshards
    )


@_mesh_scoped(1)
def device_window(
    engine: Any,
    blocks: JaxBlocks,
    schema: Schema,
    items: List[Any],
) -> Optional[Tuple[JaxBlocks, Schema]]:
    """Window functions as device programs (verdict r3 item 4's device
    lowering): whole-partition aggregates gather segment reductions back
    per row; the ranking family (row_number/rank/dense_rank/ntile/
    percent_rank/cume_dist) runs through _window_rank_family's sorted-
    space program (stable sort + per-segment start offsets + adjacent-
    row peer detection). ``items`` mixes
    ``("col", (out_name, src_name))`` passthroughs with ``("win", spec)``
    entries (see ``algebra_bridge.WindowSpec``). Returns None when any
    referenced column is host-resident."""
    if not all(c.on_device for c in blocks.columns.values()):
        return None
    p = blocks.padded_nrows
    out_cols: Dict[str, JaxColumn] = {}
    fields: List[Any] = []
    for kind, payload in items:
        if kind == "col":
            out_name, src_name = payload
            src = blocks.columns.get(src_name)
            if src is None:
                return None
            out_cols[out_name] = src
            fields.append(
                pa.field(out_name, schema[src_name].type)
            )
            continue
        spec = payload
        if spec.partition_by:
            fr = groupby.factorize_keys(blocks, list(spec.partition_by))
            seg, S = fr.seg, max(fr.num_segments, 1)
        else:
            seg, S = jnp.zeros((p,), dtype=jnp.int32), 1
        if spec.func in (
            "row_number", "rank", "dense_rank", "ntile", "percent_rank",
            "cume_dist",
        ):
            col, tp = _window_rank_family(engine, blocks, spec, seg, S, p)
        elif spec.order_by:
            res = _window_frame_agg(engine, blocks, spec, seg, S, p)
            if res is None:
                return None
            col, tp = res
        else:
            res = _window_segment_agg(engine, blocks, spec, seg, S, p)
            if res is None:
                return None
            col, tp = res
        out_cols[spec.name] = col
        fields.append(pa.field(spec.name, tp))
    out_schema = Schema(fields)
    return (
        JaxBlocks(
            blocks._nrows,
            out_cols,
            blocks.mesh,
            row_valid=blocks.row_valid,
            nrows_dev=blocks._nrows_dev,
        ),
        out_schema,
    )


def _window_rank_family(
    engine: Any, blocks: JaxBlocks, spec: Any, seg: Any, S: int, p: int
) -> Tuple[JaxColumn, pa.DataType]:
    """The ranking family (row_number / rank / dense_rank / ntile /
    percent_rank / cume_dist) as one device program: stable sort by
    (order keys, partition), local position per partition, and — for the
    peer-aware variants — peer-group detection by comparing ADJACENT
    sorted rows' key codes (null-neutralized exactly like the sort)."""
    kind = spec.func
    buckets = int(getattr(spec, "param", 0) or 0)  # ntile's N
    codes = _sort_code_columns(
        blocks, [(name, asc) for name, asc, _ in spec.order_by]
    )
    assert_or_throw(codes is not None, ValueError("sort key not on device"))
    na_first = [
        (nf if nf is not None else False) for _, _, nf in spec.order_by
    ]

    def _prog(
        code_arrs: Tuple[Any, ...],
        null_arrs: Dict[int, Any],
        seg_: Any,
        row_valid: Optional[Any],
        nrows_s: Any,
    ) -> Any:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        order = _stable_sort_order(
            code_arrs, null_arrs,
            [asc for _, _, asc in codes],  # type: ignore[misc]
            na_first, valid, invalid_last=False,
        )
        segv = jnp.where(valid, seg_, S)
        order = order[jnp.argsort(segv[order], stable=True)]
        pos = jnp.arange(p, dtype=jnp.int32)
        cnt = jax.ops.segment_sum(
            valid.astype(jnp.int32), segv, num_segments=S + 1
        )[:S]
        starts = jnp.cumsum(cnt) - cnt
        sseg = segv[order]
        start_pos = starts[jnp.clip(sseg, 0, S - 1)]
        psize = cnt[jnp.clip(sseg, 0, S - 1)]
        local_sorted = pos - start_pos  # 0-based row number per partition
        if kind == "row_number":
            out_sorted: Any = local_sorted + 1
        elif kind == "ntile":
            # first (psize % n) buckets take the extra rows (standard)
            q_ = psize // buckets
            rem = psize % buckets
            cutoff = rem * (q_ + 1)
            head = local_sorted // jnp.maximum(q_ + 1, 1) + 1
            tail = rem + (local_sorted - cutoff) // jnp.maximum(q_, 1) + 1
            out_sorted = jnp.where(local_sorted < cutoff, head, tail)
        else:
            false0 = jnp.zeros((1,), dtype=bool)
            same_part = jnp.concatenate([false0, sseg[1:] == sseg[:-1]])
            is_peer = same_part
            for i, c in enumerate(code_arrs):
                sc = c
                if i in null_arrs:
                    sc = jnp.where(null_arrs[i], jnp.zeros_like(sc), sc)
                scs = sc[order]
                eq = jnp.concatenate([false0, scs[1:] == scs[:-1]])
                if i in null_arrs:
                    nn = null_arrs[i][order]
                    eq = eq & jnp.concatenate([false0, nn[1:] == nn[:-1]])
                is_peer = is_peer & eq
            if kind in ("rank", "percent_rank"):
                # the peer-group head's GLOBAL position carries forward
                # (cummax is safe: positions are globally increasing and
                # every partition head starts a new peer group)
                head_pos = jax.lax.cummax(jnp.where(~is_peer, pos, -1))
                rank_sorted = head_pos - start_pos + 1
                if kind == "rank":
                    out_sorted = rank_sorted
                else:
                    out_sorted = jnp.where(
                        psize > 1,
                        (rank_sorted - 1)
                        / jnp.maximum(psize - 1, 1).astype(jnp.float64),
                        0.0,
                    )
            elif kind == "dense_rank":
                cs = jnp.cumsum((~is_peer).astype(jnp.int32))
                cs_at_start = cs[jnp.clip(start_pos, 0, p - 1)]
                out_sorted = cs - cs_at_start + 1
            else:  # cume_dist: peers share the group's LAST position
                big = jnp.int32(p)
                heads = jnp.where(~is_peer, pos, big)
                # next peer-head strictly after each position, via a
                # reversed cummin of head positions shifted left
                nh = jnp.flip(jax.lax.cummin(jnp.flip(
                    jnp.concatenate([heads[1:], big[None]])
                )))
                part_end = start_pos + psize - 1
                last_pos = jnp.minimum(nh - 1, part_end)
                out_sorted = (
                    (last_pos - start_pos + 1)
                    / jnp.maximum(psize, 1).astype(jnp.float64)
                )
        if kind in ("percent_rank", "cume_dist"):
            return jnp.zeros((p,), dtype=jnp.float64).at[order].set(
                out_sorted.astype(jnp.float64)
            )
        return (
            jnp.zeros((p,), dtype=jnp.int64).at[order].set(
                out_sorted.astype(jnp.int64)
            )
        )

    rn = engine._jit_cached(
        (
            "win_rank", kind, buckets, p, S, tuple(spec.partition_by),
            tuple(
                (nm, asc, nf)
                for (nm, asc, _), nf in zip(spec.order_by, na_first)
            ),
            tuple(i for i in range(len(codes)) if codes[i][1] is not None),
        ),
        _prog,
    )(
        tuple(c for c, _, _ in codes),
        {i: nl for i, (_, nl, _) in enumerate(codes) if nl is not None},
        seg,
        blocks.row_valid,
        _nrows_arg(blocks),
    )
    tp = (
        pa.float64()
        if kind in ("percent_rank", "cume_dist")
        else pa.int64()
    )
    rn = place_rows(rn, blocks.mesh, engine._host_reshards)
    return JaxColumn(tp, rn), tp


def _window_frame_agg(
    engine: Any, blocks: JaxBlocks, spec: Any, seg: Any, S: int, p: int
) -> Optional[Tuple[JaxColumn, pa.DataType]]:
    """Ordered window programs in sorted space (the role the reference's
    DuckDB backend plays natively for framed/running windows,
    ``/root/reference/fugue_duckdb/execution_engine.py:37``): stable
    sort by (order keys, partition), then

    - running (default RANGE) aggregates: segment-offset prefix sums
      with peers sharing their group's LAST value,
    - ROWS-framed aggregates: prefix-sum differences over positional
      [lo, hi] bounds; min/max via a log2(p)-level sparse table,
    - GROUPS frames: peer-group ids with per-group start/end tables,
    - RANGE frames: peer bounds, with numeric offsets resolved by a
      vectorized per-partition bisect over the raw order key,
    - lag/lead: a shifted gather with partition-boundary masking,
    - first/last/nth_value: gathers at frame boundary positions,

    and one scatter back to row space. Returns None when the argument or
    a sort key is host-resident or the dtype is outside the device set.
    """
    func = "avg" if spec.func == "mean" else spec.func
    gather_like = func in (
        "lag", "lead", "first_value", "last_value", "nth_value"
    )
    if spec.arg is None:  # count(*)
        vcol = None
        arg_tp: Optional[pa.DataType] = None
    else:
        vcol = blocks.columns.get(spec.arg)
        if vcol is None or not vcol.on_device:
            return None
        if vcol.is_string and not gather_like:
            return None
        if vcol.is_string and spec.default is not None:
            return None  # a fill literal has no dictionary code
        if (
            spec.default is not None
            and isinstance(spec.default, float)
            and pa.types.is_integer(vcol.pa_type)
        ):
            return None  # the host upcasts int columns to float here
        arg_tp = vcol.pa_type
    cast_result = True
    if func == "count":
        tp: pa.DataType = pa.int64()
    elif func in ("sum", "avg"):
        if arg_tp is None or not (
            pa.types.is_integer(arg_tp)
            or pa.types.is_floating(arg_tp)
            or pa.types.is_boolean(arg_tp)
        ):
            return None
        tp = (
            pa.float64()
            if func == "avg"
            else (pa.int64() if pa.types.is_integer(arg_tp) else pa.float64())
        )
    elif func in ("min", "max"):
        if arg_tp is None or pa.types.is_boolean(arg_tp):
            return None
        tp = arg_tp
        if pa.types.is_timestamp(arg_tp) or pa.types.is_date32(arg_tp):
            cast_result = False
    else:  # gathers keep the argument's device representation
        assert arg_tp is not None
        tp = arg_tp
        cast_result = False
    codes = _sort_code_columns(
        blocks, [(name, asc) for name, asc, _ in spec.order_by]
    )
    if codes is None:
        return None
    na_first = [
        (nf if nf is not None else False) for _, _, nf in spec.order_by
    ]
    frame = spec.frame  # None = running default frame (peers share)
    off = int(spec.param or 0)  # lag/lead offset or nth_value position
    default = spec.default
    values = None if vcol is None else vcol.data
    vmask = None if vcol is None else vcol.mask
    okey = None
    okey_mask = None
    if frame is not None and frame[0] == "range" and any(
        kd in ("p", "f") for kd in (frame[1], frame[3])
    ):
        # numeric RANGE offsets: the raw single ORDER BY key drives the
        # per-partition value search (bridge guarantees one key)
        kcol = blocks.columns.get(spec.order_by[0][0])
        if (
            kcol is None
            or not kcol.on_device
            or kcol.is_string
            or not (
                pa.types.is_integer(kcol.pa_type)
                or pa.types.is_floating(kcol.pa_type)
                or pa.types.is_boolean(kcol.pa_type)
            )
        ):
            return None  # non-numeric key: host runner owns the error
        okey = kcol.data
        okey_mask = kcol.mask

    def _prog(
        code_arrs: Tuple[Any, ...],
        null_arrs: Dict[int, Any],
        values_: Optional[Any],
        vmask_: Optional[Any],
        okey_: Optional[Any],
        okey_mask_: Optional[Any],
        seg_: Any,
        row_valid: Optional[Any],
        nrows_s: Any,
    ) -> Tuple[Any, Optional[Any]]:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        order = _stable_sort_order(
            code_arrs, null_arrs,
            [asc for _, _, asc in codes],  # type: ignore[misc]
            na_first, valid, invalid_last=False,
        )
        segv = jnp.where(valid, seg_, S)
        order = order[jnp.argsort(segv[order], stable=True)]
        pos = jnp.arange(p, dtype=jnp.int32)
        cnt = jax.ops.segment_sum(
            valid.astype(jnp.int32), segv, num_segments=S + 1
        )[:S]
        starts = jnp.cumsum(cnt) - cnt
        sseg = segv[order]
        part_start = starts[jnp.clip(sseg, 0, S - 1)]
        psize = cnt[jnp.clip(sseg, 0, S - 1)]
        part_end = part_start + psize - 1
        svalid = valid[order]
        sv = None if values_ is None else values_[order]
        if values_ is None:
            sm = svalid
        elif vmask_ is None:
            sm = svalid
        else:
            sm = svalid & vmask_[order]
        if sv is not None and jnp.issubdtype(sv.dtype, jnp.floating):
            sm = sm & ~jnp.isnan(sv)

        def _scatter(out_sorted: Any, m_sorted: Optional[Any]) -> Tuple[
            Any, Optional[Any]
        ]:
            out = jnp.zeros((p,), dtype=out_sorted.dtype).at[order].set(
                out_sorted
            )
            m = (
                None
                if m_sorted is None
                else jnp.zeros((p,), dtype=bool).at[order].set(m_sorted)
            )
            return out, m

        if func in ("lag", "lead"):
            src = pos - off if func == "lag" else pos + off
            inb = (src >= part_start) & (src <= part_end)
            srcc = jnp.clip(src, 0, p - 1)
            val = sv[srcc]
            vm = sm[srcc] & inb
            if default is not None:
                dv = jnp.asarray(default).astype(val.dtype)
                val = jnp.where(inb, val, dv)
                vm = vm | ~inb
            return _scatter(val, vm)

        # frame bounds [lo, hi] in sorted space
        unit = None if frame is None else frame[0]
        if unit is None or unit in ("groups", "range"):
            # peer detection (adjacent sorted rows tying on every key)
            false0 = jnp.zeros((1,), dtype=bool)
            same_part = jnp.concatenate([false0, sseg[1:] == sseg[:-1]])
            is_peer = same_part
            for i, c in enumerate(code_arrs):
                sc = c
                if i in null_arrs:
                    sc = jnp.where(null_arrs[i], jnp.zeros_like(sc), sc)
                scs = sc[order]
                eq = jnp.concatenate([false0, scs[1:] == scs[:-1]])
                if i in null_arrs:
                    nn = null_arrs[i][order]
                    eq = eq & jnp.concatenate([false0, nn[1:] == nn[:-1]])
                is_peer = is_peer & eq
        if unit in ("groups", "range"):
            gnew = ~is_peer
            g_glob = (jnp.cumsum(gnew.astype(jnp.int32)) - 1).astype(
                jnp.int32
            )
            g_start_by = jax.ops.segment_min(pos, g_glob, num_segments=p)
            g_end_by = jax.ops.segment_max(pos, g_glob, num_segments=p)
            peer_start = g_start_by[g_glob]
            peer_end = g_end_by[g_glob]
        if unit is None:
            # running: lo = partition start, hi = peer group's LAST row
            big = jnp.int32(p)
            heads = jnp.where(~is_peer, pos, big)
            nh = jnp.flip(jax.lax.cummin(jnp.flip(
                jnp.concatenate([heads[1:], big[None]])
            )))
            lo = part_start
            hi = jnp.minimum(nh - 1, part_end)
        elif unit == "rows":
            _, sk, sn, ek, en = frame

            def _bound(kd: str, nv: Optional[int]) -> Any:
                if kd == "up":
                    return part_start
                if kd == "uf":
                    return part_end
                if kd == "c":
                    return pos
                return pos + int(nv) if kd == "f" else pos - int(nv)

            lo = jnp.maximum(_bound(sk, sn), part_start)
            hi = jnp.minimum(_bound(ek, en), part_end)
        elif unit == "groups":
            _, sk, sn, ek, en = frame
            g_first = g_glob[part_start]
            g_last = g_glob[part_end]

            def _gbound(kd: str, nv: Optional[int], is_start: bool) -> Any:
                if kd == "up":
                    return part_start
                if kd == "uf":
                    return part_end
                if kd == "c":
                    return peer_start if is_start else peer_end
                tg = g_glob + (int(nv) if kd == "f" else -int(nv))
                tgc = jnp.clip(tg, 0, p - 1)
                if is_start:
                    out = jnp.where(
                        tg < g_first, part_start, g_start_by[tgc]
                    )
                    return jnp.where(tg > g_last, part_end + 1, out)
                out = jnp.where(tg > g_last, part_end, g_end_by[tgc])
                return jnp.where(tg < g_first, part_start - 1, out)

            lo = jnp.maximum(_gbound(sk, sn, True), part_start)
            hi = jnp.minimum(_gbound(ek, en, False), part_end)
        else:  # range (peer bounds; numeric offsets via bisect)
            _, sk, sn, ek, en = frame
            need_key = sk in ("p", "f") or ek in ("p", "f")
            if need_key:  # okey_ is loaded only for offset bounds
                kv = okey_.astype(jnp.float64)
                knull = (
                    jnp.zeros((p,), dtype=bool)
                    if okey_mask_ is None
                    else ~okey_mask_
                )
                knull = knull | jnp.isnan(okey_.astype(jnp.float64))
                asc = bool(spec.order_by[0][1])
                if not asc:
                    kv = -kv
                skv = kv[order]
                snull = (knull | ~valid)[order]
                # non-null span [a, b] per row: nulls sort to one end
                ncnt = jax.ops.segment_sum(
                    (knull & valid).astype(jnp.int32), segv,
                    num_segments=S + 1,
                )[:S][jnp.clip(sseg, 0, S - 1)]
                nf = spec.order_by[0][2]
                nulls_first = bool(nf) if nf is not None else False
                if nulls_first:
                    a_, b_ = part_start + ncnt, part_end
                else:
                    a_, b_ = part_start, part_end - ncnt
            steps = max(1, int(np.ceil(np.log2(max(p, 2)))) + 1)

            def _bisect(target: Any, right: bool) -> Any:
                lo_b, hi_b = a_, b_ + 1
                for _ in range(steps):
                    mid = (lo_b + hi_b) // 2
                    mv = skv[jnp.clip(mid, 0, p - 1)]
                    go = (mv <= target) if right else (mv < target)
                    go = go & (lo_b < hi_b)
                    stay = (lo_b < hi_b) & ~go
                    lo_b = jnp.where(go, mid + 1, lo_b)
                    hi_b = jnp.where(stay, mid, hi_b)
                return lo_b

            def _rbound(kd: str, nv: Any, is_start: bool) -> Any:
                if kd == "up":
                    return part_start
                if kd == "uf":
                    return part_end
                if kd == "c":
                    return peer_start if is_start else peer_end
                delta = float(nv) if kd == "f" else -float(nv)
                tgt = skv + delta
                res = (
                    _bisect(tgt, right=False)
                    if is_start
                    else _bisect(tgt, right=True) - 1
                )
                # null keys: the bound resolves to the null peer group
                return jnp.where(
                    snull, peer_start if is_start else peer_end, res
                )

            lo = jnp.maximum(_rbound(sk, sn, True), part_start)
            hi = jnp.minimum(_rbound(ek, en, False), part_end)
        empty = lo > hi
        lo_s = jnp.clip(lo, 0, p - 1)
        hi_s = jnp.clip(hi, 0, p - 1)

        if func == "count":
            if sv is None:
                out = jnp.where(empty, 0, hi - lo + 1).astype(jnp.int64)
            else:
                c = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int64), jnp.cumsum(
                        sm.astype(jnp.int64)
                    )]
                )
                out = jnp.where(empty, 0, c[hi_s + 1] - c[lo_s])
            return _scatter(out.astype(jnp.int64), None)
        if func in ("sum", "avg"):
            acc = (
                jnp.int64
                if arg_tp is not None and pa.types.is_integer(arg_tp)
                else jnp.float64
            )
            fv = jnp.where(sm, sv.astype(acc), jnp.zeros((), acc))
            cs = jnp.concatenate(
                [jnp.zeros((1,), acc), jnp.cumsum(fv)]
            )
            cn = jnp.concatenate(
                [jnp.zeros((1,), jnp.int64), jnp.cumsum(
                    sm.astype(jnp.int64)
                )]
            )
            fcnt = jnp.where(empty, 0, cn[hi_s + 1] - cn[lo_s])
            tot = jnp.where(
                empty, jnp.zeros((), acc), cs[hi_s + 1] - cs[lo_s]
            )
            if func == "sum":
                return _scatter(tot, fcnt > 0)
            return _scatter(
                tot.astype(jnp.float64)
                / jnp.maximum(fcnt, 1).astype(jnp.float64),
                fcnt > 0,
            )
        if func in ("min", "max"):
            is_min = func == "min"
            if jnp.issubdtype(sv.dtype, jnp.floating):
                sentinel = jnp.array(
                    jnp.inf if is_min else -jnp.inf, dtype=sv.dtype
                )
            else:
                info = jnp.iinfo(sv.dtype)
                sentinel = jnp.array(
                    info.max if is_min else info.min, dtype=sv.dtype
                )
            op = jnp.minimum if is_min else jnp.maximum
            level = jnp.where(sm, sv, sentinel)
            levels = [level]
            w = 1
            while w < p:
                shifted = jnp.concatenate(
                    [level[w:], jnp.full((w,), sentinel, dtype=sv.dtype)]
                )
                level = op(level, shifted)
                levels.append(level)
                w *= 2
            stack = jnp.stack(levels)  # (K, p): min/max over [i, i+2^k-1]
            length = (hi_s - lo_s + 1).astype(jnp.float64)
            kq = jnp.floor(
                jnp.log2(jnp.maximum(length, 1.0))
            ).astype(jnp.int32)
            flat = stack.reshape(-1)
            a = flat[kq * p + lo_s]
            b = flat[kq * p + jnp.maximum(hi_s - (1 << kq) + 1, 0)]
            out = op(a, b)
            cn = jnp.concatenate(
                [jnp.zeros((1,), jnp.int64), jnp.cumsum(
                    sm.astype(jnp.int64)
                )]
            )
            fcnt = jnp.where(empty, 0, cn[hi_s + 1] - cn[lo_s])
            if cast_result:
                out = out.astype(tp.to_pandas_dtype())
            return _scatter(out, fcnt > 0)
        # first/last/nth_value: boundary gathers
        if func == "nth_value":
            at = lo + off - 1
            bad = empty | (at > hi)
        elif func == "first_value":
            at = lo
            bad = empty
        else:
            at = hi
            bad = empty
        atc = jnp.clip(at, 0, p - 1)
        return _scatter(sv[atc], sm[atc] & ~bad)

    out, outm = engine._jit_cached(
        (
            "win_frame", func, spec.arg, frame, off,
            None if default is None else float(default), p, S,
            tuple(spec.partition_by),
            tuple(
                (nm, asc, nf)
                for (nm, asc, _), nf in zip(spec.order_by, na_first)
            ),
            str(tp), vmask is not None,
            tuple(i for i in range(len(codes)) if codes[i][1] is not None),
        ),
        _prog,
    )(
        tuple(c for c, _, _ in codes),
        {i: nl for i, (_, nl, _) in enumerate(codes) if nl is not None},
        values,
        vmask,
        okey,
        okey_mask,
        seg,
        blocks.row_valid,
        _nrows_arg(blocks),
    )
    dictionary = None if vcol is None else (
        vcol.host_dict if gather_like else None
    )
    out, outm = place_rows((out, outm), blocks.mesh, engine._host_reshards)
    return JaxColumn(tp, out, outm, dictionary=dictionary), tp


def _window_segment_agg(
    engine: Any, blocks: JaxBlocks, spec: Any, seg: Any, S: int, p: int
) -> Optional[Tuple[JaxColumn, pa.DataType]]:
    if spec.arg is None:  # count(*)
        values = jnp.ones((p,), dtype=jnp.int32)
        vmask = None
        arg_tp: Optional[pa.DataType] = None
    else:
        col = blocks.columns.get(spec.arg)
        if col is None or not col.on_device or col.is_string:
            return None
        values, vmask = col.data, col.mask
        arg_tp = col.pa_type
    func = "avg" if spec.func == "mean" else spec.func
    cast_result = True
    if func == "count":
        tp: pa.DataType = pa.int64()
    elif func in ("avg", "sum"):
        # numeric payloads only — the host oracle owns the error for
        # SUM(timestamp) etc.
        if arg_tp is None or not (
            pa.types.is_integer(arg_tp)
            or pa.types.is_floating(arg_tp)
            or pa.types.is_boolean(arg_tp)
        ):
            return None
        tp = (
            pa.float64()
            if func == "avg"
            else (pa.int64() if pa.types.is_integer(arg_tp) else pa.float64())
        )
    else:  # min/max
        if arg_tp is None:
            return None
        tp = arg_tp
        if pa.types.is_timestamp(arg_tp) or pa.types.is_date32(arg_tp):
            # device representation is already the right integer encoding;
            # datetime64 is not a jax dtype (review finding)
            cast_result = False

    # windowed sum/avg/count are segment reductions too: same strategy
    # layer as the group-by (min/max stay scatter-native inside the impl)
    strat = engine._count_reduce_strategy(blocks, S + 1)

    def _prog(
        values_: Any,
        vmask_: Optional[Any],
        seg_: Any,
        row_valid: Optional[Any],
        nrows_s: Any,
    ) -> Tuple[Any, Optional[Any]]:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        segv = jnp.where(valid, seg_, S)
        v, m = groupby._segment_agg_impl(
            func, values_, vmask_, segv, S + 1, valid, strategy=strat
        )
        segc = jnp.clip(seg_, 0, S - 1)
        out = v[:S][segc]
        if cast_result:
            out = out.astype(tp.to_pandas_dtype())
        outm = None if m is None else m[:S][segc]
        return out, outm

    out, outm = engine._jit_cached(
        (
            "win_agg", func, spec.arg, p, S, tuple(spec.partition_by),
            str(tp), vmask is not None, strat,
        ),
        _prog,
    )(values, vmask, seg, blocks.row_valid, _nrows_arg(blocks))
    out, outm = place_rows((out, outm), blocks.mesh, engine._host_reshards)
    return JaxColumn(tp, out, outm), tp


@_mesh_scoped(1)
def device_sample(
    engine: Any,
    blocks: JaxBlocks,
    n: Optional[int],
    frac: Optional[float],
    seed: Optional[int],
) -> JaxBlocks:
    """Sampling without replacement as a validity flip: every row draws a
    distinct priority (a random permutation, so no float-tie inflation);
    the k smallest priorities among valid rows are kept. k is `n` or
    ``round(nrows * frac)`` computed IN-program, so lazy counts stay lazy."""
    p = blocks.padded_nrows
    if seed is None:
        seed = int(np.random.default_rng().integers(0, 2**31 - 1))

    def _prog(key: Any, row_valid: Optional[Any], nrows_s: Any) -> Tuple[Any, Any]:
        valid = groupby.materialize_validity(row_valid, p, nrows_s)
        pri = jax.random.permutation(key, p).astype(
            jnp.int32
        )
        masked = jnp.where(valid, pri, p)
        srt = jnp.sort(masked)
        nvalid = jnp.sum(valid.astype(jnp.int32))
        if n is not None:
            k = jnp.int32(n)
        else:
            k = jnp.round(nvalid.astype(jnp.float64) * frac).astype(jnp.int32)
        k = jnp.minimum(k, nvalid)
        kth = srt[jnp.clip(k - 1, 0, p - 1)]
        keep = valid & (masked <= kth) & (k > 0)
        return keep, jnp.sum(keep).astype(jnp.int32)

    keep, cnt = engine._jit_cached(
        ("sample", p, n, frac), _prog
    )(jax.random.PRNGKey(seed), blocks.row_valid, _nrows_arg(blocks))
    return JaxBlocks(
        None, dict(blocks.columns), blocks.mesh, row_valid=keep, nrows_dev=cnt
    )
