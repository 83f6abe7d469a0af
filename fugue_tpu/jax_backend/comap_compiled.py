"""Compiled comap: cotransform as ONE whole-shard jitted program.

The reference's comap (fugue/execution/execution_engine.py:1066-1118)
deserializes each key group and applies the cotransformer in a per-group
host loop — SURVEY §3.5's perf cliff, and the one place this framework
still paid it (zipped.py keeps that loop for host cotransformers). For a
jax-annotated cotransformer (``Dict[str, jax.Array]`` per member) the
TPU-first shape is the same one the map/groupby/join paths already use:

- every member's zip keys are co-factorized into ONE shared segment space
  (the join machinery's N-way generalization of
  ``relational.shared_factorize``);
- the user function runs ONCE, compiled, over whole mesh-sharded columns,
  with per-member ``_segment_ids`` in the shared space — per-key work
  becomes ``jax.ops.segment_*`` reductions instead of a Python loop;
- zip presence rules (inner/left_outer/...) become a per-segment ``alive``
  mask computed in-program: rows of dead segments are masked out of
  ``_row_valid`` and re-pointed at the out-of-range sentinel, so segment
  ops drop them with zero host syncs.

The cotransformer ABI (mirrors the map ABI, JaxMapEngine._compiled_map):
the function receives one dict per zipped member, each carrying

- its columns as arrays (string columns as int32 dictionary codes plus a
  static ``_<name>_dict`` decode table), ``_<name>_mask`` validity masks;
- ``_row_valid`` bool[padded_m]: True = real row in a LIVE segment;
- ``_nrows``: traced int32 count of those rows;
- ``_segment_ids`` int32[padded_m] in the SHARED space (sentinel
  ``_num_segments`` for dead/padding rows);
- ``_num_segments``: the STATIC shared segment-space size (same value in
  every member dict; some segments may be empty or dead).

Output dict semantics (by array length):

- ``num_segments``: one row per segment — the frame keeps only LIVE
  segments via its validity mask, count stays lazy (zero host syncs);
- member 0's padded length: row-aligned with member 0 (inherits its
  masked validity);
- anything else: include ``_nrows`` (one sync, prefix layout).

The same function runs unmodified on host engines: ``JaxArraysParam``
presents each logical partition as a one-segment member dict.
"""

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.dataframe import ArrayDataFrame, DataFrame
from fugue_tpu.jax_backend import groupby
from fugue_tpu.jax_backend.blocks import (
    JaxBlocks,
    JaxColumn,
    is_device_type,
    jit_row_sharded,
    padded_len,
)
from fugue_tpu.jax_backend.relational import (
    _common_dtype,
    _merged_stats,
    harmonize_string_keys,
)
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw


class HostPathRequired(Exception):
    """The zipped shape can't run compiled; the caller falls back to the
    host group loop (zipped.device_comap). The message is the fallback
    reason recorded by the engine's counter."""


def _harmonize_n(cs: List[JaxColumn], mesh: Any) -> List[JaxColumn]:
    """Re-encode N dictionary columns into one shared dictionary by
    left-folding the pairwise harmonizer: each step only APPENDS to the
    union dictionary, so earlier members' codes stay valid and just adopt
    the final table."""
    out = [cs[0]]
    for c in cs[1:]:
        base, remapped, _ = harmonize_string_keys(out[0], c, mesh)
        out[0] = base
        out.append(remapped)
    union = out[0].dictionary
    hi = max(len(union) - 1, 0)
    return [
        JaxColumn(c.pa_type, c.data, c.mask, union, (0, hi)) for c in out
    ]


def _concat_key_blocks_n(
    blocks_list: List[JaxBlocks], keys: List[str]
) -> Tuple[JaxBlocks, List[int]]:
    """All members' key columns stacked along the row axis (member 0 rows
    first) — the N-way form of relational.concat_key_blocks. Padding rows
    stay invalid, so factorization sees them as non-rows. Arrays are
    built inside one row-sharded jitted program (multihost-safe — see
    relational.concat_key_blocks)."""
    mesh = blocks_list[0].mesh
    ps = [b.padded_nrows for b in blocks_list]
    n = len(blocks_list)
    per_key: Dict[str, List[JaxColumn]] = {}
    for k in keys:
        cs = [b.columns[k] for b in blocks_list]
        if cs[0].is_string:
            cs = _harmonize_n(cs, mesh)
        per_key[k] = cs
    dts = {}
    for k, cs in per_key.items():
        dt = cs[0].data.dtype
        for c in cs[1:]:
            dt = _common_dtype(dt, c.data.dtype)
        dts[k] = dt
    masked = tuple(
        sorted(
            k
            for k, cs in per_key.items()
            if any(c.mask is not None for c in cs)
        )
    )

    key_names = tuple(sorted(per_key))

    def _prog(
        datas: List[Dict[str, Any]],
        masks: List[Dict[str, Any]],
        rvs: Tuple[Optional[Any], ...],
        nrs: Tuple[Any, ...],
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Any]:
        # iterate NAMES only: closing over per_key would pin the first
        # call's device arrays inside the process-wide jit cache
        data = {
            k: jnp.concatenate(
                [datas[m][k].astype(dts[k]) for m in range(n)]
            )
            for k in key_names
        }
        mask = {
            k: jnp.concatenate(
                [
                    masks[m].get(k, jnp.ones((ps[m],), dtype=bool))
                    for m in range(n)
                ]
            )
            for k in masked
        }
        valid = jnp.concatenate(
            [
                groupby.materialize_validity(rvs[m], ps[m], nrs[m])
                for m in range(n)
            ]
        )
        return data, mask, valid

    prog = jit_row_sharded(
        mesh,
        (
            "concat_keys_n", tuple(ps), tuple(sorted(per_key)), masked,
            tuple(str(dts[k]) for k in sorted(dts)),
        ),
        _prog,
    )
    from fugue_tpu.jax_backend.execution_engine import _nrows_arg

    data, mask, row_valid = prog(
        [{k: cs[m].data for k, cs in per_key.items()} for m in range(n)],
        [
            {
                k: cs[m].mask
                for k, cs in per_key.items()
                if cs[m].mask is not None
            }
            for m in range(n)
        ],
        tuple(b.row_valid for b in blocks_list),
        tuple(_nrows_arg(b) for b in blocks_list),
    )
    cols: Dict[str, JaxColumn] = {}
    for k, cs in per_key.items():
        stats = cs[0]
        for c in cs[1:]:
            stats = JaxColumn(
                stats.pa_type, stats.data, None, None,
                _merged_stats(stats, c),
            )
        cols[k] = JaxColumn(
            cs[0].pa_type, data[k], mask.get(k), cs[0].host_dict,
            stats.stats,
        )
    combined = JaxBlocks(None, cols, mesh, row_valid=row_valid)
    return combined, ps


def _alive_rule(how: str, present: List[Any]) -> Any:
    """Per-segment liveness under the zip's presence rule — the compiled
    form of the host loop's membership tests (zipped.device_comap)."""
    if how == "inner":
        alive = present[0]
        for p in present[1:]:
            alive = alive & p
        return alive
    if how == "left_outer":
        return present[0]
    if how == "right_outer":
        return present[-1]
    # full_outer: any member present
    alive = present[0]
    for p in present[1:]:
        alive = alive | p
    return alive


def compiled_comap(
    engine: Any,
    zdf: Any,  # JaxZippedDataFrame (import cycle)
    fn: Callable,
    output_schema: Any,
    partition_spec: PartitionSpec,
    on_init: Optional[Callable],
) -> DataFrame:
    """Run a jax-annotated cotransformer compiled over the shared segment
    space, or raise :class:`HostPathRequired` with the reason."""
    from fugue_tpu.jax_backend.execution_engine import (
        _StringDictUnavailable,
        _is_dict_key,
        _nrows_arg,
        _pad_to,
    )
    from fugue_tpu.jax_backend.dataframe import JaxDataFrame

    out_schema = Schema(output_schema)
    how = zdf.how
    keys = list(zdf.keys)
    if zdf.zip_spec.presort or partition_spec.presort:
        # presort orders rows WITHIN a group; whole-shard segment programs
        # have no per-group row order, so honoring it needs the host loop
        raise HostPathRequired("comap presort requires host grouping")
    if not all(is_device_type(f.type) for f in out_schema.fields):
        raise HostPathRequired("comap output schema has host-only types")
    for s in (f.schema for f in zdf.frames):
        if not all(is_device_type(f.type) for f in s.fields):
            raise HostPathRequired("comap member has host-only columns")
    jdfs: List[JaxDataFrame] = [engine.to_df(f) for f in zdf.frames]
    blocks_list = [j.blocks for j in jdfs]
    mesh = blocks_list[0].mesh
    if any(b.mesh is not mesh and b.mesh != mesh for b in blocks_list):
        raise HostPathRequired("comap members on different meshes")
    if not all(b.all_on_device for b in blocks_list):
        raise HostPathRequired("comap member has host-resident columns")

    n_members = len(blocks_list)
    ps = [b.padded_nrows for b in blocks_list]
    if how == "cross":
        S = 1
        zero_prog = jit_row_sharded(
            mesh,
            ("comap_zero_segs", tuple(ps)),
            lambda: tuple(
                jnp.zeros((p,), dtype=jnp.int32) for p in ps
            ),
        )
        segs: List[Any] = list(zero_prog())
    else:
        combined, _ = _concat_key_blocks_n(blocks_list, keys)
        fr = groupby.factorize_keys(combined, keys)
        S = max(fr.num_segments, 1)
        bounds = []
        off = 0
        for p in ps:
            bounds.append((off, off + p))
            off += p
        # row-sharded split (eager slices are not multihost-safe)
        split = jit_row_sharded(
            mesh,
            ("comap_seg_split", tuple(ps)),
            lambda s: tuple(
                jax.lax.slice(s, (a,), (b,)) for a, b in bounds
            ),
        )
        segs = list(split(fr.seg))

    if S == ps[0]:
        # output length is the ONLY signal separating per-segment from
        # member-0-row-aligned results; when the two coincide the compiled
        # path could keep/drop the wrong rows — the host loop is always
        # correct (the ABI runs per group there), so use it
        raise HostPathRequired(
            "ambiguous output length: num_segments == member 0 padding"
        )

    array_args: Dict[str, Any] = {}
    static_args: List[Dict[str, Any]] = []
    col_names: List[List[str]] = []
    for m, b in enumerate(blocks_list):
        st: Dict[str, Any] = {}
        names: List[str] = []
        for name, col in b.columns.items():
            array_args[f"m{m}:{name}"] = col.data
            names.append(name)
            if col.mask is not None:
                array_args[f"m{m}:_{name}_mask"] = col.mask
            if col.host_dict is not None:
                st[f"_{name}_dict"] = col.dictionary
        array_args[f"m{m}:__seg"] = segs[m]
        static_args.append(st)
        col_names.append(names)
    rvs = tuple(b.row_valid for b in blocks_list)
    nrows_args = tuple(_nrows_arg(b) for b in blocks_list)
    stash: Dict[str, Any] = {}

    def _wrapped(
        aa: Dict[str, Any],
        rv_in: Tuple[Optional[Any], ...],
        nrows_in: Tuple[Any, ...],
    ) -> Any:
        member_dicts: List[Dict[str, Any]] = []
        valids = [
            groupby.materialize_validity(rv_in[m], ps[m], nrows_in[m])
            for m in range(n_members)
        ]
        seg_eff = [
            jnp.where(valids[m], aa[f"m{m}:__seg"], S)
            for m in range(n_members)
        ]
        if how == "cross":
            # cross zip is always ONE group, even over empty members
            alive = jnp.ones((S,), dtype=bool)
        else:
            present = [
                jax.ops.segment_sum(
                    valids[m].astype(jnp.int32), seg_eff[m], num_segments=S
                )
                > 0
                for m in range(n_members)
            ]
            alive = _alive_rule(how, present)
        cnt_alive = jnp.sum(alive).astype(jnp.int32)
        row_alive: List[Any] = []
        for m in range(n_members):
            ra = valids[m] & alive[jnp.clip(aa[f"m{m}:__seg"], 0, S - 1)]
            row_alive.append(ra)
            d: Dict[str, Any] = {}
            for name in col_names[m]:
                d[name] = aa[f"m{m}:{name}"]
                mk = aa.get(f"m{m}:_{name}_mask")
                if mk is not None:
                    d[f"_{name}_mask"] = mk
            d.update(static_args[m])
            d["_row_valid"] = ra
            d["_nrows"] = jnp.sum(ra).astype(jnp.int32)
            d["_segment_ids"] = jnp.where(ra, aa[f"m{m}:__seg"], S)
            d["_num_segments"] = S
            member_dicts.append(d)
        out = fn(*member_dicts)
        assert_or_throw(
            isinstance(out, dict),
            ValueError("jax cotransformer must return a dict of arrays"),
        )
        for k in [k for k in out if _is_dict_key(k)]:
            stash[k] = np.asarray(out.pop(k), dtype=object)
        cnt0 = jnp.sum(row_alive[0]).astype(jnp.int32)
        return out, alive, cnt_alive, row_alive[0], cnt0

    cache_key = (
        "comap", id(fn), how, S, tuple(ps), tuple(sorted(array_args)),
        tuple(
            (m, k, id(v))
            for m, st in enumerate(static_args)
            for k, v in sorted(st.items())
        ),
    )
    cache = getattr(engine, "_comap_cache", None)
    if cache is None:
        cache = {}
        engine._comap_cache = cache
    if cache_key not in cache:
        # abstract trace now: it fills the stash (fn-returned decode
        # tables pop out at trace time) BEFORE the string-output check,
        # and is cached with the executable so id-reuse cannot alias
        shaped = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in array_args.items()
        }
        rv_s = tuple(
            None if r is None else jax.ShapeDtypeStruct(r.shape, r.dtype)
            for r in rvs
        )
        nr_s = tuple(
            jax.ShapeDtypeStruct((), jnp.int32) for _ in nrows_args
        )
        try:
            jax.eval_shape(_wrapped, shaped, rv_s, nr_s)
        except HostPathRequired:
            raise
        except Exception as ex:
            # a function valid in the host's one-segment mode but not
            # jit-traceable (float()/item()/data-dependent branching)
            # belongs on the host group loop, not a trace crash
            raise HostPathRequired(
                f"cotransformer not jit-traceable ({type(ex).__name__})"
            )
        cache[cache_key] = (jax.jit(_wrapped), stash)
    jitted, dict_stash = cache[cache_key]
    # every string output needs an fn-returned decode table: co-reduced
    # codes are never an input passthrough across the member boundary
    for f in out_schema.fields:
        if pa.types.is_string(f.type) or pa.types.is_large_string(f.type):
            if f"_{f.name}_dict" not in dict_stash:
                raise _StringDictUnavailable(f.name)
    # past the last bail-out point: on_init runs exactly once per comap
    # (the host-loop fallback has its own call — review finding)
    if on_init is not None:
        on_init(0, _empty_dfs(zdf))
    out, alive, cnt_alive, rv0, cnt0 = jitted(array_args, rvs, nrows_args)

    first = -1
    for f in out_schema.fields:
        assert_or_throw(
            f.name in out,
            ValueError(f"jax cotransformer output missing column {f.name}"),
        )
        n = int(out[f.name].shape[0])
        if first < 0:
            first = n
        assert_or_throw(
            n == first,
            ValueError("jax cotransformer output columns differ in length"),
        )

    ndev = int(mesh.devices.size)
    row_valid_out: Optional[Any] = None
    nrows_out: Optional[int] = None
    nrows_dev_out: Optional[Any] = None
    cols: Dict[str, JaxColumn] = {}
    to_pad: Dict[str, Any] = {}
    alive_key = "__alive"
    while alive_key in out or any(
        f.name == alive_key for f in out_schema.fields
    ):
        alive_key += "_"  # never collide with a user output column
    if "_nrows" in out:
        nrows_out = int(out["_nrows"])  # explicit count: one sync
        # an over-reporting cotransformer would make garbage padding rows
        # real; match the host group loop's validation instead of
        # exporting them
        assert_or_throw(
            0 <= nrows_out <= first,
            ValueError(
                f"jax cotransformer reported _nrows={nrows_out} outside "
                f"[0, {first}] (its output column length)"
            ),
        )
        target = max(padded_len(nrows_out, ndev), padded_len(first, ndev))
    elif first == S:
        # per-segment output: live segments are the rows, count lazy
        target = padded_len(S, ndev)
        to_pad[alive_key] = alive
        nrows_dev_out = cnt_alive
    elif first == ps[0]:
        # row-aligned with member 0 (validity has dead-segment drops)
        target = ps[0]
        row_valid_out = rv0
        nrows_dev_out = cnt0
    else:
        raise ValueError(
            "jax cotransformer output length must be _num_segments "
            f"({S}), member 0's padded length ({ps[0]}), or come with "
            f"an explicit '_nrows' (got {first})"
        )
    for f in out_schema.fields:
        to_pad[f.name] = out[f.name]
        mk = out.get(f"_{f.name}_mask")
        if mk is not None:
            to_pad[f"_{f.name}_mask"] = mk
    # pad through ONE row-sharded program (eager concatenate/device_put
    # of process-spanning arrays is not multihost-safe)
    sig = tuple(
        (k, str(v.dtype), int(v.shape[0])) for k, v in sorted(to_pad.items())
    )

    def _pad_prog(arrs: Dict[str, Any]) -> Dict[str, Any]:
        return {k: _pad_to(v, target) for k, v in arrs.items()}

    padded = jit_row_sharded(
        mesh, ("comap_pad", target, sig), _pad_prog
    )(to_pad)
    if alive_key in padded:
        row_valid_out = padded[alive_key]
    for f in out_schema.fields:
        mask = padded.get(f"_{f.name}_mask")
        dictionary = None
        if f"_{f.name}_dict" in dict_stash and (
            pa.types.is_string(f.type)
            or pa.types.is_large_string(f.type)
        ):
            dictionary = dict_stash[f"_{f.name}_dict"]
        cols[f.name] = JaxColumn(
            f.type, padded[f.name], mask, dictionary, None
        )
    return JaxDataFrame(
        JaxBlocks(
            nrows_out,
            cols,
            mesh,
            row_valid=row_valid_out,
            nrows_dev=nrows_dev_out,
        ),
        out_schema,
    )


def _empty_dfs(zdf: Any) -> Any:
    from fugue_tpu.jax_backend.zipped import _make_dfs

    return _make_dfs(
        zdf.names, [ArrayDataFrame([], f.schema) for f in zdf.frames]
    )
