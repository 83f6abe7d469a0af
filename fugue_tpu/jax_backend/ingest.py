"""Streamed parquet -> device ingest.

The eager path (``blocks.from_arrow``) decodes the WHOLE arrow table on
host, then ships every column in one ``device_put`` — decode and device
staging are serial, which is why end-to-end parquet pipelines were the
weakest bench config. This module pipelines the two phases:

- the parquet file is read as a stream of record batches
  (``fugue.jax.io.batch_rows`` rows each) through the engine's virtual
  filesystem, so the same code path streams from local disk,
  ``memory://`` or object storage;
- each device-kind column fills a host staging buffer laid out in MESH
  SHARD ORDER; the moment the decode frontier crosses a shard boundary,
  that shard's slice ships to its device with an async ``device_put``
  (per-shard staging) while the NEXT batches keep decoding on host;
- after the last batch, the per-device shards are assembled into one
  global row-sharded array via ``make_array_from_single_device_arrays``
  — no concat program, no extra copy.

String columns dictionary-encode per batch and remap through a running
global dictionary, so codes stream like any numeric column. Integer
stats (min/max) and the monotonic-uniqueness proof are tracked across
batches, matching the eager ingest's metadata exactly.

The result stays LAZY (``JaxDataFrame.from_lazy``): the streamed upload
runs only when a device op first touches ``blocks``; host-only chains
read back through the normal host decode instead.

Fallbacks return None (caller uses the eager path): multi-process
meshes (SPMD ingest needs every host to hold the same array),
hive-partitioned directories, schema-expression column specs, and
non-parquet formats.
"""

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from fugue_tpu.jax_backend import blocks as B
from fugue_tpu.schema import Schema

def _row_groups_surviving(
    md: Any, pruning: List[Any]
) -> Optional[List[int]]:
    """Row groups a conjunctive ``[col, op, literal]`` predicate cannot
    refute via the group's column statistics. Pruning with any SUBSET of
    a conjunction is sound (a refuted conjunct falsifies every row of
    the group; null rows fail comparisons anyway), and statistics-less
    columns simply keep the group. None = statistics unreadable, keep
    everything."""
    try:
        keep: List[int] = []
        for g in range(md.num_row_groups):
            rg = md.row_group(g)
            stats: Dict[str, Any] = {}
            for j in range(rg.num_columns):
                cmeta = rg.column(j)
                st = cmeta.statistics
                if st is not None and st.has_min_max:
                    stats[cmeta.path_in_schema] = st
            alive = True
            for name, op, val in pruning:
                st = stats.get(name)
                if st is None or not isinstance(val, (int, float)):
                    continue
                mn, mx = st.min, st.max
                if not isinstance(mn, (int, float)) or not isinstance(
                    mx, (int, float)
                ):
                    continue
                if (
                    (op == ">" and not mx > val)
                    or (op == ">=" and not mx >= val)
                    or (op == "<" and not mn < val)
                    or (op == "<=" and not mn <= val)
                    or (op == "==" and not (mn <= val <= mx))
                ):
                    alive = False
                    break
            if alive:
                keep.append(g)
        return keep
    except Exception:  # pragma: no cover - stats drift: keep everything
        return None


def try_stream_load(
    engine: Any,
    path: Any,
    format_hint: Optional[str],
    columns: Any,
    batch_rows: int,
    pruning: Optional[List[Any]] = None,
    **kwargs: Any,
) -> Optional[Any]:
    """Build a lazily-streaming JaxDataFrame for a parquet load, or None
    when the input needs the eager path. ``pruning`` (optimizer-attached
    conjunctive ``[col, op, literal]`` triples) skips row groups whose
    parquet statistics refute the predicate — advisory: the downstream
    filter re-applies the full condition."""
    from fugue_tpu.utils.io import infer_format

    if jax.process_count() > 1 or batch_rows <= 0 or len(kwargs) > 0:
        return None
    if isinstance(columns, str):
        return None  # schema-expression select+cast: host owns it
    paths = [path] if isinstance(path, str) else list(path)
    try:
        if infer_format(paths[0], format_hint or None) != "parquet":
            return None
    except NotImplementedError:
        return None
    fs = engine.fs
    files: List[str] = []
    for p in paths:
        if fs.isdir(p):
            children = [
                fs.join(p, f)
                for f in fs.listdir(p)
                if not f.startswith(".") and not f.startswith("_")
            ]
            if len(children) == 0 or any(fs.isdir(c) for c in children):
                return None  # empty or hive-partitioned: eager dataset read
            files.extend(sorted(children))
        else:
            if not fs.exists(p):
                return None  # eager path owns the error message
            files.append(p)

    # metadata pass: row count + arrow schema (+ row-group pruning),
    # no data pages touched
    total_rows = 0
    est_bytes = 0
    arrow_schema: Optional[pa.Schema] = None
    group_meta: List[Any] = []  # (file, [rows/group], [bytes/group], keep)
    for f in files:
        with fs.open_input_stream(f) as fp:
            pf = pq.ParquetFile(fp)
            md = pf.metadata
            if arrow_schema is None:
                arrow_schema = pf.schema_arrow
            elif pf.schema_arrow != arrow_schema:
                # heterogeneous part files (missing/reordered columns,
                # dtype drift): the eager dataset read owns null
                # promotion/unification semantics
                return None
            g_rows = [md.row_group(i).num_rows for i in range(md.num_row_groups)]
            g_bytes = [
                md.row_group(i).total_byte_size
                for i in range(md.num_row_groups)
            ]
            keep = _row_groups_surviving(md, pruning) if pruning else None
            group_meta.append((f, g_rows, g_bytes, keep))
    row_groups: Optional[Dict[str, List[int]]] = None
    if pruning and all(k is not None for _, _, _, k in group_meta):
        pruned_rows = sum(
            sum(rows[g] for g in keep) for _, rows, _, keep in group_meta
        )
        if pruned_rows > 0:
            # an all-groups-refuted load would need empty-frame device
            # shapes the streamed path doesn't model: fall back to the
            # unpruned stream (the filter drops every row anyway)
            row_groups = {f: list(keep) for f, _, _, keep in group_meta}
    for f, g_rows, g_bytes, _ in group_meta:
        sel = row_groups[f] if row_groups is not None else range(len(g_rows))
        total_rows += sum(g_rows[g] for g in sel)
        est_bytes += sum(g_bytes[g] for g in sel)
    assert arrow_schema is not None
    base_schema = arrow_schema
    # provisional placement only (admit=False): the binding admission
    # decision happens in load_blocks at materialization time
    mesh = engine._place(est_bytes, admit=False)[0]
    nrows = total_rows
    from fugue_tpu.jax_backend.dataframe import JaxDataFrame

    def plan(cols_sel: Optional[List[str]]) -> Any:
        """Build the lazy frame for a column selection from the ALREADY
        captured metadata (files, schema, row count) — re-planning a
        narrower select never re-lists the directory or re-reads parquet
        footers."""
        sel = None if cols_sel is None else list(cols_sel)
        a_schema = (
            base_schema
            if sel is None
            else pa.schema([base_schema.field(c) for c in sel])
        )
        schema = Schema(a_schema)

        def load_blocks() -> B.JaxBlocks:
            # re-consult placement AND admission at MATERIALIZATION time:
            # under the fault layer's host-tier degrade override
            # (thread-local, see JaxExecutionEngine.degraded_to_host) the
            # streamed upload must re-place onto the host mesh even
            # though the plan captured the device tier, and the memory
            # governor's watermark/spill decision must see the ledger as
            # it is NOW, not as it was at plan time
            mesh_now, tier = engine._place(est_bytes)
            gate = engine._memory.gate(tier, est_bytes)
            gate.before()
            loaded = _stream_to_blocks(
                fs,
                files,
                schema,
                mesh_now,
                nrows,
                batch_rows,
                sel,
                row_groups,
                first_batch_hook=_first_batch_hook(engine),
            )
            gate.after(loaded)
            return loaded

        def load_table() -> pa.Table:
            tables = []
            for f in files:
                groups = None if row_groups is None else row_groups[f]
                if groups is not None and len(groups) == 0:
                    continue  # every row group refuted: nothing to read
                with fs.open_input_stream(f) as fp:
                    if groups is None:
                        tables.append(pq.read_table(fp, columns=sel))
                    else:
                        tables.append(
                            pq.ParquetFile(fp).read_row_groups(
                                groups, columns=sel
                            )
                        )
            return tables[0] if len(tables) == 1 else pa.concat_tables(tables)

        def load_head(n: int) -> pa.Table:
            """First n rows only: stop reading batches the moment they're
            covered (head/peek on a lazy frame must not decode the file)."""
            batches = []
            remaining = n
            for f in files:
                if remaining <= 0:
                    break
                groups = None if row_groups is None else row_groups[f]
                if groups is not None and len(groups) == 0:
                    continue
                with fs.open_input_stream(f) as fp:
                    pf = pq.ParquetFile(fp)
                    for b in pf.iter_batches(
                        batch_size=max(min(batch_rows, max(n, 1)), 1),
                        columns=sel,
                        row_groups=groups,
                    ):
                        batches.append(b.slice(0, remaining))
                        remaining -= min(b.num_rows, remaining)
                        if remaining <= 0:
                            break
            return pa.Table.from_batches(batches, schema=a_schema)

        return JaxDataFrame.from_lazy(
            load_blocks, load_table, mesh, schema, nrows, load_head, plan
        )

    return plan(list(columns) if columns is not None else None)


def _first_batch_hook(engine: Any) -> Optional[Callable[[], None]]:
    """Pipelined first-batch dispatch (``fugue.jax.io.pipeline``): the
    moment the FIRST record batches are decoded, kick a background warm
    of the persistent-executable cache for this engine's plan signature
    — deserializing the consumer's compiled program overlaps the decode
    and staging of the remaining batches, so the first dispatch after
    assembly is execute-only instead of compile/load-bound. A no-op
    when no cache dir is configured or the warm already ran."""
    from fugue_tpu.constants import (
        FUGUE_CONF_JAX_IO_PIPELINE,
        typed_conf_get,
    )

    try:
        if not typed_conf_get(engine.conf, FUGUE_CONF_JAX_IO_PIPELINE):
            return None
        if not getattr(engine, "_exec_enabled", False):
            return None
    except Exception:  # pragma: no cover - conf-less engine stub
        return None
    return lambda: engine.warm_executables(background=True)


class _ShardStager:
    """Per-column staging buffer that ships each mesh shard to its device
    the moment decode fills it (device_put is async — the transfer
    overlaps the decode of later batches)."""

    def __init__(self, pad_n: int, ndev: int, dtype: Any, fill: Any,
                 devices: List[Any]):
        self.buf = np.full((pad_n,), fill, dtype=dtype)
        self.shard = pad_n // ndev
        self.devices = devices
        self.sent = 0  # number of shards already shipped
        self.parts: List[Any] = []

    def fill_to(self, end: int) -> None:
        """Rows [0, end) are final; ship every fully-decoded shard."""
        while (self.sent + 1) * self.shard <= end:
            lo = self.sent * self.shard
            hi = lo + self.shard
            self.parts.append(
                jax.device_put(self.buf[lo:hi], self.devices[self.sent])
            )
            self.sent += 1

    def finish(self) -> List[Any]:
        self.fill_to(len(self.buf))
        return self.parts


def _stream_to_blocks(
    fs: Any,
    files: List[str],
    schema: Schema,
    mesh: Any,
    nrows: int,
    batch_rows: int,
    columns: Any,
    row_groups: Optional[Dict[str, List[int]]] = None,
    first_batch_hook: Optional[Callable[[], None]] = None,
) -> B.JaxBlocks:
    B.ensure_x64()
    ndev = int(mesh.devices.size)
    pad_n = B.padded_len(nrows, ndev)
    sharding = B.row_sharding(mesh)
    devices = list(mesh.devices.flat)
    cols = list(columns) if columns is not None else None

    device_fields = [f for f in schema.fields if B.is_device_type(f.type)]
    host_chunks: Dict[str, List[pa.Array]] = {
        f.name: [] for f in schema.fields if not B.is_device_type(f.type)
    }
    stagers: Dict[str, _ShardStager] = {}
    mask_stagers: Dict[str, _ShardStager] = {}
    # string state: running global dictionary per column
    dicts: Dict[str, Dict[Any, int]] = {}
    # int stats / uniqueness tracked across batches
    stats: Dict[str, Tuple[int, int]] = {}
    monotonic: Dict[str, Any] = {}

    for f in device_fields:
        tp = f.type
        if pa.types.is_string(tp) or pa.types.is_large_string(tp):
            np_dtype: Any = np.int32
            dicts[f.name] = {}
        else:
            np_dtype = B._np_dtype_for(tp)
        stagers[f.name] = _ShardStager(pad_n, ndev, np_dtype, 0, devices)
        if pa.types.is_integer(tp) and 0 < nrows <= B._UNIQUE_CHECK_MAX:
            # falsified by data / masks below; gated on size like the
            # eager path — never pay the O(n) host check just to discard it
            monotonic[f.name] = True

    offset = 0
    for fname in files:
        groups = None if row_groups is None else row_groups.get(fname)
        if groups is not None and len(groups) == 0:
            continue  # every row group statistically refuted
        with fs.open_input_stream(fname) as fp:
            pf = pq.ParquetFile(fp)
            for batch in pf.iter_batches(
                batch_size=batch_rows, columns=cols, row_groups=groups
            ):
                n = batch.num_rows
                if n == 0:
                    continue
                for f in schema.fields:
                    arr = batch.column(batch.schema.get_field_index(f.name))
                    if f.name in host_chunks:
                        host_chunks[f.name].append(arr)
                        continue
                    _decode_into(
                        f.name, f.type, arr, offset, n,
                        stagers, mask_stagers, dicts, stats, monotonic,
                        pad_n, ndev, devices,
                    )
                offset += n
                end = offset
                for st in stagers.values():
                    st.fill_to(end)
                for st in mask_stagers.values():
                    st.fill_to(end)
                if first_batch_hook is not None:
                    # leading batches are decoded/staged: overlap the
                    # executable warm with the remaining stream
                    hook, first_batch_hook = first_batch_hook, None
                    try:
                        hook()
                    except Exception:  # pragma: no cover - warm is
                        pass  # best-effort, never an ingest error

    out_cols: Dict[str, B.JaxColumn] = {}
    for f in schema.fields:
        tp = f.type
        if f.name in host_chunks:
            out_cols[f.name] = B.JaxColumn(
                tp, B.host_array(pa.chunked_array(host_chunks[f.name], type=tp))
            )
            continue
        data = _assemble(stagers[f.name], (pad_n,), sharding)
        mask = (
            _assemble(mask_stagers[f.name], (pad_n,), sharding)
            if f.name in mask_stagers
            else None
        )
        if f.name in dicts:
            dictionary = np.empty((len(dicts[f.name]),), dtype=object)
            for v, code in dicts[f.name].items():
                dictionary[code] = v
            out_cols[f.name] = B.JaxColumn(
                tp, data, mask, dictionary,
                stats=(0, max(len(dictionary) - 1, 0)),
            )
            continue
        # membership, not truthiness: the stored value is the column's
        # LAST element, which may legitimately be 0/False
        unique = bool(
            mask is None
            and pa.types.is_integer(tp)
            and 0 < nrows <= B._UNIQUE_CHECK_MAX
            and f.name in monotonic
        )
        out_cols[f.name] = B.JaxColumn(
            tp, data, mask, stats=stats.get(f.name), unique=unique
        )
    return B.JaxBlocks(nrows, out_cols, mesh)


def _chunk_view(blocks: B.JaxBlocks, lo: int, hi: int) -> B.JaxBlocks:
    """A zero-copy row-range view of prefix-layout blocks: device
    columns slice lazily on device (the fetch worker materializes them),
    host columns slice their arrow storage. Decode semantics are then
    EXACTLY ``blocks.to_arrow`` on the view — the pipelined save cannot
    diverge from the one-shot conversion."""
    cols: Dict[str, B.JaxColumn] = {}
    for name, col in blocks.columns.items():
        if col.on_device:
            cols[name] = B.JaxColumn(
                col.pa_type,
                col.data[lo:hi],
                None if col.mask is None else col.mask[lo:hi],
                col.host_dict,
                stats=col.stats,
            )
        else:
            cols[name] = B.JaxColumn(col.pa_type, col.data.slice(lo, hi - lo))
    return B.JaxBlocks(hi - lo, cols, blocks.mesh)


def try_pipelined_save(
    engine: Any,
    jdf: Any,
    path: str,
    format_hint: Optional[str],
    mode: str,
    partition_cols: Any,
    batch_rows: int,
    kwargs: Dict[str, Any],
) -> bool:
    """Overlap row-group writes with the tail of compute: the result
    frame is fetched to host CHUNK BY CHUNK on a prefetch worker (device
    slice + transfer of chunk k+1 runs while chunk k parquet-encodes and
    writes), so the save's host encode no longer waits for the full
    device readback. Returns False when the target/frame needs one of
    the general paths (non-parquet, dir targets, append concat, masked
    layout, pending/lazy frames) — the caller then uses the eager save.
    Row content and order are identical by construction (parity-tested):
    each chunk decodes through the same ``blocks.to_arrow``."""
    from fugue_tpu.constants import (
        FUGUE_CONF_JAX_IO_PIPELINE,
        typed_conf_get,
    )
    from fugue_tpu.utils.io import infer_format

    if batch_rows <= 0 or partition_cols:
        return False
    try:
        if not typed_conf_get(engine.conf, FUGUE_CONF_JAX_IO_PIPELINE):
            return False
    except Exception:  # pragma: no cover - conf-less engine stub
        return False
    try:
        if infer_format(path, format_hint or None) != "parquet":
            return False
    except NotImplementedError:
        return False
    if mode not in ("overwrite", "error"):
        return False  # append reads + concats the old artifact: host path
    if jdf._blocks is None:
        return False  # pending/lazy frame: no device tail to overlap
    blocks = jdf._blocks
    if blocks.row_valid is not None or not blocks.nrows_known:
        return False  # masked layout compacts in to_arrow: one-shot path
    nrows = blocks.nrows
    if nrows <= 0:
        return False
    fs = engine.fs
    if fs.exists(path):
        if mode == "error":
            raise FileExistsError(path)
        if fs.isdir(path):
            return False  # dir targets need the pre-delete semantics
    schema = jdf.schema
    # same contract as utils/io.save_df: batch_rows is OUR streaming
    # knob, never a pyarrow writer kwarg (here the chunking already
    # bounds row groups at batch_rows)
    kwargs = {k: v for k, v in kwargs.items() if k != "batch_rows"}
    spans = [
        (lo, min(lo + batch_rows, nrows))
        for lo in range(0, nrows, batch_rows)
    ]
    from concurrent.futures import ThreadPoolExecutor

    def fetch(span: Tuple[int, int]) -> pa.Table:
        lo, hi = span
        return B.to_arrow(_chunk_view(blocks, lo, hi), schema)

    with ThreadPoolExecutor(
        1, thread_name_prefix="fugue-save-fetch"
    ) as pool:

        def write_all(fp: Any) -> None:
            writer = None
            try:
                fut = pool.submit(fetch, spans[0])
                for i in range(len(spans)):
                    table = fut.result()
                    if i + 1 < len(spans):
                        fut = pool.submit(fetch, spans[i + 1])
                    if writer is None:
                        writer = pq.ParquetWriter(
                            fp, table.schema, **kwargs
                        )
                    writer.write_table(table)
            finally:
                if writer is not None:
                    writer.close()

        fs.write_file_atomic(path, write_all)
    return True


def _assemble(stager: _ShardStager, shape: Tuple[int, ...], sharding: Any) -> Any:
    parts = stager.finish()
    # order the shards by each device's row range in the sharding
    idx_map = sharding.addressable_devices_indices_map(shape)
    by_dev = {d: p for d, p in zip(stager.devices, parts)}
    ordered = [by_dev[d] for d in idx_map.keys()]
    return jax.make_array_from_single_device_arrays(shape, sharding, ordered)


def _decode_into(
    name: str,
    tp: pa.DataType,
    arr: pa.Array,
    offset: int,
    n: int,
    stagers: Dict[str, _ShardStager],
    mask_stagers: Dict[str, _ShardStager],
    dicts: Dict[str, Dict[Any, int]],
    stats: Dict[str, Tuple[int, int]],
    monotonic: Dict[str, Any],
    pad_n: int,
    ndev: int,
    devices: List[Any],
) -> None:
    """Decode one record-batch column into the staging buffers (the
    per-batch mirror of blocks.from_arrow's whole-table decode)."""
    buf = stagers[name].buf
    if pa.types.is_string(tp) or pa.types.is_large_string(tp):
        enc = arr.dictionary_encode()
        codes_np = enc.indices.to_numpy(zero_copy_only=False)
        import pandas as pd

        valid = ~pd.isna(codes_np)
        local_codes = np.where(valid, np.nan_to_num(codes_np, nan=0), 0).astype(
            np.int64
        )
        gdict = dicts[name]
        remap = np.empty((len(enc.dictionary),), dtype=np.int32)
        for i, v in enumerate(enc.dictionary.to_pylist()):
            code = gdict.get(v)
            if code is None:
                code = len(gdict)
                gdict[v] = code
            remap[i] = code
        buf[offset:offset + n] = (
            remap[local_codes] if len(remap) > 0 else 0
        )
        _mask_write(name, valid, offset, n, arr.null_count > 0,
                    mask_stagers, pad_n, ndev, devices, stagers)
        return
    np_dtype = B._np_dtype_for(tp)
    null_count = arr.null_count
    values = B.decode_device_values(arr, tp)
    if null_count > 0:
        import pyarrow.compute as pc

        valid = pc.is_valid(arr).to_numpy(zero_copy_only=False)
        if values.dtype.kind == "f" and not np.issubdtype(np_dtype, np.floating):
            values = np.nan_to_num(values)
        filled = np.where(valid, values, 0).astype(np_dtype)
        _mask_write(name, valid.astype(np.bool_), offset, n, True,
                    mask_stagers, pad_n, ndev, devices, stagers)
        monotonic.pop(name, None)  # masked ints don't claim uniqueness
    else:
        filled = np.ascontiguousarray(values, dtype=np_dtype)
        if name in mask_stagers:  # earlier batches had nulls
            _mask_write(name, np.ones((n,), dtype=np.bool_), offset, n, True,
                        mask_stagers, pad_n, ndev, devices, stagers)
    buf[offset:offset + n] = filled
    s = B._int_like_stats(filled, tp)
    if s is not None:
        prev = stats.get(name)
        stats[name] = s if prev is None else (
            min(prev[0], s[0]), max(prev[1], s[1])
        )
    if name in monotonic and filled.dtype.kind in "iu" and n > 0:
        prev_last = monotonic[name]
        ok = bool((filled[1:] > filled[:-1]).all()) if n > 1 else True
        if prev_last is not True and prev_last is not False:
            ok = ok and filled[0] > prev_last
        if not ok:
            monotonic.pop(name, None)
        else:
            monotonic[name] = filled[-1]


def _mask_write(
    name: str,
    valid: np.ndarray,
    offset: int,
    n: int,
    has_nulls: bool,
    mask_stagers: Dict[str, _ShardStager],
    pad_n: int,
    ndev: int,
    devices: List[Any],
    stagers: Dict[str, _ShardStager],
) -> None:
    """Write a batch's validity into the column's mask stager, creating
    it on first need. A mask that appears MID-STREAM (first nulls in a
    late batch) backfills earlier rows as valid — but any already-shipped
    shard can't gain a mask, so creation is only allowed while no shard
    has shipped without one; otherwise the earlier shards' all-valid
    mask is reconstructed here before the new batch writes."""
    if name not in mask_stagers and not has_nulls:
        return
    st = mask_stagers.get(name)
    if st is None:
        st = _ShardStager(pad_n, ndev, np.bool_, False, devices)
        st.buf[:offset] = True  # earlier batches were fully valid
        # ship the backfilled shards the data stager already shipped so
        # both stagers stay in lockstep
        st.fill_to(stagers[name].sent * stagers[name].shard)
        mask_stagers[name] = st
    st.buf[offset:offset + n] = valid
