"""Device block layer: dataframe columns as sharded jax.Arrays on a mesh.

The TPU-native columnar format (BASELINE north star: "partitions live as
sharded jax.Array blocks on a TPU pod mesh"):

- numeric/bool columns  -> jax.Array (+ bool validity mask when nulls exist)
- timestamp             -> int64 microseconds since epoch
- date                  -> int32 days since epoch
- string                -> dictionary-encoded: int32 codes on device, the
                           dictionary (np object array) on host
- anything else (nested, binary, decimal) -> host arrow column

Rows are padded to a multiple of the mesh size. Row membership has TWO
layouts: *prefix* (rows [0, nrows) are real — the ingest layout) and
*masked* (a device bool ``row_valid`` marks real rows — produced by filter/
dropna/distinct/aggregate so those ops never synchronize with the host).
A frame's true row count may therefore be LAZY: a device scalar that is
only read back when the host actually needs the number (count(), arrow
export). This is the core of the engine's latency design: every host
sync stalls the dispatch queue until the device drains, so the whole
pipeline compiles to a chain of async dispatches with a single sync at
the host boundary.

Integer-like columns carry host-known (min, max) ``stats`` captured at
ingest and propagated through gathers/passthroughs; they let group-by key
factorization choose static bin counts without reading bounds back from
the device (see groupby.py).

All device arrays are placed with ``NamedSharding(mesh, P("p"))`` over the
leading (row) axis so jit-compiled ops auto-partition and XLA inserts ICI
collectives (scaling-book recipe: pick a mesh, annotate shardings, let XLA
do the rest).
"""

import logging
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fugue_tpu.schema import Schema
from fugue_tpu.testing.retrace import active_retrace_sentinel
from fugue_tpu.utils.assertion import assert_or_throw

_EPOCH = np.datetime64(0, "us")
_LOG = logging.getLogger("fugue_tpu.jax")


def ensure_x64() -> None:
    """Enable 64-bit dtypes (required for long/timestamp column fidelity:
    without x64, device_put silently truncates int64 -> int32).

    Called from engine/mesh/ingest entry points rather than at import time
    so importing fugue_tpu.jax_backend does not mutate global jax config
    for unrelated code (advisor finding r1). Opt out with
    FUGUE_TPU_DISABLE_X64=1 if every column fits 32 bits."""
    import os

    if os.environ.get("FUGUE_TPU_DISABLE_X64", "").lower() in ("1", "true"):
        return
    if not jax.config.jax_enable_x64:
        _LOG.info(
            "fugue_tpu: enabling jax_enable_x64 for 64-bit column fidelity"
        )
        jax.config.update("jax_enable_x64", True)


class JaxColumn:
    """One column: device data + optional mask, or a host arrow fallback.

    ``stats`` is an optional host-known (min, max) int pair bounding the
    VALID values of an integer-like column (a superset bound is fine);
    ``dictionary`` holds the decode table for string columns. ``unique``
    is a host-known guarantee that the column's VALID values are
    pairwise distinct (captured at ingest for strictly monotonic integer
    keys — the dimension-table surrogate-key pattern); it stays sound
    under row filtering (a subset of distinct values is distinct) and is
    dropped by every transformation that could duplicate values."""

    def __init__(
        self,
        pa_type: pa.DataType,
        data: Any,  # jax.Array (device kinds) or pa.ChunkedArray (host kind)
        mask: Optional[Any] = None,  # jax bool array, True = valid
        dictionary: Optional[np.ndarray] = None,  # for string kind
        stats: Optional[Tuple[int, int]] = None,  # host-known (min, max)
        unique: bool = False,
    ):
        self.pa_type = pa_type
        self.data = data
        self.mask = mask
        self.dictionary = dictionary
        self.stats = stats
        self.unique = unique

    @property
    def on_device(self) -> bool:
        return not isinstance(self.data, (pa.ChunkedArray, pa.Array))

    @property
    def is_string(self) -> bool:
        return self.dictionary is not None

    def with_data(
        self, data: Any, mask: Optional[Any], keep_stats: bool = True
    ) -> "JaxColumn":
        """Same logical column, new storage (e.g. after a row gather —
        a subset of rows keeps the same value bounds and dictionary)."""
        return JaxColumn(
            self.pa_type,
            data,
            mask,
            self.dictionary,
            self.stats if keep_stats else None,
        )


def is_device_type(tp: pa.DataType) -> bool:
    return (
        pa.types.is_integer(tp)
        or pa.types.is_floating(tp)
        or pa.types.is_boolean(tp)
        or pa.types.is_timestamp(tp)
        or pa.types.is_date32(tp)
        or pa.types.is_string(tp)
        or pa.types.is_large_string(tp)
    )


def _np_dtype_for(tp: pa.DataType) -> Any:
    if pa.types.is_timestamp(tp):
        return np.int64
    if pa.types.is_date32(tp):
        return np.int32
    if pa.types.is_boolean(tp):
        return np.bool_
    return tp.to_pandas_dtype()


def make_mesh(devices: Optional[List[Any]] = None) -> Mesh:
    ensure_x64()
    devs = devices if devices is not None else jax.devices()
    return Mesh(np.array(devs), axis_names=("p",))


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("p"))


# Compiled row-sharded programs are cached ON their mesh object (one
# dict per mesh) instead of in a module-global keyed by the mesh: each
# cached program's out_sharding holds a strong reference back to its
# mesh, so a global would root every mesh it ever saw — dead meshes
# (fleet replica churn, per-test engines) would leak their compiled
# programs forever, and no finalizer could fire to stop it. Attached to
# the mesh, cache + programs + mesh form one cycle the ordinary GC
# reclaims the moment the last outside reference drops. The weak
# registry below only observes which meshes currently carry a cache
# (tests assert it stays weak and that no module global here strongly
# roots a mesh or its programs).
_JIT_ROW_SHARDED_ATTR = "_fugue_jit_row_sharded_cache"
_JIT_ROW_SHARDED_MESHES: Any = weakref.WeakSet()


def jit_row_sharded(mesh: Mesh, key: Any, fn: Any) -> Any:
    """Jit ``fn`` with every output constrained to the mesh's row
    sharding, cached per (mesh, key). This is the multihost-safe way to
    CREATE row-axis arrays outside engine programs: eager jnp creations
    commit to one process-local device, and ``device_put`` onto a
    process-spanning sharding is a cross-host reshard jax refuses on CPU
    meshes. Callers must pass HOST scalars (np.int32, not jnp) so inputs
    never carry a single-device commitment either."""
    per_mesh = getattr(mesh, _JIT_ROW_SHARDED_ATTR, None)
    if per_mesh is None:
        per_mesh = {}
        setattr(mesh, _JIT_ROW_SHARDED_ATTR, per_mesh)
        _JIT_ROW_SHARDED_MESHES.add(mesh)
    prog = per_mesh.get(key)
    if prog is None:
        prog = jax.jit(fn, out_shardings=row_sharding(mesh))
        per_mesh[key] = prog
    san = active_retrace_sentinel()
    if san is None:
        return prog
    return _sentineled_dispatch(san, key, prog)


def _sentineled_dispatch(san: Any, key: Any, prog: Any) -> Any:
    """Retrace-sentinel shim over one row-sharded program: a dispatch
    that grew jax's per-shape cache was an actual XLA trace, counted
    against the program key's budget. Only ever constructed while the
    debug sentinel is armed — the disarmed path returns the raw jitted
    handle untouched."""
    name = "row_sharded:" + (
        str(key[0]) if isinstance(key, tuple) and key else str(key)
    )

    def _watched(*args: Any, **kwargs: Any) -> Any:
        sizer = getattr(prog, "_cache_size", None)
        before = -1
        if sizer is not None:
            try:
                before = sizer()
            except Exception:  # pragma: no cover - jax version drift
                sizer = None
        out = prog(*args, **kwargs)
        if sizer is not None:
            try:
                traced = sizer() > before
            except Exception:  # pragma: no cover
                traced = False
            if traced:
                ev = san.note_trace(name, key, args)
                san.raise_if_armed(ev)
        return out

    return _watched


def on_mesh(mesh: Mesh) -> Any:
    """Context manager pinning EAGER jnp array creation to the mesh's
    backend. Without it, eager ``jnp.arange``/``ones``/``concatenate``
    land on the process default device — on a TPU process operating a
    HOST-tier frame that silently bounces arrays across the host<->device
    link and back. Jitted programs don't need this: they follow their
    inputs' placement."""
    return jax.default_device(mesh.devices.flat[0])


def padded_len(n: int, ndev: int) -> int:
    if n == 0:
        return ndev
    return ((n + ndev - 1) // ndev) * ndev


def put_sharded(arr: np.ndarray, sharding: NamedSharding) -> Any:
    """Host numpy -> sharded device array. Single-process: a plain
    ``device_put``. Multi-process (after ``init_distributed``): every
    process holds the same host array (SPMD ingest) and contributes only
    its ADDRESSABLE shards via ``make_array_from_callback`` — device_put
    cannot place onto non-addressable devices."""
    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )
    return jax.device_put(arr, sharding)


class JaxBlocks:
    """All columns of a frame + row membership.

    Invariant: either ``row_valid`` is a device bool array over the padded
    rows (masked layout; ``nrows`` may be lazy — a pending device scalar),
    or ``row_valid`` is None and ``nrows`` is a known int with prefix
    layout (rows [0, nrows) real)."""

    def __init__(
        self,
        nrows: Optional[int],
        columns: Dict[str, JaxColumn],
        mesh: Mesh,
        row_valid: Optional[Any] = None,
        nrows_dev: Optional[Any] = None,
    ):
        assert_or_throw(
            nrows is not None or row_valid is not None,
            ValueError("lazy nrows requires a row_valid mask"),
        )
        self._nrows = nrows
        self._nrows_dev = nrows_dev
        self.columns = columns
        self.mesh = mesh
        self.row_valid = row_valid
        # per-frame cache of key factorizations (see groupby.factorize_keys)
        self.factorize_cache: Dict[Any, Any] = {}
        # device-loss bookkeeping: ``lost`` marks a frame whose shards
        # died with a device and could not be rebuilt (touching it fails
        # the owning query with DeviceLostError); ``lineage`` optionally
        # holds a zero-arg loader returning a fresh arrow table — the
        # recoverable provenance (lazy ingest plan, checkpoint artifact,
        # pinned lake:// version) recovery re-materializes from
        self.lost = False
        self.lineage: Optional[Any] = None

    @property
    def nrows(self) -> int:
        """True row count; synchronizes with the device if lazy."""
        if self._nrows is None:
            if self._nrows_dev is not None:
                self._nrows = int(self._nrows_dev)
            else:
                self._nrows = int(jnp.sum(self.row_valid))
        return self._nrows

    @property
    def nrows_known(self) -> bool:
        return self._nrows is not None

    @property
    def nrows_scalar(self) -> Any:
        """Row count usable inside traced programs without a host sync."""
        if self._nrows is not None:
            return jnp.int32(self._nrows)
        if self._nrows_dev is not None:
            return self._nrows_dev.astype(jnp.int32)
        return jnp.sum(self.row_valid).astype(jnp.int32)

    @property
    def all_on_device(self) -> bool:
        return all(c.on_device for c in self.columns.values())

    @property
    def padded_nrows(self) -> int:
        for c in self.columns.values():
            if c.on_device:
                return int(c.data.shape[0])
        return self.nrows

    def validity(self) -> jnp.ndarray:
        """Device bool array over padded rows: True = real row. Built by
        a row-sharded jitted program so the mask is a GLOBAL array on
        multi-process meshes (an eager arange commits to one local
        device, and device_put cannot reshard across hosts)."""
        if self.row_valid is not None:
            return self.row_valid
        pad_n = self.padded_nrows
        prog = jit_row_sharded(
            self.mesh,
            ("validity", pad_n),
            lambda n: jnp.arange(pad_n, dtype=jnp.int32) < n,
        )
        return prog(np.int32(self._nrows))

    @property
    def is_prefix_layout(self) -> bool:
        return self.row_valid is None


def residency_arrays(blocks: JaxBlocks) -> List[Any]:
    """EVERY device array a frame owns: column data, column validity
    masks, and the row_valid mask. This is the set persist() and an
    honest bench endpoint must ``block_until_ready`` on — an array left
    out could still be staging when the caller starts its clock."""
    arrs: List[Any] = []
    for c in blocks.columns.values():
        if c.on_device:
            arrs.append(c.data)
            if c.mask is not None:
                arrs.append(c.mask)
    if blocks.row_valid is not None:
        arrs.append(blocks.row_valid)
    return arrs


def device_nbytes(blocks: JaxBlocks) -> int:
    """A frame's REAL device-tier footprint: the byte sum over every
    device array it owns (column data, validity masks, row_valid). This
    is the number the memory governor's ledger registers — tests assert
    ledger parity against it, so it must stay in lockstep with
    :func:`residency_arrays`."""
    return sum(int(a.nbytes) for a in residency_arrays(blocks))


def _int_like_stats(
    values: np.ndarray, tp: pa.DataType
) -> Optional[Tuple[int, int]]:
    """Host-side (min, max) bound for integer-like ingest data. The array
    is already null-filled with 0, so the bound is a superset of the valid
    values — exactly what bin factorization needs."""
    if values.size == 0:
        return (0, 0)
    if values.dtype == np.bool_:
        return (0, 1)
    if values.dtype.kind in "iu":
        return (int(values.min()), int(values.max()))
    return None


def decode_device_values(arr: Any, tp: pa.DataType) -> np.ndarray:
    """Arrow array/chunked-array -> raw numpy values for a device-kind
    non-string column (timestamps to int64 us-since-epoch, date32 to
    int32 days; null positions arrive as NaN/NaT and are filled by the
    caller). THE canonical decode — the eager ingest (:func:`from_arrow`)
    and the streamed per-batch ingest (ingest._decode_into) must stay
    value-identical, so both call this."""
    if pa.types.is_timestamp(tp):
        values = arr.cast(pa.timestamp("us")).to_numpy(zero_copy_only=False)
        return (values.astype("datetime64[us]") - _EPOCH).astype(np.int64)
    if pa.types.is_date32(tp):
        values = arr.to_numpy(zero_copy_only=False)
        values = (
            values.astype("datetime64[D]").astype("datetime64[us]") - _EPOCH
        ).astype(np.int64) // 86_400_000_000
        return values.astype(np.int32)
    return arr.to_numpy(zero_copy_only=False)


def from_arrow(table: pa.Table, schema: Schema, mesh: Mesh) -> JaxBlocks:
    """Arrow -> device blocks (pads rows, encodes strings, builds masks,
    captures host-side key stats)."""
    ensure_x64()
    ndev = mesh.devices.size
    n = table.num_rows
    pad_n = padded_len(n, ndev)
    sharding = row_sharding(mesh)
    cols: Dict[str, JaxColumn] = {}
    for field in schema.fields:
        arr = table.column(field.name)
        tp = field.type
        if not is_device_type(tp):
            cols[field.name] = JaxColumn(tp, arr.combine_chunks())
            continue
        if pa.types.is_string(tp) or pa.types.is_large_string(tp):
            enc = arr.combine_chunks().dictionary_encode()
            codes_np = enc.indices.to_numpy(zero_copy_only=False)
            valid = ~pd.isna(codes_np)
            codes = np.where(valid, np.nan_to_num(codes_np, nan=0), 0).astype(
                np.int32
            )
            dictionary = np.asarray(enc.dictionary.to_pylist(), dtype=object)
            data = _pad(codes, pad_n, 0)
            mask = _pad(valid.astype(np.bool_), pad_n, False)
            cols[field.name] = JaxColumn(
                tp,
                put_sharded(data, sharding),
                put_sharded(mask, sharding),
                dictionary,
                stats=(0, max(len(dictionary) - 1, 0)),
            )
            continue
        np_dtype = _np_dtype_for(tp)
        combined = arr.combine_chunks()
        null_count = combined.null_count
        values = decode_device_values(combined, tp)
        if null_count > 0:
            import pyarrow.compute as pc

            valid = pc.is_valid(combined).to_numpy(zero_copy_only=False)
            # int columns with nulls surface as float+NaN from to_numpy
            if values.dtype.kind == "f" and not np.issubdtype(
                np_dtype, np.floating
            ):
                values = np.nan_to_num(values)
            filled = np.where(valid, values, 0).astype(np_dtype)
            mask_arr: Optional[Any] = put_sharded(
                _pad(valid.astype(np.bool_), pad_n, False), sharding
            )
            data = _pad(filled, pad_n, 0)
            stats = _int_like_stats(filled, tp)
        else:
            mask_arr = None
            data = _pad(np.ascontiguousarray(values, dtype=np_dtype), pad_n, 0)
            stats = _int_like_stats(data[:n] if n > 0 else data[:0], tp)
        unique = False
        if (
            mask_arr is None
            and pa.types.is_integer(tp)
            and 0 < n <= _UNIQUE_CHECK_MAX
        ):
            # strictly monotonic integer keys (the dim-table surrogate-key
            # pattern) are provably unique — unlocks the sync-free
            # unique-right join fast path (relational.expand_join).
            # element-wise comparison, NOT np.diff: subtraction wraps for
            # unsigned/extreme values and would falsely prove uniqueness
            unique = bool((data[1:n] > data[: n - 1]).all())
        cols[field.name] = JaxColumn(
            tp, put_sharded(data, sharding), mask_arr, stats=stats,
            unique=unique,
        )
    return JaxBlocks(n, cols, mesh)


_UNIQUE_CHECK_MAX = 4_000_000  # O(n) host check only for dim-table sizes


def _pad(arr: np.ndarray, target: int, fill: Any) -> np.ndarray:
    if arr.shape[0] == target:
        return arr
    out = np.full((target,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def to_arrow(blocks: JaxBlocks, schema: Schema) -> pa.Table:
    """Device blocks -> arrow (host gather, mask->null, dict decode).

    This is THE host boundary: masked-layout frames are compacted here with
    one readback of the validity mask; all lazy row counts materialize.
    All device columns transfer in ONE async wave (per-array readbacks
    would each pay a full device sync)."""
    for col in blocks.columns.values():
        if col.on_device:
            col.data.copy_to_host_async()
            if col.mask is not None:
                col.mask.copy_to_host_async()
    take: Optional[np.ndarray] = None
    if blocks.row_valid is not None:
        blocks.row_valid.copy_to_host_async()
        valid_np = np.asarray(blocks.row_valid)
        take = np.nonzero(valid_np)[0]
        n = int(take.shape[0])
        blocks._nrows = n  # materialized for free
    else:
        n = blocks.nrows
    arrays = []
    for field in schema.fields:
        col = blocks.columns[field.name]
        tp = field.type
        if not col.on_device:
            host = col.data
            if take is not None:
                host = host.take(pa.array(take))
            elif hasattr(host, "slice"):
                host = host.slice(0, n)
            arrays.append(host)
            continue
        full = np.asarray(col.data)
        values = full[take] if take is not None else full[:n]
        if col.mask is not None:
            m_full = ~np.asarray(col.mask)
            mask_np = m_full[take] if take is not None else m_full[:n]
        else:
            mask_np = None
        if col.is_string:
            # dictionary fast path: wrap the codes in an arrow
            # DictionaryArray and cast — arrow's C++ expand is ~8x faster
            # than numpy object-space decode (12ms vs 98ms at 2M rows)
            indices = pa.array(
                values.astype(np.int32, copy=False), mask=mask_np
            )
            da = pa.DictionaryArray.from_arrays(
                indices, pa.array(col.dictionary, type=pa.string())
            )
            arrays.append(da.cast(tp))
            continue
        if pa.types.is_timestamp(tp):
            ts = (values.astype(np.int64)).astype("datetime64[us]")
            arrays.append(
                pa.array(ts, type=pa.timestamp("us"), from_pandas=True).cast(tp)
                if mask_np is None
                else pa.array(
                    np.ma.masked_array(ts, mask=mask_np)  # type: ignore
                ).cast(tp)
            )
            continue
        if pa.types.is_date32(tp):
            days = values.astype(np.int32)
            arrays.append(
                pa.array(days, type=pa.int32()).cast(pa.date32())
                if mask_np is None
                else pa.Array.from_pandas(
                    pd.Series(days).mask(mask_np), type=pa.int32()
                ).cast(pa.date32())
            )
            continue
        if mask_np is None:
            arrays.append(pa.array(values, type=tp))
        else:
            arrays.append(
                pa.Array.from_pandas(
                    pd.Series(values).mask(mask_np), type=tp
                )
            )
    return pa.Table.from_arrays(arrays, schema=schema.pa_schema)


def blocks_schema(blocks: JaxBlocks) -> Schema:
    """A frame's schema as derived from its own columns (arrow types are
    authoritative on every JaxColumn). Used when no external Schema is
    at hand — e.g. device-loss evacuation of an anonymous frame."""
    return Schema(
        pa.schema(
            [pa.field(n, c.pa_type) for n, c in blocks.columns.items()]
        )
    )


def evacuate_blocks(
    blocks: JaxBlocks, mesh: Mesh, schema: Optional[Schema] = None
) -> None:
    """Rebuild a frame's storage onto ``mesh`` IN PLACE via an arrow
    round trip, preserving logical content exactly (row membership
    compacts, strings re-encode). In place because callers across the
    engine (catalog tables, session views, in-flight queries) hold
    references to THIS JaxBlocks object — recovery must heal them all,
    not just ones it can find.

    An arrow round trip rather than a device-to-device resharding:
    the old padding (a multiple of the dead mesh's size) is generally
    not divisible by the survivor count, and the source sharding spans
    a device that no longer answers — the host is the only safe relay.
    Raises if the dead device's shards are already unreadable; the
    caller then falls back to the frame's lineage."""
    sch = schema if schema is not None else blocks_schema(blocks)
    table = to_arrow(blocks, sch)
    fresh = from_arrow(table, sch, mesh)
    replace_blocks(blocks, fresh)


def replace_blocks(blocks: JaxBlocks, fresh: JaxBlocks) -> None:
    """Swap ``blocks``'s storage for ``fresh``'s in place (same logical
    frame, new arrays/mesh). Derived caches reset; the ``lost`` flag
    clears — the frame is healthy again."""
    blocks.columns = fresh.columns
    blocks.mesh = fresh.mesh
    blocks.row_valid = fresh.row_valid
    blocks._nrows = fresh._nrows
    blocks._nrows_dev = fresh._nrows_dev
    blocks.factorize_cache.clear()
    blocks.lost = False


def gather_indices(blocks: JaxBlocks, idx: Any, schema: Schema) -> JaxBlocks:
    """Row-gather every device column in ONE jitted dispatch (host columns
    via arrow take). ``idx`` must index real rows only."""
    mesh = blocks.mesh
    ndev = mesh.devices.size
    new_n = int(idx.shape[0])
    pad_n = padded_len(new_n, ndev)
    sharding = row_sharding(mesh)
    device_cols = {n: c for n, c in blocks.columns.items() if c.on_device}
    datas = {n: c.data for n, c in device_cols.items()}
    masks = {n: c.mask for n, c in device_cols.items() if c.mask is not None}
    with on_mesh(mesh):
        idx_dev = jnp.asarray(idx)
    out_d, out_m = _gather_program(pad_n)(datas, masks, idx_dev)
    cols: Dict[str, JaxColumn] = {}
    for name, col in blocks.columns.items():
        if not col.on_device:
            taken = col.data.take(pa.array(np.asarray(idx)))
            cols[name] = JaxColumn(col.pa_type, taken)
            continue
        cols[name] = col.with_data(
            jax.device_put(out_d[name], sharding),
            None
            if name not in out_m
            else jax.device_put(out_m[name], sharding),
        )
    return JaxBlocks(new_n, cols, mesh)


_GATHER_CACHE: Dict[int, Any] = {}


def _gather_program(pad_n: int) -> Any:
    """Jitted multi-column gather; padding rows repeat index 0 (garbage by
    convention — consumers respect the frame's row membership)."""
    if pad_n not in _GATHER_CACHE:

        @jax.jit
        def _gather(
            datas: Dict[str, jnp.ndarray],
            masks: Dict[str, jnp.ndarray],
            idx: jnp.ndarray,
        ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
            n = idx.shape[0]
            if n != pad_n:
                idx = jnp.concatenate(
                    [idx, jnp.zeros((pad_n - n,), dtype=idx.dtype)]
                )
            return (
                {k: v[idx] for k, v in datas.items()},
                {k: v[idx] for k, v in masks.items()},
            )

        _GATHER_CACHE[pad_n] = _gather
    return _GATHER_CACHE[pad_n]
