"""JaxDataFrame: a DataFrame whose columns live as sharded jax.Arrays
(the ``fugue_jax`` sibling-backend dataframe of the BASELINE north star;
structural parity role: fugue_spark/dataframe.py:38 etc.)."""

from typing import Any, Dict, Iterable, List, NamedTuple, Optional

import pandas as pd
import pyarrow as pa

from fugue_tpu.dataframe import ArrowDataFrame, DataFrame, LocalBoundedDataFrame
from fugue_tpu.dataframe.arrow_utils import cast_table
from fugue_tpu.jax_backend.blocks import (
    JaxBlocks,
    JaxColumn,
    from_arrow,
    to_arrow,
)
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw


class _LazyState(NamedTuple):
    """Loaders for a frame still sitting in storage (streamed ingest)."""

    load_blocks: Any  # () -> JaxBlocks: stream batches straight to mesh
    load_table: Any  # () -> pa.Table: host-only full decode
    mesh: Any
    nrows: int  # from file metadata: count is free
    load_head: Any  # (n) -> pa.Table reading only leading batches, or None
    narrow: Any  # (cols) -> JaxDataFrame re-planned column subset, or None


class JaxDataFrame(DataFrame):
    """Columnar, device-resident, mesh-sharded dataframe.

    Ingestion is LAZY: a frame built :meth:`from_table` keeps the arrow
    table and uploads to the mesh only when a device op first touches
    :attr:`blocks`. Host-path chains (host-fallback maps, string
    transforms, immediate ``as_local``) therefore never pay a
    host->device->host copy of the whole frame. Once blocks materialize,
    the host copy is dropped (no double-residency); columns are immutable
    so the pending table is always an exact image of the frame."""

    def __init__(self, blocks: JaxBlocks, schema: Schema):
        super().__init__(schema)
        self._blocks: Optional[JaxBlocks] = blocks
        self._pending: Optional[Any] = None  # (pa.Table, mesh) before upload
        # (load_blocks, load_table, mesh, nrows) for storage-lazy frames
        self._lazy: Optional[Any] = None
        # memory-governance admission ticket (memory.AllocationGate) set
        # by the engine on governed pending frames; consumed at blocks
        # materialization
        self._mem_gate: Optional[Any] = None
        # () -> pa.Table reload plan set by engine.load_df on
        # storage-backed frames; becomes blocks.lineage at
        # materialization so device-loss recovery can re-read the
        # artifact (see engine.recover_from_device_loss)
        self._lineage_loader: Optional[Any] = None

    @staticmethod
    def from_table(table: pa.Table, mesh: Any, schema: Optional[Schema] = None) -> "JaxDataFrame":
        schema = Schema(table.schema) if schema is None else schema
        res = JaxDataFrame.__new__(JaxDataFrame)
        DataFrame.__init__(res, schema)
        res._blocks = None
        res._pending = (table, mesh)
        res._lazy = None
        res._mem_gate = None
        res._lineage_loader = None
        return res

    @staticmethod
    def from_lazy(
        load_blocks: Any,
        load_table: Any,
        mesh: Any,
        schema: Schema,
        nrows: int,
        load_head: Any = None,
        narrow: Any = None,
    ) -> "JaxDataFrame":
        """A frame still sitting IN STORAGE (streamed parquet ingest):
        ``load_blocks()`` streams record batches straight to the mesh
        when a device op first touches :attr:`blocks`; ``load_table()``
        is the host-only decode used by ``as_arrow`` chains that never
        need the device copy; ``load_head(n)`` (optional) reads only the
        leading batches so ``head``/``peek`` never decode the whole
        file; ``narrow(cols)`` (optional) re-plans the load over a
        column subset so selects prune decode/staging at the source.
        ``nrows`` comes from file metadata, so ``count`` is free in
        every state."""
        res = JaxDataFrame.__new__(JaxDataFrame)
        DataFrame.__init__(res, schema)
        res._blocks = None
        res._pending = None
        res._lazy = _LazyState(
            load_blocks, load_table, mesh, nrows, load_head, narrow
        )
        res._mem_gate = None
        res._lineage_loader = None
        return res

    @property
    def is_pending(self) -> bool:
        """True while the data only lives on host/storage (no device
        copy yet)."""
        return self._blocks is None

    @property
    def native(self) -> JaxBlocks:
        return self.blocks

    @property
    def blocks(self) -> JaxBlocks:
        if self._blocks is None:
            # governance runs at MATERIALIZATION time: before() may spill
            # LRU persisted frames to make room (and hosts the
            # device.alloc fault site); after() registers the real
            # footprint. A raised alloc failure leaves the gate armed so
            # a later touch is still governed.
            gate = getattr(self, "_mem_gate", None)
            if gate is not None:
                gate.before()
            if self._lazy is not None:
                # the host decode plan doubles as device-loss recovery
                # lineage: a dead device's shards can be re-read from
                # storage onto the degraded mesh
                loader = self._lazy.load_table
                self._blocks = self._lazy.load_blocks()
                self._blocks.lineage = loader
                self._lazy = None  # device copy is authoritative now
            else:
                table, mesh = self._pending  # type: ignore[misc]
                self._blocks = from_arrow(table, self.schema, mesh)
                self._blocks.lineage = getattr(
                    self, "_lineage_loader", None
                )
                self._pending = None  # device copy is authoritative now
            if gate is not None:
                gate.after(self._blocks)
                self._mem_gate = None
        return self._blocks

    @property
    def mesh(self) -> Any:
        if self._blocks is not None:
            return self._blocks.mesh
        if self._lazy is not None:
            return self._lazy.mesh
        return self._pending[1]  # type: ignore[index]

    @property
    def is_local(self) -> bool:
        return False

    @property
    def is_bounded(self) -> bool:
        return True

    @property
    def num_partitions(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def empty(self) -> bool:
        return self.count() == 0

    def count(self) -> int:
        if self._blocks is not None:
            return self._blocks.nrows
        if self._lazy is not None:
            return self._lazy.nrows
        return self._pending[0].num_rows  # type: ignore[index]

    def peek_array(self) -> List[Any]:
        self.assert_not_empty()
        return self.head(1).as_array(type_safe=True)[0]

    def as_arrow(self, type_safe: bool = False) -> pa.Table:
        if self._blocks is not None:
            return to_arrow(self._blocks, self.schema)
        if self._lazy is not None:
            # host-only decode, no device trip; memoize as an in-memory
            # pending frame so a second host touch (or a later device op)
            # never re-reads the file
            table = self._lazy.load_table()
            self._pending = (table, self._lazy.mesh)
            self._lazy = None
            return table
        return self._pending[0]  # type: ignore[index]

    def as_pandas(self) -> pd.DataFrame:
        from fugue_tpu.dataframe.arrow_utils import table_to_pandas

        return table_to_pandas(self.as_arrow())

    def as_local_bounded(self) -> LocalBoundedDataFrame:
        res = ArrowDataFrame(self.as_arrow(), self.schema)
        if self.has_metadata:
            res.reset_metadata(self.metadata)
        return res

    def as_array(
        self, columns: Optional[List[str]] = None, type_safe: bool = False
    ) -> List[Any]:
        return self.as_local_bounded().as_array(columns, type_safe)

    def as_array_iterable(
        self, columns: Optional[List[str]] = None, type_safe: bool = False
    ) -> Iterable[Any]:
        return self.as_local_bounded().as_array_iterable(columns, type_safe)

    def _drop_cols(self, cols: List[str]) -> DataFrame:
        schema = self.schema.exclude(cols)
        return self._select_schema(schema)

    def _select_cols(self, cols: List[Any]) -> DataFrame:
        schema = self.schema.extract(cols)
        return self._select_schema(schema)

    def _select_schema(self, schema: Schema) -> "JaxDataFrame":
        if self._blocks is None and self._lazy is not None:
            load_blocks, load_table, mesh, nrows, load_head, narrow = self._lazy
            names = list(schema.names)
            if narrow is not None:
                res = narrow(names)
                if res is not None:
                    return res  # re-planned: unselected columns never decode
            return JaxDataFrame.from_lazy(
                lambda: _subset_blocks(load_blocks(), names),
                lambda: load_table().select(names),
                mesh, schema, nrows,
                None if load_head is None
                else lambda n: load_head(n).select(names),
            )
        if self._blocks is None:
            table, mesh = self._pending  # type: ignore[misc]
            res = JaxDataFrame.from_table(
                table.select(schema.names), mesh, schema
            )
            # the derived pending frame materializes under the same
            # admission ticket (sharing it is safe: the gate is
            # stateless and registers whatever blocks it is handed) and
            # inherits the reload plan (recovery re-selects the subset)
            res._mem_gate = self._mem_gate
            res._lineage_loader = self._lineage_loader
            return res
        blocks = JaxBlocks(
            self._blocks._nrows,
            {n: self._blocks.columns[n] for n in schema.names},
            self._blocks.mesh,
            row_valid=self._blocks.row_valid,
            nrows_dev=self._blocks._nrows_dev,
        )
        return JaxDataFrame(blocks, schema)

    def rename(self, columns: Dict[str, str]) -> DataFrame:
        schema = self._rename_schema(columns)
        if self._blocks is None and self._lazy is not None:
            load_blocks, load_table, mesh, nrows, load_head, _ = self._lazy
            mapping = dict(columns)
            names = list(schema.names)
            return JaxDataFrame.from_lazy(
                lambda: _rename_blocks(load_blocks(), mapping),
                lambda: load_table().rename_columns(names),
                mesh, schema, nrows,
                None if load_head is None
                else lambda n: load_head(n).rename_columns(names),
            )
        if self._blocks is None:
            table, mesh = self._pending  # type: ignore[misc]
            res = JaxDataFrame.from_table(
                table.rename_columns(schema.names), mesh, schema
            )
            res._mem_gate = self._mem_gate  # same admission ticket
            return res
        cols = {
            columns.get(n, n): c for n, c in self._blocks.columns.items()
        }
        return JaxDataFrame(
            JaxBlocks(
                self._blocks._nrows,
                cols,
                self._blocks.mesh,
                row_valid=self._blocks.row_valid,
                nrows_dev=self._blocks._nrows_dev,
            ),
            schema,
        )

    def alter_columns(self, columns: Any) -> DataFrame:
        new_schema = self._alter_schema(columns)
        if new_schema == self.schema:
            return self
        # general correctness path: cast at the host boundary, re-device
        table = cast_table(self.as_arrow(), new_schema)
        return JaxDataFrame.from_table(table, self.mesh, new_schema)

    def head(
        self, n: int, columns: Optional[List[str]] = None
    ) -> LocalBoundedDataFrame:
        assert_or_throw(n >= 0, ValueError("n must be >= 0"))
        schema = self.schema if columns is None else self.schema.extract(columns)
        src = self if columns is None else self[columns]
        if src._blocks is None:  # type: ignore[union-attr]
            lazy = src._lazy  # type: ignore[union-attr]
            if lazy is not None and lazy.load_head is not None:
                # bounded read: only the leading batches, not the file
                table = lazy.load_head(n)
            else:
                table = src.as_arrow()  # pending/lazy host path, no device
            return ArrowDataFrame(table.slice(0, n), schema)
        blocks = src._blocks  # type: ignore
        if blocks.row_valid is not None:
            # masked layout: locate the first n valid rows (one mask
            # readback), gather them on device, export the small frame
            import numpy as np

            from fugue_tpu.jax_backend.blocks import gather_indices

            idx = np.nonzero(np.asarray(blocks.row_valid))[0][:n]
            small = gather_indices(blocks, idx, schema)
            return ArrowDataFrame(to_arrow(small, schema), schema)
        take_n = min(n, blocks.nrows)
        table = to_arrow(
            JaxBlocks(take_n, blocks.columns, blocks.mesh), schema
        )
        return ArrowDataFrame(table, schema)


def _subset_blocks(blocks: JaxBlocks, names: List[str]) -> JaxBlocks:
    return JaxBlocks(
        blocks._nrows,
        {n: blocks.columns[n] for n in names},
        blocks.mesh,
        row_valid=blocks.row_valid,
        nrows_dev=blocks._nrows_dev,
    )


def _rename_blocks(blocks: JaxBlocks, mapping: Dict[str, str]) -> JaxBlocks:
    return JaxBlocks(
        blocks._nrows,
        {mapping.get(n, n): c for n, c in blocks.columns.items()},
        blocks.mesh,
        row_valid=blocks.row_valid,
        nrows_dev=blocks._nrows_dev,
    )
