"""JaxExecutionEngine: the flagship TPU-native backend (BASELINE north star).

Structure parity: a sibling of fugue_spark/fugue_dask engines (reference
fugue_spark/execution_engine.py:336) — but TPU-first in design:

- dataframes are mesh-sharded device blocks (see blocks.py)
- select/filter/assign/aggregate lower to jit-compiled masked jnp programs
  and segment reductions (no shuffle: XLA inserts ICI collectives)
- the map primitive has a compiled path for jax-annotated transformers
  (``Dict[str, jax.Array] -> Dict[str, jax.Array]``, whole-shard vectorized —
  the TPU-idiomatic transformer contract) and a host fallback with exact
  reference semantics for everything else
- **latency design**: every host synchronization stalls dispatch until
  the device drains and every eager (non-jit) op is its own dispatch,
  so the steady-state pipeline is a chain of cached jitted dispatches
  with ZERO intermediate readbacks — filter/dropna/distinct flip validity
  masks instead of gathering, group-by uses host-known key stats for
  static bin counts, row counts stay lazy device scalars, and the single
  sync happens at the host boundary (arrow export)
- relational ops run on device: joins/set-ops via shared key factorization
  (relational.py), zip/comap without serialization (zipped.py), fillna/
  take/sample as validity flips; long-context streams fold through donated
  accumulators (streaming.py); host fallbacks are COUNTED (``fallbacks``)
  so a silent 100x slowdown cannot hide
"""

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa

from fugue_tpu.collections.partition import PartitionCursor, PartitionSpec
from fugue_tpu.column.expressions import ColumnExpr, _NamedColumnExpr
from fugue_tpu.column.functions import VARIANCE_FUNCS
from fugue_tpu.column.sql import SelectColumns
from fugue_tpu.constants import (
    FUGUE_CONF_JAX_PARTITIONS,
    KEYWORD_PARALLELISM,
    KEYWORD_ROWCOUNT,
    typed_conf_get,
)
from fugue_tpu.dataframe import (
    ArrowDataFrame,
    DataFrame,
    LocalDataFrame,
)
from fugue_tpu.exceptions import DeviceLostError
from fugue_tpu.lake import format as _lake_io
from fugue_tpu.obs.trace import start_span
from fugue_tpu.testing.locktrace import tracked_lock
from fugue_tpu.testing.retrace import active_retrace_sentinel
from fugue_tpu.execution.execution_engine import (
    ExecutionEngine,
    MapEngine,
    SQLEngine,
)
from fugue_tpu.execution.native_execution_engine import (
    NativeExecutionEngine,
    PandasMapEngine,
    PandasSQLEngine,
)
from fugue_tpu.jax_backend import expr_eval, groupby, relational, shuffle
from fugue_tpu.jax_backend.blocks import (
    JaxBlocks,
    JaxColumn,
    blocks_schema,
    ensure_x64,
    evacuate_blocks,
    from_arrow,
    gather_indices,
    make_mesh,
    padded_len,
    place_rows,
    row_sharding,
)
from fugue_tpu.jax_backend.dataframe import JaxDataFrame
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw


class JaxMapEngine(MapEngine):
    """Map primitive: compiled whole-shard path for jax transformers, host
    loop fallback otherwise (role parity: SparkMapEngine's pandas-udf vs RDD
    path selection, reference fugue_spark/execution_engine.py:112-133)."""

    @property
    def is_distributed(self) -> bool:
        return True

    def map_dataframe(
        self,
        df: DataFrame,
        map_func: Callable[[PartitionCursor, LocalDataFrame], LocalDataFrame],
        output_schema: Any,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable[[int, DataFrame], Any]] = None,
        map_func_format_hint: Optional[str] = None,
    ) -> DataFrame:
        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        output_schema = Schema(output_schema)
        if map_func_format_hint == "jax":
            raw = self._extract_jax_func(map_func)
            runner = getattr(map_func, "__self__", None)
            if raw is not None and getattr(runner, "ignore_errors", ()):
                # per-partition error swallowing can't run whole-shard:
                # the host loop owns that semantics (same rule as comap);
                # counted ONCE here, so skip the not-mappable counter
                engine._count_fallback(
                    "map", "ignore_errors needs the host partition loop"
                )
            else:
                jdf = engine.to_df(df)
                if raw is not None and self._device_mappable(
                    jdf, output_schema, partition_spec
                ):
                    try:
                        return self._compiled_map(
                            jdf, raw, output_schema, partition_spec, on_init
                        )
                    except _StringDictUnavailable as e:
                        engine._count_fallback(
                            "map",
                            f"string output '{e}' has no dictionary source",
                        )
                else:
                    engine._count_fallback(
                        "map", "jax-hinted transformer not device-mappable"
                    )
        # host fallback: exact reference semantics via the pandas map engine;
        # fugue.jax.default.partitions sets the split count when the spec
        # doesn't name one
        default_parts = typed_conf_get(engine.conf, FUGUE_CONF_JAX_PARTITIONS)
        if (
            default_parts > 0
            and partition_spec.num_partitions == "0"
            and len(partition_spec.partition_by) == 0
        ):
            partition_spec = PartitionSpec(partition_spec, num=default_parts)
        host = PandasMapEngine(engine)
        res = host.map_dataframe(
            df, map_func, output_schema, partition_spec, on_init,
            map_func_format_hint,
        )
        return engine.to_df(res)

    def _extract_jax_func(self, map_func: Callable) -> Optional[Callable]:
        """Reach the raw user function through the transformer runner."""
        runner = getattr(map_func, "__self__", None)
        tf = getattr(runner, "transformer", None)
        wrapper = getattr(tf, "wrapper", None)
        if wrapper is not None and wrapper.input_code.startswith("j"):
            return wrapper.func
        return None

    def _device_mappable(
        self, df: JaxDataFrame, output_schema: Schema, spec: PartitionSpec
    ) -> bool:
        """String columns ARE device-mappable: they enter the compiled-map
        ABI as int32 dictionary codes plus a static host-side decode table
        (``_<name>_dict``) — see :meth:`_compiled_map`."""
        from fugue_tpu.jax_backend.blocks import is_device_type

        if df.is_pending:
            # decide from the schema — don't materialize the device copy
            # just to discover the frame belongs on the host path
            ok_in = all(is_device_type(f.type) for f in df.schema.fields)
        else:
            ok_in = all(c.on_device for c in df.blocks.columns.values())
        ok_out = all(is_device_type(f.type) for f in output_schema.fields)
        return ok_in and ok_out

    def _compiled_map(
        self,
        df: JaxDataFrame,
        fn: Callable,
        output_schema: Schema,
        spec: PartitionSpec,
        on_init: Optional[Callable],
    ) -> DataFrame:
        """Whole-shard vectorized execution: the function sees the full
        (padded, mesh-sharded) columns as a dict of jax arrays; XLA fuses and
        auto-partitions; groups never leave the device.

        Contract (the TPU transformer ABI):

        - ``_row_valid`` bool[padded]: True = real row (padding AND
          filtered-out rows are False).
        - ``_nrows``: the true row count as a TRACED int32 scalar (it is
          data-dependent under the lazy-count design; use it in arithmetic
          / ``jnp.where``, not as a static shape).
        - with partition keys: ``_segment_ids`` int32[padded] (invalid rows
          carry the out-of-range sentinel ``_num_segments``, so segment ops
          with ``num_segments=_num_segments`` drop them automatically) and
          ``_num_segments`` — a STATIC python int segment-id space size
          (some segments may be empty; fine for segment_* reductions).
        - string columns: ``arrs[name]`` is the int32 dictionary CODES
          array (traced) and ``arrs[f"_{name}_dict"]`` the host decode
          table (np object array, STATIC — use it in host python, not in
          traced math). A string OUTPUT column must either pass codes
          through unchanged (it inherits the input's dictionary) or return
          a remapped ``_<name>_dict`` alongside its codes — the host-side
          dict remap + device gather pattern, so e.g. ``value.map(m)``
          costs O(|dictionary|) host work and zero device work.
        - output columns the same padded length as the input are row-aligned
          with it; to change the row count, include ``_nrows`` in the output
          dict (forces one host sync).
        """
        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        blocks = df.blocks
        if on_init is not None:
            on_init(0, df)
        keys = list(spec.partition_by)
        num_segments = -1
        seg: Optional[Any] = None
        if len(keys) > 0:
            fr = groupby.factorize_keys(blocks, keys)
            seg = fr.seg
            num_segments = fr.num_segments
        array_args: Dict[str, Any] = {}
        static_args: Dict[str, Any] = {}
        for name, col in blocks.columns.items():
            array_args[name] = col.data
            if col.mask is not None:
                array_args[f"_{name}_mask"] = col.mask
            if col.host_dict is not None:
                static_args[f"_{name}_dict"] = col.dictionary
        if seg is not None:
            array_args["_segment_ids"] = seg
        pad_n = blocks.padded_nrows
        stash: Dict[str, Any] = {}  # fn-returned decode tables (trace time)

        def _wrapped(
            aa: Dict[str, Any], row_valid: Optional[Any], nrows_s: Any
        ) -> Any:
            full = dict(aa)
            row_valid = groupby.materialize_validity(row_valid, pad_n, nrows_s)
            full["_row_valid"] = row_valid
            full["_nrows"] = nrows_s
            if num_segments >= 0:
                full["_num_segments"] = num_segments
            full.update(static_args)
            out = fn(full)
            if isinstance(out, dict):
                # dictionaries are host values: strip them from the traced
                # outputs into the program's stash (filled at trace time,
                # cached with the executable)
                for k in [k for k in out if _is_dict_key(k)]:
                    stash[k] = np.asarray(out.pop(k), dtype=object)
            return out

        jitted, passthrough, dict_stash = engine._map_program(
            (
                "map", id(fn), pad_n, num_segments, tuple(sorted(array_args)),
                tuple((k, id(v)) for k, v in sorted(static_args.items())),
            ),
            _wrapped,
            array_args,
            blocks,
            list(blocks.columns),
            stash,
        )
        # every string output must have a decode table before we commit to
        # the compiled result: fn-returned (stash) or inherited (passthrough)
        for f in output_schema.fields:
            if pa.types.is_string(f.type) or pa.types.is_large_string(f.type):
                if f"_{f.name}_dict" in dict_stash:
                    continue
                src = blocks.columns.get(passthrough.get(f.name, ""))
                if src is None or src.host_dict is None:
                    raise _StringDictUnavailable(f.name)
        out = jitted(
            array_args, blocks.row_valid, _nrows_arg(blocks)
        )
        assert_or_throw(
            isinstance(out, dict),
            ValueError("jax transformer must return a dict of arrays"),
        )
        ndev = int(blocks.mesh.devices.size)
        sharding = row_sharding(blocks.mesh)
        first = -1
        for f in output_schema.fields:
            assert_or_throw(
                f.name in out,
                ValueError(f"jax transformer output missing column {f.name}"),
            )
            data = out[f.name]
            if first < 0:
                first = int(data.shape[0])
            assert_or_throw(
                int(data.shape[0]) == first,
                ValueError("jax transformer output columns differ in length"),
            )
        row_valid_out: Optional[Any] = None
        nrows_out: Optional[int] = None
        nrows_dev_out: Optional[Any] = None
        if "_nrows" in out:
            # explicit count -> prefix layout over [0, _nrows). One sync;
            # only row-count-changing transformers pay it.
            nrows_out = int(out["_nrows"])
            target = max(padded_len(nrows_out, ndev), padded_len(first, ndev))
        elif first == pad_n:
            # same shape -> row-aligned: inherit the input's membership
            # (including a pending lazy count) with zero syncs
            row_valid_out = blocks.row_valid
            nrows_out = blocks._nrows
            nrows_dev_out = blocks._nrows_dev
            target = pad_n
        else:
            raise ValueError(
                "jax transformer changed the row count "
                f"({pad_n} -> {first}) without returning "
                "'_nrows'; include '_nrows' in the output dict"
            )
        cols: Dict[str, JaxColumn] = {}
        for f in output_schema.fields:
            data = _pad_to(out[f.name], target)
            mask = out.get(f"_{f.name}_mask")
            src_name = passthrough.get(f.name)
            psrc = blocks.columns.get(src_name) if src_name else None
            if (
                mask is None
                and psrc is not None
                and psrc.mask is not None
                and int(psrc.mask.shape[0]) == target
            ):
                # passthrough values keep their nulls unless the fn
                # returned an explicit mask: masked slots hold fill
                # garbage, so treating them as valid is never intended
                mask = psrc.mask
            stats = dictionary = None
            if f"_{f.name}_dict" in dict_stash and (
                pa.types.is_string(f.type) or pa.types.is_large_string(f.type)
            ):
                # fn-provided decode table wins over the inherited one
                dictionary = dict_stash[f"_{f.name}_dict"]
                src_name = None
            if src_name is not None and src_name in blocks.columns:
                src = blocks.columns[src_name]
                # jaxpr identity alone is not enough: a dict-encoded string
                # column's codes passed through to a non-string output field
                # must NOT carry the dictionary (to_arrow would decode codes
                # into the wrong type); stats only describe integer-like
                # value bounds (advisor r2, low)
                if src.host_dict is not None and (
                    pa.types.is_string(f.type)
                    or pa.types.is_large_string(f.type)
                ):
                    dictionary = src.host_dict
                if (
                    pa.types.is_integer(f.type)
                    or pa.types.is_boolean(f.type)
                    or pa.types.is_timestamp(f.type)
                    or pa.types.is_date32(f.type)
                ):
                    # any type whose device representation is integer-like
                    # keeps its (min,max) bounds — matches ingest's stats
                    stats = src.stats
            cols[f.name] = JaxColumn(
                f.type,
                jax.device_put(data, sharding),
                None
                if mask is None
                else jax.device_put(_pad_to(mask, target), sharding),
                dictionary,
                stats,
            )
        return JaxDataFrame(
            JaxBlocks(
                nrows_out,
                cols,
                blocks.mesh,
                row_valid=row_valid_out,
                nrows_dev=nrows_dev_out,
            ),
            output_schema,
        )


class JaxSQLEngine(PandasSQLEngine):
    """SQL facet: parse with the built-in front end and lower the query
    through the algebra bridge into DEVICE relational primitives — joins,
    set ops, GROUP BY aggregates, ORDER BY/LIMIT and DISTINCT all execute
    as jitted device programs (the role Spark SQL / DuckDB play for the
    reference's engines, ``/root/reference/fugue_duckdb/
    execution_engine.py:238-483``). Query shapes outside the bridge
    (window functions, non-equi joins, LIKE, correlated subqueries) run
    on the host SELECT runner with exact SQL semantics — each such
    fallback is counted."""

    @property
    def is_distributed(self) -> bool:
        return True

    def select(self, dfs: Any, statement: Any) -> DataFrame:
        from fugue_tpu.sql_frontend.algebra_bridge import (
            inline_scalar_subqueries,
            translate_query,
        )
        from fugue_tpu.sql_frontend.parser import parse_select

        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        sql = statement.construct(dialect=self.dialect)
        plan = None
        try:
            with start_span("sql.plan"):
                schemas = {
                    name: list(df.schema.names) for name, df in dfs.items()
                }
                q = parse_select(sql)
                # uncorrelated scalar subqueries run as device plans NOW
                # and inline as literals (one scalar readback each);
                # whatever stays un-inlined makes the outer translate
                # give up below
                inline_scalar_subqueries(
                    q, schemas, lambda p: self._exec_plan(p, dfs, {})
                )
                plan = translate_query(q, schemas)
        except Exception:
            plan = None
        if plan is not None:
            try:
                return self._exec_plan(plan, dfs, {})
            except Exception:
                # semantics disagreement -> host runner is the oracle
                engine._count_fallback("sql_select", "device plan raised")
                return super().select(dfs, statement)
        engine._count_fallback("sql_select", "non-lowerable query shape")
        return super().select(dfs, statement)

    def _exec_plan(
        self, plan: Any, dfs: Any, done: Dict[int, DataFrame]
    ) -> DataFrame:
        # ``done`` memoizes by node identity: the translator shares one
        # Plan per CTE, so a CTE referenced twice executes once
        if id(plan) in done:
            return done[id(plan)]
        res = self._exec_plan_uncached(plan, dfs, done)
        done[id(plan)] = res
        return res

    def _exec_plan_uncached(
        self, plan: Any, dfs: Any, done: Dict[int, DataFrame]
    ) -> DataFrame:
        from fugue_tpu.sql_frontend import algebra_bridge as ab

        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        if isinstance(plan, ab.ScanPlan):
            lowered = {n.lower(): n for n in dfs.keys()}
            return engine.to_df(dfs[lowered[plan.table]])
        if isinstance(plan, ab.JoinPlan):
            return engine.join(
                self._exec_plan(plan.left, dfs, done),
                self._exec_plan(plan.right, dfs, done),
                how=plan.how,
                on=list(plan.on),
            )
        if isinstance(plan, ab.NotInJoinPlan):
            l_df: JaxDataFrame = engine.to_df(
                self._exec_plan(plan.left, dfs, done)
            )  # type: ignore[assignment]
            r_df: JaxDataFrame = engine.to_df(
                self._exec_plan(plan.right, dfs, done)
            )  # type: ignore[assignment]
            l_df, r_df = engine._align_meshes(l_df, r_df)
            assert_or_throw(
                relational.device_joinable(
                    l_df.blocks, r_df.blocks, [plan.key], [plan.key]
                ),
                ValueError("NOT IN key not device-resident"),
            )
            out = relational.not_in_join(
                engine, l_df.blocks, r_df.blocks, [plan.key]
            )
            return JaxDataFrame(out, l_df.schema)
        if isinstance(plan, ab.SetPlan):
            left = self._exec_plan(plan.left, dfs, done)
            right = self._exec_plan(plan.right, dfs, done)
            if plan.op == "union":
                return engine.union(left, right, distinct=plan.distinct)
            if plan.op == "except":
                return engine.subtract(left, right, distinct=plan.distinct)
            return engine.intersect(left, right, distinct=plan.distinct)
        if isinstance(plan, ab.WindowPlan):
            src: JaxDataFrame = engine.to_df(
                self._exec_plan(plan.source, dfs, done)
            )  # type: ignore[assignment]
            if plan.where is not None:
                src = engine.to_df(engine.filter(src, plan.where))  # type: ignore
            res = relational.device_window(
                engine, src.blocks, src.schema, plan.items
            )
            assert_or_throw(
                res is not None,
                ValueError("window columns not device-resident"),
            )
            wblocks, wschema = res  # type: ignore[misc]
            return JaxDataFrame(wblocks, wschema)
        assert_or_throw(
            isinstance(plan, ab.SelectPlan), ValueError(f"bad plan {plan}")
        )
        src = self._exec_plan(plan.source, dfs, done)
        if plan.cols is not None:
            out = engine.select(
                src, plan.cols, where=plan.where, having=plan.having
            )
        else:
            out = src
        if plan.distinct:
            out = engine.distinct(out)
        if plan.order_by or plan.limit is not None or plan.offset is not None:
            out = self._exec_sort(out, plan)
        return out

    def _exec_sort(self, df: DataFrame, plan: Any) -> DataFrame:
        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        jdf: JaxDataFrame = engine.to_df(df)  # type: ignore
        sorts = [
            (name, asc, None if nulls is None else (nulls == "FIRST"))
            for name, asc, nulls in plan.order_by
        ]
        with start_span(
            "engine.sort", devices=int(jdf.blocks.mesh.devices.size)
        ):
            out = relational.device_sort(
                engine, jdf.blocks, jdf.schema, sorts,
                limit=plan.limit, offset=plan.offset,
            )
        assert_or_throw(
            out is not None,
            ValueError("sort column not device-resident"),
        )
        return JaxDataFrame(out, jdf.schema)

    # ---- table catalog: DEVICE-resident hot tables ----------------------
    # The shared process-wide catalog keeps the PERSISTED JaxDataFrame
    # itself instead of a host arrow copy: a table saved once stays on
    # its device tier across load_table calls (the serving daemon's hot
    # sessions never re-ingest), is the memory governor's spillable
    # population (persist marks it), and under pressure moves tiers IN
    # PLACE — the catalog reference follows automatically. Entries from
    # other engines (host arrow tuples) still load through the parent.
    def save_table(
        self,
        df: DataFrame,
        table: str,
        mode: str = "overwrite",
        partition_spec: Any = None,
        **kwargs: Any,
    ) -> None:
        from fugue_tpu.execution.native_execution_engine import (
            _TABLE_CATALOG,
        )

        assert_or_throw(
            mode in ("overwrite", "error"),
            NotImplementedError(f"save mode {mode}"),
        )
        if mode == "error":
            assert_or_throw(
                table not in _TABLE_CATALOG,
                ValueError(f"table {table} exists"),
            )
        engine: "JaxExecutionEngine" = self.execution_engine  # type: ignore
        _TABLE_CATALOG[table] = engine.persist(engine.to_df(df))

    def load_table(self, table: str, **kwargs: Any) -> DataFrame:
        from fugue_tpu.execution.native_execution_engine import (
            _TABLE_CATALOG,
        )

        entry = _TABLE_CATALOG.get(table)
        if isinstance(entry, DataFrame):
            return self.execution_engine.to_df(entry)
        return super().load_table(table, **kwargs)


class JaxExecutionEngine(ExecutionEngine):
    """ExecutionEngine over a jax device mesh (single controller).

    **Two-tier placement.** The engine owns TWO meshes: the accelerator
    mesh (``jax.devices()``) and a host mesh over the CPU backend
    (``jax.devices("cpu")``). Every op runs the same jitted programs on
    whichever mesh a frame's blocks live on — XLA compiles per backend.
    Ingest places a frame by a bandwidth-aware policy
    (``fugue.jax.placement``): on ``auto`` (default), frames smaller than
    ``fugue.jax.placement.min_device_bytes`` stay on the host tier, because
    for a one-shot query the host<->accelerator link transfer dominates any
    compute win — the same reason the reference routes small/IO-bound work
    to its NativeExecutionEngine rather than a cluster (reference
    fugue/execution/native_execution_engine.py:171-419 is the engine that
    wins those workloads). ``device`` / ``host`` pin the tier; engines
    constructed with an explicit ``mesh=`` are always pinned to it.

    Config keys: ``fugue.jax.default.partitions`` (logical split count for
    host-fallback maps; default = mesh size), ``fugue.jax.placement``,
    ``fugue.jax.placement.min_device_bytes``, ``fugue.optimize.cache.dir``
    (persistent compiled-executable cache; the deprecated
    ``fugue.jax.compile.cache`` key aliases it)."""

    def __init__(self, conf: Any = None, mesh: Any = None):
        super().__init__(conf)
        ensure_x64()
        # fugue.jax.devices carves the engine's mesh out of a slice of
        # the pod (how each fleet replica owns its own devices); an
        # explicitly passed mesh always wins
        self._mesh = (
            mesh
            if mesh is not None
            else make_mesh(_devices_from_conf(self.conf))
        )
        self._mesh_pinned = mesh is not None
        self._host_mesh = self._mesh if mesh is not None else _host_mesh_like(
            self._mesh
        )
        # host sibling used for fallback relational ops
        self._native = NativeExecutionEngine(conf)
        # host-fallback observability: op name -> count. Silent fallbacks
        # are silent 100x slowdowns (verdict r2); every host round-trip on
        # an op with a device path increments this and logs at info, so
        # tests/benches can assert a pipeline stayed on device. Since
        # ISSUE 8 the storage is a labeled counter family on the
        # engine's metrics registry — the `fallbacks` property is the
        # unchanged back-compat dict view over it.
        self._m_fallbacks = self.metrics.counter(
            "fugue_engine_fallbacks_total",
            "host fallbacks and memory-governance events per op "
            "(engine.fallbacks back-compat surface)",
            ["op"],
        )
        # jit program-cache hit/miss counters (surfaces on /v1/status
        # and /v1/metrics); children pre-resolved: the increment on the
        # dispatch hot path is one lock + add
        _m_compile = self.metrics.counter(
            "fugue_engine_compile_cache_total",
            "engine jit program-cache lookups by result",
            ["result"],
        )
        self._compile_hits = _m_compile.labels(result="hit")
        self._compile_misses = _m_compile.labels(result="miss")
        # process-wide plan cache (ISSUE 10): compiled program handles
        # are shared across engine instances under a signature folding
        # platform + mesh devices + fugue.jax.* conf, so a fresh engine
        # running a repeated query skips XLA compilation entirely.
        # These counters are EXACT lookup results (hit = a handle was
        # reused, miss = a new program was jitted), unlike the
        # per-dispatch compile_cache heuristic.
        from fugue_tpu.optimize.cache import (
            engine_plan_signature,
            get_plan_cache,
        )
        from fugue_tpu.optimize.exec_cache import (
            ExecutableDiskCache,
            resolve_cache_dir,
        )

        _m_plan = self.metrics.counter(
            "fugue_engine_plan_cache_total",
            "process-wide plan-cache lookups by tier and result "
            "(memory = shared jit handles, disk = persisted executables)",
            ["tier", "result"],
        )
        self._plan_hits = _m_plan.labels(tier="memory", result="hit")
        self._plan_misses = _m_plan.labels(tier="memory", result="miss")
        self._disk_hits = _m_plan.labels(tier="disk", result="hit")
        self._disk_misses = _m_plan.labels(tier="disk", result="miss")
        self._disk_evicts = _m_plan.labels(tier="disk", result="evict")
        self._disk_corrupt = _m_plan.labels(tier="disk", result="corrupt")
        self._plan_cache = get_plan_cache()
        self._plan_cache.configure(self.conf)
        self._plan_sig = engine_plan_signature(self)
        # DISK tier under the plan cache (ISSUE 11): AOT-serialized
        # executables under fugue.optimize.cache.dir (or its deprecated
        # fugue.jax.compile.cache alias) — a fresh PROCESS running a
        # cached program skips XLA entirely. Disabled (empty dir) = the
        # dispatch hot path never touches any of this.
        self._exec_cache = ExecutableDiskCache(
            self, resolve_cache_dir(self.conf, self.log)
        )
        self._exec_enabled = self._exec_cache.enabled
        self._m_deserialize = self.metrics.histogram(
            "fugue_engine_exec_cache_deserialize_seconds",
            "disk-tier executable deserialize latency",
        )
        _m_persist = self.metrics.counter(
            "fugue_engine_exec_cache_persist_total",
            "disk-tier executable persist outcomes",
            ["result"],
        )
        self._persist_ok = _m_persist.labels(result="ok")
        self._persist_err = _m_persist.labels(result="error")
        # retrace-sentinel violations per program (the runtime twin of
        # the FJX jit-hazard lint plane): only ever incremented while
        # the debug sentinel is armed — a standing zero in production
        self._m_retrace = self.metrics.counter(
            "fugue_engine_retrace_sentinel_total",
            "jitted programs that exceeded the armed retrace sentinel's "
            "trace budget (fugue.debug.retrace_sentinel.max_traces)",
            ["program"],
        )
        # program outputs that blocks.place_rows had to reshard through
        # the host (a blocking fetch, then a put per device): it slices
        # outputs left replicated on the devices, so a standing zero
        self._host_reshards = self.metrics.counter(
            "fugue_engine_host_reshards_total",
            "program outputs resharded through the host onto the mesh's "
            "row sharding (outputs on other devices than the mesh's)",
        ).labels()
        # compile/execute/disk-load wall clock split of every jitted
        # dispatch since construction — the daemon's time_to_first_query
        # phase report reads deltas of this
        self._dispatch_secs_lock = tracked_lock(
            "jax.engine.JaxExecutionEngine._dispatch_secs_lock"
        )
        self._dispatch_secs = {
            "compile": 0.0, "execute": 0.0, "disk_load": 0.0,
        }
        self.metrics.add_collector(self._collect_memory_gauges)
        # segment-reduction strategy observability, mirroring fallbacks:
        # strategy name -> times an aggregate program ran on it ("generic"
        # = the unpacked per-agg path). Benches report this per config so
        # the crossover selector's choices are visible, not guessed.
        self._strategy_counts: Dict[str, int] = {}
        # shuffle-repartition observability (the fugue_shuffle_ family):
        # per-op program runs split by overlap mode, transported-byte
        # estimates, and dispatch wall clock. EXPLAIN ANALYZE surfaces
        # deltas of the shuffle_counts view over these.
        self._m_shuffle_ops = self.metrics.counter(
            "fugue_shuffle_ops_total",
            "all-to-all shuffle-repartitioned programs per op, split by "
            "whether the collective/compute overlap split was traced",
            ["op", "overlap"],
        )
        self._m_shuffle_bytes = self.metrics.counter(
            "fugue_shuffle_bytes_total",
            "estimated bytes moved through padded all-to-all exchanges "
            "per op (static shape estimate, counts the full padded "
            "send buffers)",
            ["op"],
        )
        self._m_shuffle_secs = self.metrics.counter(
            "fugue_shuffle_seconds_total",
            "dispatch wall clock of shuffle-repartitioned programs per "
            "op (async dispatch time; the collective itself overlaps "
            "downstream compute)",
            ["op"],
        )
        # (fn, arg avals) of jitted programs as they run, for AOT
        # cost_analysis (see program_cost_analysis). Recording is DISARMED
        # until reset_program_log() so the per-dispatch aval capture never
        # taxes workloads that don't profile (review finding)
        self._program_log: Dict[Any, Tuple[Callable, Any]] = {}
        self._program_log_armed = False
        # per-THREAD placement override: the fault-tolerance layer re-runs
        # a device-OOM task under degraded_to_host() — thread-local so one
        # degraded task in a parallel runner doesn't demote its siblings
        self._tier_override = threading.local()
        # proactive device-memory governance: byte ledger + admission
        # control + LRU spill-to-host (memory.py). Disabled unless
        # fugue.jax.memory.budget_bytes/.budget_fraction is set.
        from fugue_tpu.jax_backend.memory import MemoryGovernor

        self._memory = MemoryGovernor(self)
        # device-fault recovery state (recover_from_device_loss): live
        # frame registry for the evacuation sweep (weak — the registry
        # must never pin a frame's device memory), the devices retired
        # so far (device OBJECTS: numeric ids collide across backends),
        # and how many degrade-rebuild cycles ran
        self._live_frames: Any = weakref.WeakSet()
        self._lost_devices: set = set()
        self._device_recoveries = 0
        # task-granular dispatch serialization for SHARED-engine use (the
        # serving daemon): XLA's CPU backend runs cross-device collectives
        # through a per-execution rendezvous on a shared thread pool — two
        # concurrently dispatched programs with collectives can starve
        # each other's participants and deadlock. Reentrant, so a serial
        # in-thread workflow nests freely.
        self._dispatch_lock = tracked_lock(
            "jax.engine.JaxExecutionEngine._dispatch_lock", reentrant=True
        )

    @property
    def fallbacks(self) -> Dict[str, int]:
        """Read-only snapshot of the host-fallback/governance counters
        since construction (or `reset_fallbacks`). Cited by the static
        analyzer's cost pass when predicting host behavior. A dict view
        over the registry's ``fugue_engine_fallbacks_total`` family."""
        return self._m_fallbacks.as_int_dict()

    def reset_fallbacks(self) -> None:
        self._m_fallbacks.clear()

    def _bump_fallback_counter(self, name: str, kind: str, detail: str) -> None:
        """The ONE increment path behind every fallback-surface counter:
        host fallbacks and memory-governance events share the same
        metric family, the same info log shape, and therefore the same
        assertions in tests/benches."""
        self._m_fallbacks.labels(op=name).inc()
        self.log.info(
            "fugue_tpu.jax %s: %s%s",
            kind,
            name,
            f" ({detail})" if detail else "",
        )

    @property
    def compile_cache_stats(self) -> Dict[str, int]:
        """Jit program-cache hit/miss counts since construction — the
        compile-amortization signal ``/v1/status`` reports."""
        return {
            "hits": int(self._compile_hits.value),
            "misses": int(self._compile_misses.value),
        }

    @property
    def plan_cache_stats(self) -> Dict[str, int]:
        """EXACT program-handle lookup counts against the process-wide
        plan cache (hit = compiled handle reused — from this engine or a
        previous same-signature one; miss = a new program was jitted).
        ``/v1/status`` reports these as ``compile_cache`` instead of the
        per-dispatch jax-cache-growth heuristic above."""
        return {
            "hits": int(self._plan_hits.value),
            "misses": int(self._plan_misses.value),
        }

    @property
    def exec_cache_stats(self) -> Dict[str, Any]:
        """The DISK tier's counters: per-shape executable loads by
        result (hit/miss/evict/corrupt) plus persist outcomes. All
        zeros when no cache dir is configured."""
        return {
            "enabled": self._exec_enabled,
            "dir": self._exec_cache.base_uri,
            "hits": int(self._disk_hits.value),
            "misses": int(self._disk_misses.value),
            "evictions": int(self._disk_evicts.value),
            "corrupt": int(self._disk_corrupt.value),
            "persisted": int(self._persist_ok.value),
            "persist_failures": int(self._persist_err.value),
        }

    @property
    def dispatch_time_stats(self) -> Dict[str, float]:
        """Wall-clock split of every jitted dispatch since construction:
        ``compile`` (dispatches that paid an XLA compile), ``execute``
        (compile-free dispatches) and ``disk_load`` (executable
        deserialize time) — the cold-start phase accounting the serving
        daemon's ``time_to_first_query`` report reads."""
        with self._dispatch_secs_lock:
            return dict(self._dispatch_secs)

    def _add_dispatch_secs(self, kind: str, secs: float) -> None:
        with self._dispatch_secs_lock:
            self._dispatch_secs[kind] += secs

    def _collect_memory_gauges(self) -> None:
        """Scrape-time collector: the PR 4 memory ledger's live/peak
        bytes per tier as labeled gauges (zeros when ungoverned)."""
        snap = self._memory.snapshot()
        live = self.metrics.gauge(
            "fugue_engine_memory_bytes",
            "live device-memory ledger bytes per tier",
            ["tier"],
        )
        peak = self.metrics.gauge(
            "fugue_engine_memory_peak_bytes",
            "peak device-memory ledger bytes per tier",
            ["tier"],
        )
        for tier, v in (snap.get("tiers") or {}).items():
            live.labels(tier=tier).set(v)
        for tier, v in (snap.get("peak") or {}).items():
            peak.labels(tier=tier).set(v)
        self.metrics.gauge(
            "fugue_engine_memory_budget_bytes",
            "configured device-memory budget (0 = ungoverned)",
        ).labels().set(snap.get("budget_bytes") or 0)

    def _count_fallback(self, op: str, why: str = "") -> None:
        self._bump_fallback_counter(op, "host fallback", why)

    def _count_memory_event(self, name: str, detail: str = "") -> None:
        """Memory-governance events ride the fallback counter surface
        (``mem_admit_host``/``mem_pressure``/``mem_spill``/
        ``mem_oom_feedback``) so tests and benches assert governance ran
        the same way they assert a pipeline stayed on device."""
        self._bump_fallback_counter(name, "memory governance", detail)

    @property
    def task_execution_lock(self) -> Any:
        """Engine-wide reentrant dispatch lock (see the base property):
        concurrent workflows sharing this engine serialize their DEVICE
        work at task granularity while their host-side phases overlap."""
        return self._dispatch_lock

    @property
    def memory_governor(self) -> Any:
        """The engine's :class:`~fugue_tpu.jax_backend.memory.MemoryGovernor`
        — the serving daemon claims session tables for their tenant and
        scopes job registrations through it."""
        return self._memory

    @property
    def memory_stats(self) -> Dict[str, Any]:
        """Snapshot of the device-memory governor: budget, per-tier live
        and peak ledger bytes, and event counters. ``enabled`` is False
        (and everything zero) unless ``fugue.jax.memory.budget_bytes`` or
        ``.budget_fraction`` is configured."""
        return self._memory.snapshot()

    def note_device_oom(self, ex: BaseException) -> None:
        """Called by the fault layer when a RESOURCE_EXHAUSTED slipped
        past admission: feed the measured allocation size back into the
        ledger (budget clamps to observed capacity, pressure is
        relieved) before the reactive host-tier degrade runs."""
        self._memory.note_oom(ex)

    @property
    def strategy_counts(self) -> Dict[str, int]:
        """Segment-reduction strategy counters since construction (or
        ``reset_strategy_counts``) — which kernel each aggregate ran on."""
        return dict(self._strategy_counts)

    def reset_strategy_counts(self) -> None:
        self._strategy_counts.clear()

    def _count_strategy(self, name: str) -> None:
        self._strategy_counts[name] = self._strategy_counts.get(name, 0) + 1

    @property
    def shuffle_counts(self) -> Dict[str, int]:
        """Shuffle-repartition counters since construction, flattened for
        the profiler's counter surface: per-op program runs (``aggregate``,
        ``join``), ``<op>_overlap`` runs that traced the double-buffered
        split, ``<op>_bytes`` transported-byte estimates, and ``<op>_ms``
        cumulative dispatch wall clock."""
        out: Dict[str, int] = {}
        for (op, overlap), v in self._m_shuffle_ops.as_int_dict().items():
            out[op] = out.get(op, 0) + v
            if overlap == "1":
                out[f"{op}_overlap"] = out.get(f"{op}_overlap", 0) + v
        for op, v in self._m_shuffle_bytes.as_int_dict().items():
            if v:
                out[f"{op}_bytes"] = v
        for op, secs in self._m_shuffle_secs.as_dict().items():
            ms = int(secs * 1000.0)
            if ms:
                out[f"{op}_ms"] = ms
        return out

    def _count_shuffle(
        self, op: str, nbytes: int, secs: float, overlap: bool
    ) -> None:
        self._m_shuffle_ops.labels(
            op=op, overlap="1" if overlap else "0"
        ).inc()
        self._m_shuffle_bytes.labels(op=op).inc(max(0, int(nbytes)))
        self._m_shuffle_secs.labels(op=op).inc(max(0.0, float(secs)))

    def reset_program_log(self) -> None:
        """Arm program recording and forget prior signatures (scopes
        program_cost_analysis to the ops run after this call)."""
        self._program_log.clear()
        self._program_log_armed = True

    def program_cost_analysis(self) -> Dict[str, Any]:
        """XLA ``cost_analysis()`` of the engine programs that ran since
        ``reset_program_log``: per-program flops and bytes accessed plus
        totals. This is the compiler's own traffic accounting — the number
        the roofline block divides by device time to report achieved GB/s
        against platform peak (ISSUE r6: a bytes-touched guess can only
        lower-bound it; XLA's real traffic proves or disproves fusion).
        Reading the analysis DISARMS recording again, so one profiling
        pass never taxes the rest of the engine's lifetime."""
        self._program_log_armed = False
        out: Dict[str, Any] = {"programs": {}, "flops": 0.0, "bytes_accessed": 0.0}
        for key, (fn, avals) in list(self._program_log.items()):
            try:
                ca = jax.jit(fn).lower(*avals).compile().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if len(ca) > 0 else {}
                flops = float(ca.get("flops", 0.0))
                nbytes = float(ca.get("bytes accessed", 0.0))
            except Exception:  # pragma: no cover - backend w/o analysis
                continue
            name = str(key[0]) if isinstance(key, tuple) and key else str(key)
            slot = out["programs"].setdefault(
                name, {"flops": 0.0, "bytes_accessed": 0.0, "count": 0}
            )
            slot["flops"] += flops
            slot["bytes_accessed"] += nbytes
            slot["count"] += 1
            out["flops"] += flops
            out["bytes_accessed"] += nbytes
        return out

    @property
    def mesh(self) -> Any:
        return self._mesh

    @property
    def host_mesh(self) -> Any:
        """The host (CPU backend) tier's mesh; equals :attr:`mesh` when the
        engine is pinned or the default platform already is CPU."""
        return self._host_mesh

    @property
    def supports_host_degrade(self) -> bool:
        """A device-OOM task can re-run on the host tier when the engine
        actually has two tiers (not pinned to one mesh)."""
        return not self._mesh_pinned and self._host_mesh is not self._mesh

    def degraded_to_host(self) -> Any:
        """Force THIS thread's ingest placement onto the host (CPU) mesh —
        the graceful-degradation venue for a task whose device allocation
        failed (RESOURCE_EXHAUSTED). Thread-local: concurrent sibling
        tasks keep their accelerator placement."""
        from contextlib import contextmanager

        @contextmanager
        def _ctx():
            prev = getattr(self._tier_override, "mode", None)
            self._tier_override.mode = "host"
            try:
                yield self
            finally:
                self._tier_override.mode = prev

        return _ctx()

    # ---- device-fault recovery -------------------------------------------
    @property
    def lost_devices(self) -> Tuple[int, ...]:
        """Ids of the devices this engine has retired after hardware
        faults (empty on a healthy engine)."""
        return tuple(sorted(int(d.id) for d in self._lost_devices))

    @property
    def surviving_device_count(self) -> int:
        """Devices in the CURRENT mesh — after a degraded-mesh rebuild
        this is the survivor count the serve plane's ``degraded`` health
        state reports."""
        return int(self._mesh.devices.size)

    @property
    def is_degraded(self) -> bool:
        """True once any device has been lost and the engine rebuilt
        onto the survivors."""
        return len(self._lost_devices) > 0

    @property
    def device_recoveries(self) -> int:
        """Completed degrade-rebuild cycles (the `device_lost_recovery`
        counter's underlying engine state)."""
        return self._device_recoveries

    def recover_from_device_loss(self, ex: BaseException) -> bool:
        """Rebuild the engine onto the surviving devices after ``ex``
        (a DEVICE_LOST-classified XLA error; see workflow/fault.py).

        The dead devices are parsed out of the error text, or probed
        when the error names none. Then, under the dispatch lock: the
        memory governor retires the dead pools and marks stranded ledger
        entries lost, a fresh mesh is built from the survivors, the plan
        signature is recomputed (a 4-device program must never serve the
        3-device mesh), and every live frame is swept — evacuated via an
        arrow round trip when its shards are still readable, re-read
        from lineage (lazy load plan / checkpoint artifact / pinned
        lake version) when not, or marked lost so only its OWNING query
        fails (at the ``to_df`` touch point) while the process and every
        other session survive.

        Returns True when a rebuild happened — the retry executor then
        counts ``device_lost_recovery`` and re-runs the task under the
        normal backoff budget. False (recovery disabled, pinned mesh,
        no identifiable corpse, no survivors, or ``max_losses``
        exhausted) fails the task with the original error."""
        from fugue_tpu.constants import (
            FUGUE_CONF_JAX_RECOVERY_ENABLED,
            FUGUE_CONF_JAX_RECOVERY_MAX_LOSSES,
        )
        from fugue_tpu.jax_backend.distributed import (
            parse_lost_devices,
            probe_devices,
        )

        if not self.conf.get(FUGUE_CONF_JAX_RECOVERY_ENABLED, True):
            return False
        if self._mesh_pinned:
            # an explicitly passed mesh: the caller owns device topology
            return False
        mesh = self._mesh
        by_id = {int(d.id): d for d in mesh.devices.flat}
        named = [i for i in parse_lost_devices(str(ex)) if i in by_id]
        if named:
            lost = [by_id[i] for i in named]
        else:
            alive = set(probe_devices(mesh))
            lost = [d for d in mesh.devices.flat if d not in alive]
        if len(lost) == 0 or len(lost) >= len(by_id):
            return False
        max_losses = int(
            self.conf.get(FUGUE_CONF_JAX_RECOVERY_MAX_LOSSES, 0)
        )
        if max_losses > 0 and len(self._lost_devices) + len(lost) > max_losses:
            return False
        with self._dispatch_lock:
            survivors = [d for d in mesh.devices.flat if d not in lost]
            single_tier = self._host_mesh is mesh
            new_mesh = make_mesh(survivors)
            self._lost_devices.update(lost)
            self._memory.retire_devices([int(d.id) for d in lost])
            self._mesh = new_mesh
            if single_tier:
                self._host_mesh = new_mesh
            # plan/exec cache signatures fold the mesh devices
            from fugue_tpu.optimize.cache import engine_plan_signature

            self._plan_sig = engine_plan_signature(self)
            self._device_recoveries += 1
            outcomes = {"evacuated": 0, "rematerialized": 0, "lost": 0}
            for blocks in list(self._live_frames):
                res = self._recover_blocks(blocks)
                if res in outcomes:
                    outcomes[res] += 1
            self._count_memory_event(
                "device_lost_recovery",
                f"lost {sorted(int(d.id) for d in lost)} -> "
                f"{len(survivors)} survivors; "
                f"{outcomes['evacuated']} evacuated, "
                f"{outcomes['rematerialized']} rematerialized, "
                f"{outcomes['lost']} unrecoverable",
            )
        return True

    def _mesh_is_stale(self, mesh: Any) -> bool:
        if not self._lost_devices:
            return False
        return any(d in self._lost_devices for d in mesh.devices.flat)

    def _recover_blocks(self, blocks: Optional[JaxBlocks]) -> str:
        """One frame's recovery: ``"ok"`` (untouched by the loss),
        ``"evacuated"`` (arrow round trip onto the degraded mesh, same
        JaxBlocks identity so every holder heals), ``"rematerialized"``
        (re-read from lineage), or ``"lost"``."""
        from fugue_tpu.testing.faults import fault_point

        if blocks is None:
            return "ok"
        if not blocks.lost and not self._mesh_is_stale(blocks.mesh):
            return "ok"
        if not blocks.lost:
            try:
                # chaos hook: a plan here simulates shards that died
                # WITH the device, forcing the lineage/lost path
                fault_point("device.lost", "evacuate")
                evacuate_blocks(blocks, self._mesh)
                self._memory.register(blocks, "device")
                return "evacuated"
            except Exception as e:
                self.log.warning("block evacuation failed: %s", e)
        loader = blocks.lineage
        if loader is not None:
            try:
                from fugue_tpu.jax_backend.blocks import replace_blocks

                table = loader()
                fresh = from_arrow(
                    table.select(list(blocks.columns.keys())),
                    blocks_schema(blocks),
                    self._mesh,
                )
                replace_blocks(blocks, fresh)
                self._memory.register(blocks, "device")
                return "rematerialized"
            except Exception as e:
                self.log.warning(
                    "lineage rematerialization failed: %s", e
                )
        blocks.lost = True
        return "lost"

    def _track_frame(self, df: JaxDataFrame) -> None:
        """Recovery touch point for every frame entering an engine op:
        remember live blocks for the evacuation sweep, re-point
        pending/lazy placement stranded on a retired mesh, heal
        materialized frames on the spot, and fail unrecoverable ones
        with :class:`DeviceLostError` — the owning query dies; the
        process (and every other session) survives."""
        blocks = df._blocks
        if blocks is None:
            if self._lost_devices:
                if df._pending is not None and self._mesh_is_stale(
                    df._pending[1]
                ):
                    df._pending = (df._pending[0], self._mesh)
                if df._lazy is not None and self._mesh_is_stale(
                    df._lazy.mesh
                ):
                    df._lazy = df._lazy._replace(mesh=self._mesh)
            return
        if blocks.lost or self._mesh_is_stale(blocks.mesh):
            if self._recover_blocks(blocks) == "lost":
                raise DeviceLostError(
                    f"frame [{df.schema}] lost its device shards "
                    f"(devices {self.lost_devices}) and has no "
                    "recoverable lineage (lazy load plan, checkpoint "
                    "artifact, or pinned lake version)",
                    lost_devices=self.lost_devices,
                    frames=(str(df.schema),),
                )
        self._live_frames.add(blocks)

    def _attach_load_lineage(
        self, df: DataFrame, loader: Callable[[], pa.Table]
    ) -> None:
        """Storage-backed frames carry their reload plan as recovery
        lineage: if a device dies holding their shards, the rebuild
        re-reads the artifact onto the degraded mesh instead of failing
        the query (see :meth:`recover_from_device_loss`)."""
        if isinstance(df, JaxDataFrame):
            df._lineage_loader = loader

    def _ingest_mesh(self, nbytes: int) -> Any:
        """Placement policy: which mesh a newly ingested frame lands on."""
        return self._place(nbytes)[0]

    def _place(self, nbytes: int, admit: bool = True) -> Tuple[Any, str]:
        """Placement + admission: the bandwidth policy picks the default
        tier; the memory governor may redirect a device-tier newcomer
        whose footprint alone exceeds the budget onto the host tier. The
        returned tier label is LOGICAL — on single-mesh engines (CPU
        tests, pinned meshes) both tiers share one mesh but the ledger
        still governs them separately. ``admit=False`` is the
        provisional, side-effect-free form for plan-time placement
        (streamed loads re-place — and admit for real — at
        materialization)."""
        tier = self._default_tier(nbytes)
        if admit:
            tier = self._memory.admit(int(nbytes), tier)
        return (self._host_mesh if tier == "host" else self._mesh), tier

    def _default_tier(self, nbytes: int) -> str:
        if self._mesh_pinned:
            return "device"
        if getattr(self._tier_override, "mode", None) == "host":
            return "host"
        from fugue_tpu.constants import (
            FUGUE_CONF_JAX_MIN_DEVICE_BYTES,
            FUGUE_CONF_JAX_PLACEMENT,
        )

        mode = str(self.conf.get(FUGUE_CONF_JAX_PLACEMENT, "auto")).lower()
        if mode == "device":
            return "device"
        if mode == "host":
            return "host"
        if self._host_mesh is self._mesh:
            # single physical tier: the transfer-cost threshold is moot
            return "device"
        threshold = int(
            self.conf.get(FUGUE_CONF_JAX_MIN_DEVICE_BYTES, 256 * 1024 * 1024)
        )
        return "device" if nbytes >= threshold else "host"

    def _align_meshes(
        self, j1: JaxDataFrame, j2: JaxDataFrame
    ) -> Tuple[JaxDataFrame, JaxDataFrame]:
        """Binary relational ops need both frames on one mesh. Move the
        pending/smaller frame onto the other's mesh (one transfer of the
        smaller side — the same cost model as a broadcast join)."""
        m1, m2 = j1.mesh, j2.mesh
        if m1 is m2 or m1 == m2:
            return j1, j2

        def _weight(j: JaxDataFrame) -> int:
            # pending frames are cheapest to move (no device copy exists)
            if j.is_pending:
                return -1
            return j.blocks.padded_nrows

        if _weight(j1) <= _weight(j2):
            return self._move_to_mesh(j1, m2), j2
        return j1, self._move_to_mesh(j2, m1)

    def _move_to_mesh(self, j: JaxDataFrame, mesh: Any) -> JaxDataFrame:
        res = JaxDataFrame.from_table(
            j.as_arrow(), mesh, j.schema
        )
        if j.has_metadata:
            res.reset_metadata(j.metadata)
        return res

    @property
    def is_distributed(self) -> bool:
        return True

    def create_default_map_engine(self) -> MapEngine:
        return JaxMapEngine(self)

    def create_default_sql_engine(self) -> SQLEngine:
        return JaxSQLEngine(self)

    def get_current_parallelism(self) -> int:
        return int(self._mesh.devices.size)

    def to_df(self, df: Any, schema: Any = None) -> DataFrame:
        from fugue_tpu.jax_backend.zipped import JaxZippedDataFrame

        if isinstance(df, JaxZippedDataFrame):
            return df  # co-partition handle: consumed by comap only
        if isinstance(df, JaxDataFrame):
            assert_or_throw(
                schema is None, ValueError("schema must be None for JaxDataFrame")
            )
            # device-fault touch point: register live blocks for the
            # recovery sweep, heal frames stranded on a retired device,
            # and fail unrecoverable ones with DeviceLostError
            self._track_frame(df)
            # LRU recency for the governor's spill ordering: a frame
            # flowing through an engine op is in active use
            self._memory.touch(df._blocks)
            return df
        if isinstance(df, DataFrame):
            assert_or_throw(
                schema is None, ValueError("schema must be None for DataFrame")
            )
            table = df.as_local_bounded().as_arrow(type_safe=True)
            res = self._governed_frame(table, df.schema)
            if df.has_metadata:
                res.reset_metadata(df.metadata)
            return res
        from fugue_tpu.collections.yielded import Yielded

        if isinstance(df, Yielded):
            return self.load_yielded(df)  # type: ignore
        local = self._native.to_df(df, schema)
        table = local.as_arrow(type_safe=True)
        return self._governed_frame(table, local.schema)

    def _governed_frame(self, table: pa.Table, schema: Schema) -> JaxDataFrame:
        """Ingest entry point for host tables: placement + admission on
        the dtype-widened device-footprint estimate, with the governor's
        admission ticket attached so the lazy upload is gated (and its
        real byte count registered) at materialization time."""
        from fugue_tpu.jax_backend.memory import estimate_table_device_bytes

        est = estimate_table_device_bytes(table)
        mesh, tier = self._place(est)
        res = JaxDataFrame.from_table(table, mesh, schema)
        res._mem_gate = self._memory.gate(tier, est)
        return res

    # ---- device-lowered column algebra ----------------------------------
    def select(
        self,
        df: DataFrame,
        cols: SelectColumns,
        where: Optional[ColumnExpr] = None,
        having: Optional[ColumnExpr] = None,
    ) -> DataFrame:
        jdf = self.to_df(df)
        resolved = cols.replace_wildcard(jdf.schema).assert_all_with_names()
        if self._can_select_on_device(jdf, resolved, where, having):
            try:
                out_schema = resolved.infer_schema(jdf.schema)
                filtered = jdf if where is None else self.filter(jdf, where)
                if not resolved.has_agg:
                    return self._device_project(filtered, resolved, out_schema)  # type: ignore
                res = self._device_groupby_select(
                    filtered, resolved, out_schema, having  # type: ignore
                )
                if res is not None:
                    return res
            except NotImplementedError:
                # size-capped lowerings (dynamic-LIKE LUTs, composed
                # CONCAT dictionaries) surface at build time: host owns
                pass
        # fallback gets the ORIGINAL frame + where (avoid double filtering)
        self._count_fallback("select")
        return self.to_df(
            self._native.select(jdf.as_local_bounded(), cols, where, having)
        )

    def filter(self, df: DataFrame, condition: ColumnExpr) -> DataFrame:
        """Mask-only filter: ONE cached jitted dispatch flips row validity;
        columns (and their stats) are untouched, the row count becomes a
        lazy device scalar. No gather, no host sync."""
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        if expr_eval.can_eval_on_device(
            condition, jdf.blocks
        ) and not expr_eval.is_string_result(condition, jdf.blocks):
            blocks = jdf.blocks
            pad_n = blocks.padded_nrows
            dicts = expr_eval.dicts_of(blocks)

            def _filter_prog(
                mcols: Dict[str, Any], row_valid: Optional[Any], nrows_s: Any
            ) -> Tuple[Any, Any]:
                row_valid = groupby.materialize_validity(
                    row_valid, pad_n, nrows_s
                )
                value, mask = expr_eval.eval_expr(
                    mcols, condition, pad_n, dicts
                )
                keep = value.astype(jnp.bool_)
                if mask is not None:
                    keep = keep & mask
                keep = keep & row_valid
                return keep, jnp.sum(keep).astype(jnp.int32)

            try:
                keep, cnt = self._jit_cached(
                    ("filter", condition.__uuid__(), pad_n,
                     expr_eval.dict_fingerprint(blocks)), _filter_prog
                )(
                    expr_eval.blocks_to_masked(blocks),
                    blocks.row_valid,
                    _nrows_arg(blocks),
                )
                return JaxDataFrame(
                    JaxBlocks(
                        None,
                        dict(blocks.columns),
                        blocks.mesh,
                        row_valid=keep,
                        nrows_dev=cnt,
                    ),
                    jdf.schema,
                )
            except NotImplementedError:
                pass  # size-capped lowering surfaced at build time
        self._count_fallback("filter")
        return self.to_df(self._native.filter(jdf.as_local_bounded(), condition))

    def assign(self, df: DataFrame, columns: List[ColumnExpr]) -> DataFrame:
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        blocks = jdf.blocks
        if all(expr_eval.can_eval_on_device(c, blocks) for c in columns):
            pad_n = blocks.padded_nrows
            dicts = expr_eval.dicts_of(blocks)
            schema = jdf.schema
            plans: List[Tuple[str, Any, ColumnExpr]] = []
            for c in columns:
                name = c.output_name
                tp = c.infer_type(schema) or (
                    schema[name].type if name in schema else None
                )
                assert_or_throw(tp is not None, ValueError(f"can't infer {c}"))
                plans.append((name, tp, c))
                if name in schema:
                    schema = schema.alter(Schema([(name, tp)]))
                else:
                    schema = schema + Schema([(name, tp)])

            def _assign_prog(mcols: Dict[str, Any]) -> Dict[str, Any]:
                outs: Dict[str, Any] = {}
                for name, _tp, c in plans:
                    v, m = expr_eval.eval_expr(mcols, c, pad_n, dicts)
                    outs[f"v:{name}"] = v
                    if m is not None:
                        outs[f"m:{name}"] = m
                return outs

            outs = self._jit_cached(
                ("assign", tuple(c.__uuid__() for c in columns), pad_n,
                 expr_eval.dict_fingerprint(blocks)),
                _assign_prog,
            )(expr_eval.blocks_to_masked(blocks))
            new_cols = dict(blocks.columns)
            made: Dict[str, Tuple[Any, ...]] = {}
            for name, tp, c in plans:
                # bare column references keep their dictionary/stats
                # (same rule as _device_project)
                src = (
                    blocks.columns.get(c.name)
                    if isinstance(c, _NamedColumnExpr) and c.as_type is None
                    else None
                )
                dict_r = (
                    src.host_dict
                    if src is not None
                    else (
                        expr_eval.result_dictionary(c, blocks)
                        if pa.types.is_string(tp)
                        else None
                    )
                )
                data = outs[f"v:{name}"]
                stats = src.stats if src is not None else None
                if dict_r is not None and src is None:
                    data, dict_r, stats = expr_eval.finalize_string_result(
                        data, dict_r
                    )
                made[name] = (tp, data, outs.get(f"m:{name}"), dict_r, stats)
            placed = place_rows(
                {n: (d, m) for n, (_, d, m, _, _) in made.items()},
                blocks.mesh, self._host_reshards,
            )
            for name, (tp, _, _, dict_r, stats) in made.items():
                data, mask = placed[name]
                new_cols[name] = JaxColumn(tp, data, mask, dict_r, stats)
            return JaxDataFrame(blocks_with_columns(blocks, new_cols), schema)
        self._count_fallback("assign")
        return self.to_df(self._native.assign(jdf.as_local_bounded(), columns))

    def aggregate(
        self,
        df: DataFrame,
        partition_spec: Optional[PartitionSpec],
        agg_cols: List[ColumnExpr],
    ) -> DataFrame:
        keys = partition_spec.partition_by if partition_spec is not None else []
        # long-context path: an ITERABLE input streams through donated
        # device accumulators chunk by chunk — the dataset never needs to
        # fit in device (or host) memory at once (see streaming.py)
        res = self._try_stream_aggregate(df, keys, agg_cols)
        if res is not None:
            return res
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        res = self._try_device_aggregate(jdf, keys, agg_cols)
        if res is not None:
            return res
        self._count_fallback("aggregate")
        return self.to_df(
            self._native.aggregate(
                jdf.as_local_bounded(), partition_spec, agg_cols
            )
        )

    # ---- device implementations of engine primitives --------------------
    def repartition(self, df: DataFrame, partition_spec: PartitionSpec) -> DataFrame:
        """Mesh sharding is fixed (rows are row-sharded over devices), so
        repartition is a device ROW REORDER: after it, contiguous even
        chunks of the frame equal the requested partitioning — hash groups
        equal-key rows together, rand applies a seeded permutation. The
        host map fallback's contiguous splitter then yields exactly the
        intended membership (reference fugue_spark/_utils/partition.py)."""
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        algo = partition_spec.algo
        if algo not in ("hash", "rand"):
            return jdf  # default/even/coarse: sharding already uniform
        blocks = jdf.blocks
        by = [
            k
            for k in (partition_spec.partition_by or jdf.schema.names)
            if k in blocks.columns
        ]
        if not all(blocks.columns[k].on_device for k in by):
            return jdf
        num = partition_spec.get_num_partitions(
            **{
                KEYWORD_ROWCOUNT: lambda: blocks.nrows,
                KEYWORD_PARALLELISM: lambda: self.get_current_parallelism(),
            }
        )
        if algo == "hash":
            if num <= 1:
                return jdf
            fr = groupby.factorize_keys(blocks, by)
            seg = np.asarray(fr.seg)
            part = seg % num
            valid = np.asarray(blocks.validity())
            # order by (partition id, key id) so equal keys stay contiguous
            # even when distinct keys collide into one partition; invalid
            # rows sort last via the out-of-range sentinels (int64 literals
            # would WRAP in the int32 seg dtype under NEP50)
            idx = np.lexsort(
                (np.where(valid, seg, seg.max() + 1),
                 np.where(valid, part, num))
            )[: int(valid.sum())]
        else:  # rand
            valid = np.asarray(blocks.validity())
            vidx = np.nonzero(valid)[0]
            idx = vidx[np.random.default_rng(42).permutation(len(vidx))]
        from fugue_tpu.jax_backend.blocks import gather_indices

        return JaxDataFrame(
            gather_indices(
                blocks, jnp.asarray(idx), jdf.schema, self._host_reshards
            ),
            jdf.schema,
        )

    def broadcast(self, df: DataFrame) -> DataFrame:
        return self.to_df(df)

    def persist(self, df: DataFrame, lazy: bool = False, **kwargs: Any) -> DataFrame:
        from fugue_tpu.jax_backend.zipped import JaxZippedDataFrame

        if isinstance(df, JaxZippedDataFrame):
            return df
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        if not lazy:
            from fugue_tpu.jax_backend.blocks import residency_arrays

            # EVERY device array: column data, column masks AND row_valid
            # — persist means "materialize NOW", and on a locally attached
            # chip block_until_ready returning means the bytes are resident
            arrs = residency_arrays(jdf.blocks)
            with start_span("engine.device_sync", op="persist"):
                jax.block_until_ready(arrs)
        if not jdf.is_pending:
            # persisted frames are the spillable population of the memory
            # governor's LRU (registered here if ingest didn't)
            self._memory.mark_persisted(jdf.blocks)
        return jdf

    def zip(
        self,
        dfs: Any,
        how: str = "inner",
        partition_spec: Optional[PartitionSpec] = None,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> DataFrame:
        """Device zip: RECORDS the co-partition (member frames + keys) in a
        JaxZippedDataFrame instead of pickling partitions into blob rows
        and unioning them (the reference design this replaces:
        execution_engine.py:969-1360; SURVEY §3.5 'the piece to
        re-architect on TPU'). comap then assembles key groups from one
        columnar export per member — serialize_df is never called.
        Disable with ``fugue.jax.device_zip=false``."""
        from fugue_tpu.constants import FUGUE_CONF_JAX_DEVICE_ZIP
        from fugue_tpu.jax_backend.zipped import JaxZippedDataFrame

        hownorm = how.lower().replace(" ", "_")
        if self.conf.get(FUGUE_CONF_JAX_DEVICE_ZIP, True) and hownorm in (
            "inner", "left_outer", "right_outer", "full_outer", "cross",
        ):
            assert_or_throw(len(dfs) > 0, ValueError("can't zip 0 dataframes"))
            spec = partition_spec or PartitionSpec()
            keys: List[str] = list(spec.partition_by)
            # members stay AS THEY ARE (device or local): comap exports to
            # pandas anyway, so converting local frames to device here would
            # be an upload immediately followed by a download
            members: List[DataFrame] = list(dfs.values())
            if len(keys) == 0 and hownorm != "cross":
                keys = [
                    n
                    for n in members[0].schema.names
                    if all(n in m.schema for m in members)
                ]
                assert_or_throw(
                    len(keys) > 0, ValueError("no common keys to zip by")
                )
            if hownorm == "cross":
                assert_or_throw(
                    len(keys) == 0, ValueError("cross zip can't have keys")
                )
            names = list(dfs.keys()) if dfs.has_dict else [""] * len(dfs)
            key_schema = Schema([members[0].schema[k] for k in keys])
            return JaxZippedDataFrame(
                members, names, hownorm, keys, key_schema, spec
            )
        self._count_fallback("zip", "device zip disabled or exotic zip type")
        return super().zip(
            dfs, how=how, partition_spec=partition_spec,
            temp_path=temp_path, to_file_threshold=to_file_threshold,
        )

    def comap(
        self,
        df: DataFrame,
        map_func: Callable,
        output_schema: Any,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable] = None,
    ) -> DataFrame:
        from fugue_tpu.jax_backend.comap_compiled import (
            HostPathRequired,
            compiled_comap,
        )
        from fugue_tpu.jax_backend.zipped import (
            JaxZippedDataFrame,
            device_comap,
        )

        if isinstance(df, JaxZippedDataFrame):
            raw = self._extract_cotransform_jax_func(map_func, len(df.frames))
            if raw is not None:
                runner = getattr(map_func, "__self__", None)
                if getattr(runner, "ignore_errors", ()):
                    # per-group error swallowing needs the host group loop
                    self._count_fallback(
                        "comap", "ignore_errors needs the host group loop"
                    )
                else:
                    try:
                        return compiled_comap(
                            self, df, raw, output_schema, partition_spec,
                            on_init,
                        )
                    except HostPathRequired as e:
                        self._count_fallback("comap", str(e))
                    except _StringDictUnavailable as e:
                        self._count_fallback(
                            "comap",
                            f"string output '{e}' has no decode table",
                        )
            return device_comap(
                self, df, map_func, output_schema, partition_spec, on_init
            )
        return super().comap(
            df, map_func, output_schema, partition_spec, on_init
        )

    def _extract_cotransform_jax_func(
        self, map_func: Callable, n_members: int
    ) -> Optional[Callable]:
        """The raw user function behind a jax-annotated cotransformer: one
        ``Dict[str, jax.Array]`` parameter per zipped member, dict output."""
        runner = getattr(map_func, "__self__", None)
        tf = getattr(runner, "transformer", None)
        wrapper = getattr(tf, "wrapper", None)
        if (
            wrapper is not None
            and wrapper.input_code == "j" * n_members
            and wrapper.output_code == "j"
        ):
            return wrapper.func
        return None

    def join(
        self,
        df1: DataFrame,
        df2: DataFrame,
        how: str,
        on: Optional[List[str]] = None,
    ) -> DataFrame:
        """Device join via shared key factorization (see relational.py):
        semi/anti flip validity masks (zero syncs); inner/left/right/full/
        cross enumerate matches on device with ONE host sync for the output
        row count. Null keys never match (SQL). Falls back to the host
        pandas path only for host-resident (nested/binary) columns."""
        from fugue_tpu.dataframe.utils import get_join_schemas

        j1: JaxDataFrame = self.to_df(df1)  # type: ignore
        j2: JaxDataFrame = self.to_df(df2)  # type: ignore
        j1, j2 = self._align_meshes(j1, j2)
        hownorm = how.lower().replace("_", "").replace(" ", "")
        key_schema, output_schema = get_join_schemas(j1, j2, hownorm, on)
        keys = list(key_schema.names)
        b1, b2 = j1.blocks, j2.blocks
        if relational.device_joinable(
            b1, b2, j1.schema.names, j2.schema.names
        ):
            if hownorm in ("semi", "leftsemi", "anti", "leftanti"):
                out = relational.semi_anti_join(
                    self, b1, b2, keys, anti=hownorm in ("anti", "leftanti")
                )
                return JaxDataFrame(out, output_schema)
            if hownorm in ("inner", "cross", "leftouter", "fullouter"):
                out = relational.expand_join(
                    self, b1, b2, keys, hownorm, j1.schema, j2.schema,
                    output_schema,
                )
                return JaxDataFrame(out, output_schema)
            if hownorm == "rightouter":
                # left join with sides swapped, columns reordered
                _, swapped_schema = get_join_schemas(
                    j2, j1, "leftouter", keys
                )
                out = relational.expand_join(
                    self, b2, b1, keys, "leftouter", j2.schema, j1.schema,
                    swapped_schema,
                )
                cols = {
                    n: out.columns[n] for n in output_schema.names
                }
                return JaxDataFrame(
                    JaxBlocks(
                        out._nrows, cols, out.mesh,
                        row_valid=out.row_valid, nrows_dev=out._nrows_dev,
                    ),
                    output_schema,
                )
        self._count_fallback("join", "host-resident columns")
        return self._host_op(
            lambda a, b: self._native.join(a, b, how=how, on=on), df1, df2
        )

    def union(self, df1: DataFrame, df2: DataFrame, distinct: bool = True) -> DataFrame:
        j1: JaxDataFrame = self.to_df(df1)  # type: ignore
        j2: JaxDataFrame = self.to_df(df2)  # type: ignore
        j1, j2 = self._align_meshes(j1, j2)
        assert_or_throw(
            j1.schema == j2.schema,
            ValueError(f"union schema mismatch {j1.schema} vs {j2.schema}"),
        )
        if j1.blocks.all_on_device and j2.blocks.all_on_device:
            out = JaxDataFrame(
                relational.union_all_blocks(j1.blocks, j2.blocks), j1.schema
            )
            return self.distinct(out) if distinct else out
        self._count_fallback("union", "host-resident columns")
        return self._host_op(
            lambda a, b: self._native.union(a, b, distinct=distinct), df1, df2
        )

    def subtract(
        self, df1: DataFrame, df2: DataFrame, distinct: bool = True
    ) -> DataFrame:
        return self._set_op(df1, df2, distinct, subtract=True)

    def intersect(
        self, df1: DataFrame, df2: DataFrame, distinct: bool = True
    ) -> DataFrame:
        return self._set_op(df1, df2, distinct, subtract=False)

    def _set_op(
        self, df1: DataFrame, df2: DataFrame, distinct: bool, subtract: bool
    ) -> DataFrame:
        name = "subtract" if subtract else "intersect"
        j1: JaxDataFrame = self.to_df(df1)  # type: ignore
        j2: JaxDataFrame = self.to_df(df2)  # type: ignore
        j1, j2 = self._align_meshes(j1, j2)
        assert_or_throw(
            j1.schema == j2.schema,
            ValueError(f"{name} schema mismatch {j1.schema} vs {j2.schema}"),
        )
        if j1.blocks.all_on_device and j2.blocks.all_on_device:
            out = relational.intersect_subtract(
                self, j1.blocks, j2.blocks, j1.schema.names, subtract,
                distinct=distinct,
            )
            return JaxDataFrame(out, j1.schema)
        self._count_fallback(name, "host-resident columns")
        host = (
            self._native.subtract if subtract else self._native.intersect
        )
        return self._host_op(
            lambda a, b: host(a, b, distinct=distinct), df1, df2
        )

    def distinct(self, df: DataFrame) -> DataFrame:
        """Mask-only distinct: factorize all columns, keep each segment's
        representative row by flipping validity — no gather, and zero host
        syncs on the binned path."""
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        blocks = jdf.blocks
        if blocks.all_on_device and not (
            blocks.nrows_known and blocks.nrows == 0
        ):
            fr = groupby.factorize_keys(blocks, jdf.schema.names)

            def _distinct_prog(
                seg: Any,
                first_idx: Any,
                row_valid: Optional[Any],
                nrows_s: Any,
            ) -> Any:
                pad_n = seg.shape[0]
                row_valid = groupby.materialize_validity(
                    row_valid, pad_n, nrows_s
                )
                pos = jnp.arange(pad_n, dtype=jnp.int32)
                # invalid rows' sentinel seg clamps OOB on gather; they
                # stay invalid regardless
                return row_valid & (first_idx[seg] == pos)

            keep = self._jit_cached(
                ("distinct", blocks.padded_nrows, fr.num_segments),
                _distinct_prog,
            )(fr.seg, fr.first_idx, blocks.row_valid, _nrows_arg(blocks))
            return JaxDataFrame(
                JaxBlocks(
                    None,
                    dict(blocks.columns),
                    blocks.mesh,
                    row_valid=keep,
                    nrows_dev=fr.num_groups_dev,
                ),
                jdf.schema,
            )
        self._count_fallback("distinct")
        return self.to_df(self._native.distinct(jdf.as_local_bounded()))

    def dropna(
        self,
        df: DataFrame,
        how: str = "any",
        thresh: Optional[int] = None,
        subset: Optional[List[str]] = None,
    ) -> DataFrame:
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        blocks = jdf.blocks
        names = subset if subset is not None else jdf.schema.names
        if all(
            n in blocks.columns and blocks.columns[n].on_device for n in names
        ):
            pad_n = blocks.padded_nrows
            masks = {
                n: blocks.columns[n].mask
                for n in names
                if blocks.columns[n].mask is not None
            }

            def _dropna_prog(
                masks_: Dict[str, Any],
                row_valid: Optional[Any],
                nrows_s: Any,
            ) -> Tuple[Any, Any]:
                row_valid = groupby.materialize_validity(
                    row_valid, pad_n, nrows_s
                )
                valid_count = jnp.full((pad_n,), len(names) - len(masks_),
                                       dtype=jnp.int32)
                for m in masks_.values():
                    valid_count = valid_count + m.astype(jnp.int32)
                if thresh is not None:
                    keep = valid_count >= thresh
                elif how == "any":
                    keep = valid_count == len(names)
                else:  # all
                    keep = valid_count > 0
                keep = keep & row_valid
                return keep, jnp.sum(keep).astype(jnp.int32)

            keep, cnt = self._jit_cached(
                ("dropna", pad_n, how, thresh, tuple(sorted(names))),
                _dropna_prog,
            )(masks, blocks.row_valid, _nrows_arg(blocks))
            return JaxDataFrame(
                JaxBlocks(
                    None,
                    dict(blocks.columns),
                    blocks.mesh,
                    row_valid=keep,
                    nrows_dev=cnt,
                ),
                jdf.schema,
            )
        self._count_fallback("dropna")
        return self.to_df(
            self._native.dropna(
                jdf.as_local_bounded(), how=how, thresh=thresh, subset=subset
            )
        )

    def fillna(
        self, df: DataFrame, value: Any, subset: Optional[List[str]] = None
    ) -> DataFrame:
        """Device fillna: one jitted mask-flip + ``jnp.where`` per frame —
        the block layout makes this trivial (masked slots take the fill
        value, the mask drops). Float columns also fill literal NaNs in the
        data, matching pandas semantics."""
        assert_or_throw(
            (not isinstance(value, dict))
            or all(v is not None for v in value.values()),
            ValueError("fillna dict can't contain None"),
        )
        assert_or_throw(value is not None, ValueError("fillna value can't be None"))
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        blocks = jdf.blocks
        if isinstance(value, dict):
            fills: Dict[str, Any] = dict(value)
        elif subset is not None:
            fills = {c: value for c in subset}
        else:
            fills = {c: value for c in jdf.schema.names}
        targets = {
            n: v
            for n, v in fills.items()
            if n in blocks.columns
        }
        res = relational.device_fillna(self, blocks, jdf.schema, targets)
        if res is not None:
            return JaxDataFrame(res, jdf.schema)
        self._count_fallback("fillna", "host-resident or untypable fill")
        return self.to_df(
            self._native.fillna(jdf.as_local_bounded(), value=value, subset=subset)
        )

    def sample(
        self,
        df: DataFrame,
        n: Optional[int] = None,
        frac: Optional[float] = None,
        replace: bool = False,
        seed: Optional[int] = None,
    ) -> DataFrame:
        assert_or_throw(
            (n is None) != (frac is None),
            ValueError("one and only one of n and frac must be set"),
        )
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        blocks = jdf.blocks
        if not replace:
            # mask-only device sampling, zero host syncs: frac keeps rows
            # under a uniform threshold; exact-n keeps the n smallest
            # uniforms (the n-th order statistic is computed in-program)
            res = relational.device_sample(self, blocks, n, frac, seed)
            return JaxDataFrame(res, jdf.schema)
        # replace=True duplicates rows (changes the row multiset) — host RNG
        # gather; not a "fallback" per se (no device path exists for it)
        if blocks.row_valid is not None:
            valid_idx = np.nonzero(np.asarray(blocks.row_valid))[0]
        else:
            valid_idx = np.arange(blocks.nrows)
        total = len(valid_idx)
        rng = np.random.default_rng(seed)
        count = n if n is not None else int(round(total * frac))  # type: ignore
        idx = valid_idx[rng.choice(total, size=count, replace=True)]
        return JaxDataFrame(
            gather_indices(
                jdf.blocks, jnp.asarray(np.sort(idx)), jdf.schema,
                self._host_reshards,
            ),
            jdf.schema,
        )

    def take(
        self,
        df: DataFrame,
        n: int,
        presort: str,
        na_position: str = "last",
        partition_spec: Optional[PartitionSpec] = None,
    ) -> DataFrame:
        assert_or_throw(
            isinstance(n, int) and n >= 0,
            ValueError("n must be a non-negative int"),
        )
        assert_or_throw(
            na_position in ("first", "last"), ValueError("invalid na_position")
        )
        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        partition_spec = partition_spec or PartitionSpec()
        from fugue_tpu.collections.partition import parse_presort_exp

        sorts = (
            parse_presort_exp(presort) if presort else partition_spec.presort
        )
        res = relational.device_take(
            self, jdf.blocks, jdf.schema, n, sorts, na_position,
            list(partition_spec.partition_by),
        )
        if res is not None:
            return JaxDataFrame(res, jdf.schema)
        self._count_fallback("take", "host-resident sort/partition column")
        return self.to_df(
            self._native.take(
                jdf.as_local_bounded(), n, presort, na_position, partition_spec
            )
        )

    def load_df(
        self,
        path: Union[str, List[str]],
        format_hint: Any = None,
        columns: Any = None,
        **kwargs: Any,
    ) -> DataFrame:
        from fugue_tpu.constants import FUGUE_CONF_JAX_IO_BATCH_ROWS

        # optimizer-attached row-group pruning triples (ADVISORY: the
        # downstream filter re-applies the predicate, so ignoring them
        # on the eager path is always correct)
        pruning = kwargs.pop("pruning", None)
        first = path if isinstance(path, str) else path[0]
        if _lake_io.is_lake_uri(first):
            # lake reads resolve a SNAPSHOT (version/timestamp) and prune
            # whole files from manifest stats — forward the triples; the
            # row-group streaming path doesn't apply to manifest-driven
            # multi-file reads
            from fugue_tpu.utils import io as _io

            local = _io.load_df(
                path, format_hint, columns, fs=self.fs,
                pruning=pruning, conf=self.conf, **kwargs
            )
            res = self.to_df(local)
            from fugue_tpu.lake import parse_lake_uri

            _, params = parse_lake_uri(first)
            pinned = (
                kwargs.get("version") is not None
                or kwargs.get("timestamp") is not None
                or "version" in params
                or "timestamp" in params
            )
            if pinned:
                # a PINNED snapshot is deterministic lineage: device-loss
                # recovery can re-read the exact same data (an unpinned
                # read would re-resolve to a possibly newer version)
                self._attach_load_lineage(
                    res,
                    lambda: _io.load_df(
                        path, format_hint, columns, fs=self.fs,
                        pruning=pruning, conf=self.conf, **kwargs
                    ).as_arrow(),
                )
            return res
        batch_rows = int(self.conf.get(FUGUE_CONF_JAX_IO_BATCH_ROWS, 0))
        if batch_rows > 0:
            from fugue_tpu.jax_backend import ingest

            res = ingest.try_stream_load(
                self, path, format_hint, columns, batch_rows,
                pruning=pruning, **kwargs
            )
            if res is not None:
                return res
        from fugue_tpu.utils import io as _io

        local = _io.load_df(path, format_hint, columns, fs=self.fs, **kwargs)
        res = self.to_df(local)
        # the stored artifact (data file or checkpoint) IS the lineage
        self._attach_load_lineage(
            res,
            lambda: _io.load_df(
                path, format_hint, columns, fs=self.fs, **kwargs
            ).as_arrow(),
        )
        return res

    def save_df(
        self,
        df: DataFrame,
        path: str,
        format_hint: Any = None,
        mode: str = "overwrite",
        partition_spec: Optional[PartitionSpec] = None,
        force_single: bool = False,
        **kwargs: Any,
    ) -> None:
        from fugue_tpu.constants import FUGUE_CONF_JAX_IO_BATCH_ROWS
        from fugue_tpu.utils import io as _io

        jdf: JaxDataFrame = self.to_df(df)  # type: ignore
        batch_rows = int(self.conf.get(FUGUE_CONF_JAX_IO_BATCH_ROWS, 0))
        partition_cols = _io.spec_partition_cols(partition_spec, force_single)
        if _lake_io.is_lake_uri(path):
            # lake saves are transactional manifest commits, not file
            # replacement — the pipelined row-group writer doesn't apply
            _io.save_df(
                jdf.as_local_bounded(), path, format_hint, mode,
                partition_cols=partition_cols, fs=self.fs, **kwargs,
            )
            return
        if batch_rows > 0:
            # pipelined save (fugue.jax.io.pipeline): row-group writes of
            # chunk k overlap the device->host fetch of chunk k+1, so the
            # parquet encode rides the tail of compute instead of waiting
            # for the full readback; falls through to the eager path for
            # targets/frames it does not cover
            from fugue_tpu.jax_backend import ingest

            if ingest.try_pipelined_save(
                self, jdf, path, format_hint, mode, partition_cols,
                batch_rows, dict(kwargs),
            ):
                return
            kwargs.setdefault("batch_rows", batch_rows)
        _io.save_df(
            jdf.as_local_bounded(), path, format_hint, mode,
            partition_cols=partition_cols,
            fs=self.fs, **kwargs,
        )

    def convert_yield_dataframe(self, df: DataFrame, as_local: bool) -> DataFrame:
        return df.as_local() if as_local else df

    # ---- helpers ---------------------------------------------------------
    def _host_op(self, func: Callable, *dfs: DataFrame) -> DataFrame:
        locals_ = [self.to_df(d).as_local_bounded() for d in dfs]
        return self.to_df(func(*locals_))

    def _can_select_on_device(
        self,
        jdf: JaxDataFrame,
        cols: SelectColumns,
        where: Optional[ColumnExpr],
        having: Optional[ColumnExpr],
    ) -> bool:
        if having is not None and not cols.has_agg:
            return False  # invalid SQL: host owns the error
        if cols.is_distinct:
            return False
        blocks = jdf.blocks
        if where is not None and (
            not expr_eval.can_eval_on_device(where, blocks)
            or expr_eval.is_string_result(where, blocks)
        ):
            return False
        if not cols.has_agg:
            return all(
                expr_eval.can_eval_on_device(c, blocks) for c in cols.all_cols
            )
        # aggregation: group keys are device columns (string keys group by
        # dictionary code) or device-evaluable expressions, which get
        # materialized as key columns before the aggregate
        for k in cols.group_keys:
            if (
                isinstance(k, _NamedColumnExpr)
                and k.as_type is None
                and k.output_name == k.name
            ):
                col = blocks.columns.get(k.name)
                if col is None or not col.on_device:
                    return False
                continue
            name = k.output_name
            if name == "" or name in blocks.columns:
                # unnamed, or shadowing an existing column an agg arg
                # might still reference: host handles it
                return False
            if not expr_eval.can_eval_on_device(k, blocks):
                return False
        from fugue_tpu.column.expressions import _FuncExpr

        for a in cols.agg_funcs:
            if not isinstance(a, _FuncExpr) or len(a.args) != 1:
                return False
            fn = a.func.lower()
            if fn not in _DEVICE_AGGS:
                return False
            if a.arg_distinct and fn not in _DEVICE_DISTINCT_AGGS:
                return False
            arg = a.args[0]
            if isinstance(arg, _NamedColumnExpr) and arg.wildcard:
                continue
            if not expr_eval.can_eval_on_device(arg, blocks) or (
                expr_eval.is_string_result(arg, blocks) and fn != "count"
            ):
                return False
        return True

    def _device_project(
        self, jdf: JaxDataFrame, cols: SelectColumns, out_schema: Schema
    ) -> DataFrame:
        blocks = jdf.blocks
        pad_n = blocks.padded_nrows
        dicts = expr_eval.dicts_of(blocks)
        exprs = list(cols.all_cols)

        def _project_prog(mcols: Dict[str, Any]) -> Dict[str, Any]:
            outs: Dict[str, Any] = {}
            for c, f in zip(exprs, out_schema.fields):
                v, m = expr_eval.eval_expr(mcols, c, pad_n, dicts)
                outs[f"v:{f.name}"] = v
                if m is not None:
                    outs[f"m:{f.name}"] = m
            return outs

        outs = self._jit_cached(
            ("project", tuple(c.__uuid__() for c in exprs), pad_n,
             expr_eval.dict_fingerprint(blocks)),
            _project_prog,
        )(expr_eval.blocks_to_masked(blocks))
        made: Dict[str, Tuple[Any, ...]] = {}
        for c, f in zip(exprs, out_schema.fields):
            # plain column references keep their stats/dictionary
            src = (
                blocks.columns.get(c.name)
                if isinstance(c, _NamedColumnExpr) and c.as_type is None
                else None
            )
            dict_r = (
                src.host_dict
                if src is not None
                else (
                    expr_eval.result_dictionary(c, blocks)
                    if pa.types.is_string(f.type)
                    else None
                )
            )
            data = outs[f"v:{f.name}"]
            stats = src.stats if src is not None else None
            if dict_r is not None and src is None:
                data, dict_r, stats = expr_eval.finalize_string_result(
                    data, dict_r
                )
            made[f.name] = (
                f.type, data, outs.get(f"m:{f.name}"), dict_r, stats
            )
        placed = place_rows(
            {n: (d, m) for n, (_, d, m, _, _) in made.items()},
            blocks.mesh, self._host_reshards,
        )
        new_cols: Dict[str, JaxColumn] = {}
        for name, (tp, _, _, dict_r, stats) in made.items():
            data, mask = placed[name]
            new_cols[name] = JaxColumn(tp, data, mask, dict_r, stats)
        return JaxDataFrame(
            blocks_with_columns(blocks, new_cols), out_schema
        )

    def _device_groupby_select(
        self,
        jdf: JaxDataFrame,
        cols: SelectColumns,
        out_schema: Schema,
        having: Optional[ColumnExpr],
    ) -> Optional[DataFrame]:
        keys: List[str] = []
        computed: List[ColumnExpr] = []
        for k in cols.group_keys:
            if (
                isinstance(k, _NamedColumnExpr)
                and k.as_type is None
                and k.output_name == k.name
            ):
                keys.append(k.name)
            else:
                # expression OR aliased key: materialize it as a key
                # column first (a bare-ref rename keeps dictionary and
                # stats; _can_select_on_device guarantees a fresh name)
                computed.append(k)
                keys.append(k.output_name)
        if computed:
            jdf = self.to_df(self.assign(jdf, computed))  # type: ignore
        agg_exprs = list(cols.agg_funcs)
        visible = [c.output_name for c in cols.all_cols]
        having2: Optional[ColumnExpr] = None
        extra: Dict[str, ColumnExpr] = {}
        if having is not None:
            # HAVING refers to aggregations: rewrite agg subtrees into
            # refs over the aggregated output, computing HIDDEN agg
            # columns as needed, filter, then drop the hidden columns
            from fugue_tpu.column.pandas_eval import _rewrite_having

            computed_map = {
                c.alias("").__uuid__(): c.output_name
                for c in cols.agg_funcs
            }
            having2 = _rewrite_having(having, computed_map, extra)
            agg_exprs = agg_exprs + list(extra.values())
        res = self._try_device_aggregate(
            jdf, keys, agg_exprs, out_schema=out_schema,
            col_order=visible + list(extra.keys()),
        )
        if res is None or having2 is None:
            return res
        jres: JaxDataFrame = self.to_df(self.filter(res, having2))  # type: ignore
        if extra:
            jres = JaxDataFrame(
                blocks_with_columns(
                    jres.blocks,
                    {n: jres.blocks.columns[n] for n in visible},
                ),
                jres.schema.extract(visible),
            )
        return jres

    def _jit_cached(
        self, key: Any, fn: Callable, static_argnums: Any = None
    ) -> Callable:
        """Per-engine jit cache: logical programs (aggregate plans, map fns,
        filters) are keyed by structure so repeated queries reuse the
        compiled executable. Keys never include row counts — those enter
        programs as traced scalars/masks.

        ``static_argnums`` passes through to ``jax.jit``; a static-arg
        program bypasses the disk tier (the exec-cache signature scheme is
        value-independent for host scalars, and an AOT executable is
        compiled for ONE static value — serving another would be wrong).
        Every distinct static value is a fresh trace, which the retrace
        sentinel counts against the program's budget like any other.

        Each call records (fn, arg avals) in the program log so
        ``program_cost_analysis`` can AOT-lower the exact program later and
        read XLA's own flops/bytes accounting."""
        cache = getattr(self, "_jit_cache", None)
        if cache is None:
            cache = {}
            self._jit_cache = cache
        local = cache.get(key)
        if local is not None:
            # engine-local reuse is a plan-cache hit too: the compiled
            # handle is shared either way (one counter, two tiers)
            self._plan_hits.inc()
            return local
        # process-wide handle reuse: a same-signature engine already
        # jitted this logical program → its per-shape executables
        # come along for free (zero XLA compile on this engine)
        global_key = (self._plan_sig, key)
        jitted = self._plan_cache.get_program(global_key)
        if jitted is None:
            jitted = (
                jax.jit(fn)
                if static_argnums is None
                else jax.jit(fn, static_argnums=static_argnums)
            )
            self._plan_cache.put_program(global_key, jitted)
            self._plan_misses.inc()
        else:
            self._plan_hits.inc()
        name = str(key[0]) if isinstance(key, tuple) and key else str(key)
        disk_ok = self._exec_enabled and static_argnums is None

        def _wrapped(
            *args: Any, _j: Any = jitted, _f: Callable = fn, _k: Any = key,
            _n: str = name, _disk: bool = disk_ok,
        ) -> Any:
            if self._program_log_armed:
                self._program_log[_k] = (
                    _f, jax.tree_util.tree_map(_as_aval, args)
                )
            if _disk:
                return self._dispatch_with_disk_tier(_j, _f, _k, _n, args)
            return self._traced_dispatch(_j, _n, args, key=_k)

        cache[key] = _wrapped
        return _wrapped

    def _dispatch_with_disk_tier(
        self, jitted: Any, fn: Callable, key: Any, name: str, args: Any
    ) -> Any:
        """Dispatch with the persistent-executable tier in front of the
        jit path: a shape this process never compiled first probes the
        disk cache (deserialize ≪ compile); a shape the jit path already
        compiled skips the probe forever. A deserialized executable that
        rejects the live inputs (layout/sharding drift) falls back to
        the jit path — the tier can lose time, never correctness."""
        from fugue_tpu.optimize.exec_cache import (
            args_signature,
            fn_source_hash,
        )

        sig = args_signature(args)
        if sig is None:
            # a leaf the signature scheme does not model (host object,
            # uncommitted np array): the disk tier skips this program
            return self._traced_dispatch(jitted, name, args, key=key)
        # the key folds the cache BASE URI (the probe/compiled/persist
        # bookkeeping describes one disk's state — two same-signature
        # engines pointed at different dirs must not starve each other)
        # and the FN SOURCE HASH (a code change under the same logical
        # key must never hit a warm-loaded stale executable)
        exec_key = (
            self._exec_cache.base_uri,
            (self._plan_sig, key),
            fn_source_hash(fn),
            sig.token,
        )
        want_persist = False
        compiled = self._plan_cache.get_executable(exec_key)
        if compiled is None and not self._plan_cache.was_compiled(exec_key):
            compiled = self._load_executable(key, fn, sig, exec_key)
            # the disk has no (valid) entry for this shape: persist one
            # after the jit dispatch below — even when the jit handle
            # already owns the executable (compiled by an earlier
            # same-signature engine), the disk must still learn it, or a
            # warm in-memory tier would starve the cross-process tier
            want_persist = compiled is None
        if compiled is not None:
            try:
                t0 = time.perf_counter()
                with start_span(
                    "engine.dispatch",
                    program=name,
                    devices=int(self._mesh.devices.size),
                ) as sp:
                    out = compiled(*args)
                    if sp:
                        sp.set_attr(compiled=0)
                        sp.name = "engine.execute"
                # an AOT dispatch is compile-free by construction: it
                # counts as a hit on the per-dispatch compile surface
                self._compile_hits.inc()
                self._add_dispatch_secs(
                    "execute", time.perf_counter() - t0
                )
                return out
            except Exception as ex:
                # ANY failure of a deserialized executable — python-level
                # aval/sharding mismatch (ValueError/TypeError) or an
                # XLA runtime rejection the token scheme cannot model —
                # drops the entry and falls back to the jit path, whose
                # fresh persist below OVERWRITES the disk entry: a bad
                # cached executable may lose time, never correctness,
                # and can never poison a query across restarts
                self._plan_cache.drop_executable(exec_key)
                want_persist = True
                self.log.info(
                    "fugue_tpu exec-cache: cached executable for %s "
                    "rejected live inputs (%s: %s); recompiling",
                    name, type(ex).__name__, ex,
                )
        return self._traced_dispatch(
            jitted, name, args,
            persist=(key, fn, sig, exec_key) if want_persist else None,
            key=key,
        )

    def _load_executable(
        self, key: Any, fn: Callable, sig: Any, exec_key: Any
    ) -> Optional[Any]:
        """One disk-tier probe: deserialize the entry for (program key,
        fn hash, avals) if present and version-valid; counts
        hit/miss/evict/corrupt under ``tier="disk"``."""
        dc = self._exec_cache
        eid = dc.entry_id(self._plan_sig, key, fn, sig.token)
        if eid is None:
            self._plan_cache.mark_compiled(exec_key)  # never probe again
            return None
        t0 = time.perf_counter()
        status, compiled, _meta = dc.load(dc.entry_uri(self._plan_sig, eid))
        elapsed = time.perf_counter() - t0
        if status == "hit":
            self._disk_hits.inc()
            self._m_deserialize.labels().observe(elapsed)
            self._add_dispatch_secs("disk_load", elapsed)
            self._plan_cache.put_executable(exec_key, compiled)
            return compiled
        # disjoint result labels (matching the warm-scan path): an
        # absent entry is a miss; a version-stale or unreadable one
        # counts ONLY as evict/corrupt — either way the caller compiles
        if status == "evict":
            self._disk_evicts.inc()
        elif status == "corrupt":
            self._disk_corrupt.inc()
        else:
            self._disk_misses.inc()
        return None

    def try_begin_warm(self) -> Optional[Callable[[], int]]:
        """SYNCHRONOUSLY claim the once-per-(cache dir, plan signature)
        executable warm and hand back the work to run (on any thread);
        None when the disk tier is off or another caller already owns
        the claim. Callers who must not lose the claim to a concurrent
        warm trigger (the daemon's readiness gate vs a streamed
        ingest's first-batch hook) claim here first, then run/spawn."""
        if not self._exec_enabled:
            return None
        if not self._plan_cache.claim_warm(
            (self._exec_cache.base_uri, self._plan_sig)
        ):
            return None
        return self._warm_executables_now

    def warm_executables(self, background: bool = False) -> Any:
        """Load every disk-tier entry matching this engine's plan
        signature into the in-memory executable store, so upcoming
        dispatches are compile-free AND deserialize-free. Runs at most
        once per (cache dir, plan signature) per process (the claim
        lives on the plan cache, taken on THIS thread). Returns the
        number of executables loaded — or, with ``background=True``,
        the started thread (None when there is nothing to do)."""
        work = self.try_begin_warm()
        if work is None:
            return None if background else 0
        if background:
            from fugue_tpu.optimize.exec_cache import spawn_warm_thread

            return spawn_warm_thread(work)
        return work()

    def _warm_executables_now(self) -> int:
        dc = self._exec_cache
        loaded = 0
        try:
            for uri in dc.scan(self._plan_sig):
                t0 = time.perf_counter()
                status, compiled, meta = dc.load(uri)
                if status == "hit" and meta is not None:
                    self._disk_hits.inc()
                    elapsed = time.perf_counter() - t0
                    self._m_deserialize.labels().observe(elapsed)
                    self._add_dispatch_secs("disk_load", elapsed)
                    self._plan_cache.put_executable(
                        (
                            dc.base_uri,
                            (meta["plan_sig"], meta["key"]),
                            # entries without a recorded fn hash can
                            # never match a live dispatch key: stale
                            # formats warm-load inert, never wrong
                            meta.get("fn_hash", ""),
                            meta["aval_token"],
                        ),
                        compiled,
                    )
                    loaded += 1
                elif status == "evict":
                    self._disk_evicts.inc()
                elif status == "corrupt":
                    self._disk_corrupt.inc()
        except Exception as ex:  # pragma: no cover - warm is best-effort
            self.log.warning(
                "fugue_tpu exec-cache: warm scan failed (%s: %s)",
                type(ex).__name__, ex,
            )
        if loaded:
            self.log.info(
                "fugue_tpu exec-cache: pre-warmed %d executables from %s",
                loaded, dc.base_uri,
            )
        return loaded

    def _traced_dispatch(
        self, jitted: Any, name: str, args: Any, persist: Any = None,
        key: Any = None,
    ) -> Any:
        """One jitted-program dispatch under the compile/execute span
        split. Whether THIS dispatch compiled is read from jax's own
        per-shape cache (``_cache_size`` growth), so shape-driven
        recompiles (row_bucket=0) and post-failure retries are labeled
        ``engine.compile`` too — the slow-query breakdown must pin
        multi-second compile time on the compile phase, not execute.

        ``persist`` (set by the disk-tier dispatch path) is the
        ``(key, fn, sig, exec_key)`` needed to background-persist the
        executable this dispatch is about to compile.

        ``key`` is the logical program key for the retrace sentinel's
        per-program trace accounting (None for unkeyed dispatches —
        counted under the program name alone)."""
        sizer = getattr(jitted, "_cache_size", None)
        before = -1
        if sizer is not None:
            try:
                before = sizer()
            except Exception:  # pragma: no cover - jax version drift
                sizer = None
        t0 = time.perf_counter()
        with start_span(
            "engine.dispatch", program=name, devices=int(self._mesh.devices.size)
        ) as sp:
            out = jitted(*args)
            compiled = False
            if sizer is not None:
                try:
                    compiled = sizer() > before
                except Exception:  # pragma: no cover
                    pass
            if compiled:
                self._compile_misses.inc()
                # retrace sentinel (debug twin of the FJX lint plane):
                # every ACTUAL trace is counted per program key; past the
                # budget the sentinel reports callsite + differing aval.
                # Off (the default) this is one module-global read.
                san = active_retrace_sentinel()
                if san is not None:
                    ev = san.note_trace(name, key, args)
                    if ev is not None:
                        self._m_retrace.labels(program=name).inc()
                        san.raise_if_armed(ev)
            else:
                self._compile_hits.inc()
            if sp:
                # the obs name settles once the dispatch revealed whether
                # it compiled; the profiler event, named at its start,
                # stays engine.dispatch and carries the attribute
                sp.set_attr(compiled=int(compiled))
                sp.name = "engine.compile" if compiled else "engine.execute"
        self._add_dispatch_secs(
            "compile" if compiled else "execute", time.perf_counter() - t0
        )
        if persist is not None:
            key, fn, sig, exec_key = persist
            # whichever way this dispatch went, the jit handle now owns
            # the shape in-process: later dispatches skip the disk probe
            self._plan_cache.mark_compiled(exec_key)
            # persist even when THIS dispatch did not compile — the
            # handle may carry an executable compiled before the disk
            # tier was watching (earlier same-signature engine), and the
            # probe above established the disk does not have it yet;
            # lower().compile() hits jax's in-memory caches either way
            self._exec_cache.schedule_persist(
                jitted, self._plan_sig, key, fn, sig, name,
                on_done=self._note_persist,
            )
        return out

    def _note_persist(self, ok: bool) -> None:
        (self._persist_ok if ok else self._persist_err).inc()

    def _map_program(
        self,
        key: Any,
        fn: Callable,
        array_args: Dict[str, Any],
        blocks: JaxBlocks,
        col_names: List[str],
        stash: Optional[Dict[str, Any]] = None,
    ) -> Tuple[Callable, Dict[str, str], Dict[str, Any]]:
        """Jit a compiled-map program and (once, at cache miss) analyze its
        jaxpr for column passthroughs: an output leaf that IS an input var
        carries the input column's value bounds, so stats (and dictionaries)
        propagate soundly through user transforms — the key enabler of
        sync-free group-by after a transform.

        ``stash`` collects fn-returned string decode tables at trace time;
        it is cached WITH the executable (the cache key includes the input
        dictionaries' identities, and the cached closure keeps them alive,
        so ``id`` reuse cannot alias entries)."""
        cache = getattr(self, "_map_cache", None)
        if cache is None:
            cache = {}
            self._map_cache = cache
        if key not in cache:
            inner = jax.jit(fn)

            def jitted(
                *args: Any, _j: Any = inner, _f: Callable = fn, _k: Any = key
            ) -> Any:
                # recorded like _jit_cached programs so the compiled map
                # shows up in program_cost_analysis (the headline's
                # transform traffic)
                if self._program_log_armed:
                    self._program_log[
                        ("map",) + (_k if isinstance(_k, tuple) else (_k,))
                    ] = (_f, jax.tree_util.tree_map(_as_aval, args))
                return self._traced_dispatch(_j, "map", args)
            passthrough: Dict[str, str] = {}
            try:
                shaped = {
                    k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in array_args.items()
                }
                rv = blocks.row_valid
                rv_s = (
                    None
                    if rv is None
                    else jax.ShapeDtypeStruct(rv.shape, rv.dtype)
                )
                closed = jax.make_jaxpr(fn)(
                    shaped, rv_s, jax.ShapeDtypeStruct((), jnp.int32)
                )
                in_leaves, in_tree = jax.tree_util.tree_flatten(
                    (shaped, rv_s, jax.ShapeDtypeStruct((), jnp.int32))
                )
                in_paths = [
                    p
                    for p, _ in jax.tree_util.tree_flatten_with_path(
                        (shaped, rv_s, jax.ShapeDtypeStruct((), jnp.int32))
                    )[0]
                ]
                # rebuild the output structure to get leaf names
                out_aval_tree = jax.eval_shape(
                    fn, shaped, rv_s, jax.ShapeDtypeStruct((), jnp.int32)
                )
                out_paths = [
                    p
                    for p, _ in jax.tree_util.tree_flatten_with_path(
                        out_aval_tree
                    )[0]
                ]
                invars = closed.jaxpr.invars
                outvars = closed.jaxpr.outvars
                var_to_in: Dict[Any, str] = {}
                for var, path in zip(invars, in_paths):
                    name = _path_leaf_key(path)
                    if name is not None:
                        var_to_in[var] = name
                for var, path in zip(outvars, out_paths):
                    name = _path_leaf_key(path)
                    if name is None or name.startswith("_"):
                        continue
                    src = var_to_in.get(var)
                    if src is not None and src in col_names:
                        passthrough[name] = src
            except Exception:  # pragma: no cover - analysis is best-effort
                passthrough = {}
            cache[key] = (jitted, passthrough, stash if stash is not None else {})
        return cache[key]

    def _try_device_aggregate(
        self,
        jdf: JaxDataFrame,
        keys: List[str],
        agg_cols: List[ColumnExpr],
        out_schema: Optional[Schema] = None,
        col_order: Optional[List[str]] = None,
    ) -> Optional[DataFrame]:
        from fugue_tpu.column.expressions import _FuncExpr

        blocks = jdf.blocks
        for k in keys:
            col = blocks.columns.get(k)
            if col is None or not col.on_device:
                return None
        plans = []
        distinct_args: Dict[str, str] = {}
        for c in agg_cols:
            if not isinstance(c, _FuncExpr) or len(c.args) != 1:
                return None
            fn = c.func.lower()
            if fn not in _DEVICE_AGGS:
                return None
            arg = c.args[0]
            if fn == "median" or fn in VARIANCE_FUNCS:
                # DISTINCT composes via the first-occurrence mask below
                tp0 = arg.infer_type(jdf.schema)
                if tp0 is None or not (
                    pa.types.is_integer(tp0)
                    or pa.types.is_floating(tp0)
                    or pa.types.is_boolean(tp0)
                ):
                    return None  # the host oracle owns the type error
            if c.arg_distinct:
                # DISTINCT: min/max are dedup-invariant; count/sum/avg
                # dedup via a per-(keys, value) first-occurrence mask.
                # first/last DISTINCT are order-sensitive: host runner.
                if fn in ("first", "last"):
                    return None
                if fn not in ("min", "max"):
                    if (
                        not isinstance(arg, _NamedColumnExpr)
                        or arg.wildcard
                        or arg.as_type is not None
                    ):
                        return None
                    acol = blocks.columns.get(arg.name)
                    if acol is None or not acol.on_device:
                        return None
                    if fn != "count" and acol.is_string:
                        return None
                    distinct_args[c.output_name] = arg.name
            if isinstance(arg, _NamedColumnExpr) and arg.wildcard:
                plans.append((c.output_name, "count", None, c))
                continue
            if not expr_eval.can_eval_on_device(
                arg, blocks
            ) or (
                expr_eval.is_string_result(arg, blocks) and fn != "count"
            ):
                return None
            plans.append((c.output_name, fn, arg, c))
        # known-empty inputs stay on the device path too: padded_len(0)=ndev
        # keeps arrays non-empty, all rows invalid, so keyed aggregates give
        # 0 groups and global ones count=0/NULL — the SAME conventions a
        # lazily-empty masked frame gets (advisor r2, low: the two paths
        # must not diverge based on whether the count happens to be known)
        pad_n = blocks.padded_nrows
        dicts = expr_eval.dicts_of(blocks)
        # resolve output types up front (needed inside the traced program)
        typed_plans = []
        for name, func, arg, expr in plans:
            tp = expr.infer_type(jdf.schema)
            if tp is None:
                return None
            typed_plans.append((name, func, arg, tp))
        mesh = blocks.mesh
        ndev = int(mesh.devices.size)
        platform = mesh.devices.flat[0].platform
        if len(keys) == 0:
            return self._global_aggregate(
                jdf, typed_plans, col_order, distinct_args
            )
        bspec = groupby.bin_spec(blocks, keys)
        if bspec is not None:
            kinds = []
            need_int, float_dtype = False, np.float32
            for _, func, arg, _ in typed_plans:
                kind = self._packed_agg_kind(jdf, func, arg)
                kinds.append(kind)
                if kind == "int":
                    need_int = True
                elif kind == "float":
                    atp = arg.infer_type(jdf.schema)
                    if atp is None or not pa.types.is_float32(atp):
                        float_dtype = np.float64
            if all(k is not None for k in kinds):
                strategy = groupby.choose_strategy(
                    platform, bspec.total, float_dtype, need_int
                )
                if strategy is not None:
                    return self._binned_packed_aggregate(
                        jdf, keys, typed_plans, bspec, col_order,
                        strategy, distinct_args,
                    )
        fr = groupby.factorize_keys(blocks, keys)
        num_segments = fr.num_segments
        out_pad = padded_len(num_segments, ndev)
        # the generic (unpacked) path still routes its sum-type reductions
        # through the strategy layer per tier — min/max/median etc. stay
        # scatter-native inside _segment_agg_impl
        seg_strategy = groupby.choose_strategy(
            platform, num_segments, count_only=True
        )
        # devices-aware column of the strategy decision: on multi-device
        # meshes, repartition rows by key (all-to-all) so each device
        # reduces only its own segments instead of every device reducing
        # the full segment space redundantly
        use_shuffle = shuffle.choose_shuffle(
            self._shuffle_mode(), blocks.mesh, pad_n, num_segments
        )
        # combinable plan sets ride the map-side combine (partial
        # aggregation + reduce-scatter-layout all-to-all): O(S * ndev)
        # traffic. Only non-combinable aggregates (median, variance)
        # need the O(rows * ndev) row shuffle
        use_preagg = use_shuffle and shuffle.preagg_ok(
            [f for _, f, _, _ in typed_plans]
        )
        use_overlap = (
            use_shuffle
            and not use_preagg
            and shuffle.choose_overlap(
                self._shuffle_overlap_mode(), blocks.mesh, num_segments
            )
        )
        mesh = blocks.mesh

        # ONE fused program: every agg + key gather + padding, single dispatch
        def _agg_program(
            mcols: Dict[str, Any],
            key_data: Dict[str, Any],
            key_masks: Dict[str, Any],
            seg_: Any,
            first_idx_: Any,
            occupied_: Optional[Any],
            dsegs_: Dict[str, Any],
            dfirsts_: Dict[str, Any],
            row_valid: Optional[Any],
            nrows_s: Any,
        ) -> Dict[str, Any]:
            valid_ = groupby.materialize_validity(row_valid, pad_n, nrows_s)
            outs: Dict[str, Any] = {}
            for k in keys:
                kd = key_data[k][first_idx_]
                km = key_masks.get(k)
                outs[f"k:{k}"] = _pad_to(kd, out_pad)
                if km is not None:
                    outs[f"km:{k}"] = _pad_to(km[first_idx_], out_pad)
            plan_inputs = []
            for name, func, arg, tp in typed_plans:
                if func == "count" and arg is None:
                    values: Any = jnp.ones((pad_n,), dtype=jnp.int32)
                    mask: Any = None
                else:
                    values, mask = expr_eval.eval_expr(
                        mcols, arg, pad_n, dicts
                    )
                mask = _apply_distinct_mask(
                    dsegs_, dfirsts_, name, pad_n, mask
                )
                plan_inputs.append((name, func, tp, values, mask))
            if use_preagg:
                # map-side combine: per-device partials, one tiny
                # all-to-all of (ndev, S_local) partial tables
                pairs = shuffle.preagg_segment_aggs(
                    mesh,
                    [f for _, f, _, _, _ in plan_inputs],
                    seg_,
                    valid_,
                    [
                        None if f == "count" else v
                        for _, f, _, v, _ in plan_inputs
                    ],
                    [m for _, _, _, _, m in plan_inputs],
                    num_segments,
                    strategy=seg_strategy,
                )
            elif use_shuffle:
                # ONE all-to-all co-locates every plan's rows by key;
                # count transports only its mask (values are unused by
                # the count kernel — but the mask MUST travel, it folds
                # into the effective row count)
                pairs = shuffle.shuffled_segment_aggs(
                    mesh,
                    [f for _, f, _, _, _ in plan_inputs],
                    seg_,
                    valid_,
                    [
                        None if f == "count" else v
                        for _, f, _, v, _ in plan_inputs
                    ],
                    [m for _, _, _, _, m in plan_inputs],
                    num_segments,
                    strategy=seg_strategy,
                    overlap=use_overlap,
                )
            else:
                pairs = [
                    groupby._segment_agg_impl(
                        f, v, m, seg_, num_segments, valid_,
                        strategy=seg_strategy,
                    )
                    for _, f, _, v, m in plan_inputs
                ]
            for (name, func, tp, _, _), (v, m) in zip(plan_inputs, pairs):
                outs[f"a:{name}"] = _pad_to(_cast_agg_result(v, tp), out_pad)
                if m is not None:
                    outs[f"am:{name}"] = _pad_to(m, out_pad)
            if occupied_ is not None:
                outs["_occupied"] = _pad_to(occupied_, out_pad)
            return outs

        dsegs, dfirsts = _distinct_factorize(blocks, keys, distinct_args)
        prog_key = (
            "agg",
            tuple((n, f, None if a is None else a.__uuid__(), str(t))
                  for n, f, a, t in typed_plans),
            tuple(keys), num_segments, out_pad, pad_n, seg_strategy,
            ("shuf", use_shuffle, use_preagg, use_overlap, ndev),
            tuple(sorted(distinct_args.items())),
            expr_eval.dict_fingerprint(blocks),
        )
        self._count_strategy("generic")
        if use_shuffle:
            # per-strategy shuffle visibility: which exchange plan ran
            # (map-side combine vs row shuffle) and which reduction
            # kernel the local pass used
            self._count_strategy(
                "shuffle_preagg" if use_preagg
                else f"shuffle_{seg_strategy}"
            )
        key_data = {k: blocks.columns[k].data for k in keys}
        key_masks = {
            k: blocks.columns[k].mask
            for k in keys
            if blocks.columns[k].mask is not None
        }
        t0 = time.perf_counter() if use_shuffle else 0.0
        outs = self._jit_cached(prog_key, _agg_program)(
            expr_eval.blocks_to_masked(blocks),
            key_data,
            key_masks,
            fr.seg,
            fr.first_idx,
            fr.occupied,
            dsegs,
            dfirsts,
            blocks.row_valid,
            _nrows_arg(blocks),
        )
        if use_shuffle:
            if use_preagg:
                # per-segment partial widths: count ships an i32 count,
                # everything else an 8B value + a marker/count column
                widths = sum(
                    4 if f == "count" else 9 for _, f, _, _ in typed_plans
                )
                nbytes = shuffle.estimate_preagg_bytes(
                    num_segments, ndev, widths
                )
            else:
                widths = sum(
                    (0 if f == "count" else 8) + 1
                    for _, f, _, _ in typed_plans
                )
                nbytes = shuffle.estimate_shuffle_bytes(
                    pad_n, ndev, widths
                )
            self._count_shuffle(
                "aggregate", nbytes, time.perf_counter() - t0, use_overlap
            )
        outs = place_rows(outs, mesh, self._host_reshards)
        out_cols: Dict[str, JaxColumn] = {}
        schema_fields = [jdf.schema[k] for k in keys]
        for k in keys:
            src_col = blocks.columns[k]
            out_cols[k] = JaxColumn(
                src_col.pa_type,
                outs[f"k:{k}"],
                outs.get(f"km:{k}"),
                src_col.host_dict,
                src_col.stats,
            )
        for name, func, arg, tp in typed_plans:
            out_cols[name] = JaxColumn(
                tp,
                outs[f"a:{name}"],
                outs.get(f"am:{name}"),
            )
            schema_fields.append(pa.field(name, tp))
        schema = Schema(schema_fields)
        if col_order is not None:
            schema = schema.extract(col_order)
            out_cols = {n: out_cols[n] for n in col_order}
        if "_occupied" in outs:
            # binned path: empty bins masked out lazily; count stays a
            # device scalar until the host asks
            return JaxDataFrame(
                JaxBlocks(
                    None,
                    out_cols,
                    blocks.mesh,
                    row_valid=outs["_occupied"],
                    nrows_dev=fr.num_groups_dev,
                ),
                schema,
            )
        return JaxDataFrame(
            JaxBlocks(num_segments, out_cols, blocks.mesh), schema
        )

    def _try_stream_aggregate(
        self, df: DataFrame, keys: List[str], agg_cols: List[ColumnExpr]
    ) -> Optional[DataFrame]:
        """Streaming aggregation for iterable-of-frames inputs (keys must
        be integer-like, aggs in the streaming whitelist); None when the
        input is an ordinary bounded frame."""
        from fugue_tpu.dataframe.dataframe_iterable_dataframe import (
            LocalDataFrameIterableDataFrame,
        )

        if not isinstance(df, LocalDataFrameIterableDataFrame):
            return None
        if len(keys) == 0:
            return None
        schema = df.schema
        for k in keys:
            if k not in schema or not (
                pa.types.is_integer(schema[k].type)
                or pa.types.is_boolean(schema[k].type)
            ):
                return None
        from fugue_tpu.column.expressions import _FuncExpr
        from fugue_tpu.jax_backend import streaming

        plans: List[Tuple[str, str, Optional[str]]] = []
        for c in agg_cols:
            if (
                not isinstance(c, _FuncExpr)
                or len(c.args) != 1
                or c.arg_distinct
                or c.func.lower() not in streaming._SUPPORTED
            ):
                return None
            arg = c.args[0]
            if isinstance(arg, _NamedColumnExpr) and arg.wildcard:
                src = keys[0]  # count(*): count key occurrences
            elif isinstance(arg, _NamedColumnExpr) and arg.as_type is None:
                src = arg.name
            else:
                return None
            plans.append((c.output_name, c.func.lower(), src))

        def _chunks() -> Any:
            for local in df.native:
                yield local.as_pandas()

        try:
            return streaming.stream_aggregate(
                self, _chunks(), schema, list(keys), plans
            )
        except streaming.StreamFallback as fb:
            # bounded-path semantics can't stream (NULL keys, unbounded key
            # space, empty stream): materialize and go through the normal
            # path so results never depend on the container type
            self._count_fallback("aggregate", f"stream fallback: {fb}")
            from fugue_tpu.dataframe import PandasDataFrame

            pdf = streaming.materialize_fallback(fb, schema)
            bounded = PandasDataFrame(pdf, schema)
            jdf = self.to_df(bounded)
            res = self._try_device_aggregate(jdf, list(keys), agg_cols)
            if res is not None:
                return res
            return self.to_df(
                self._native.aggregate(
                    bounded, PartitionSpec(by=list(keys)), agg_cols
                )
            )

    def _shuffle_mode(self) -> str:
        """``fugue.jax.shuffle`` normalized to auto/on/off — whether
        segment reductions repartition rows by key over the mesh first."""
        from fugue_tpu.constants import FUGUE_CONF_JAX_SHUFFLE

        return shuffle.shuffle_mode(
            self.conf.get(FUGUE_CONF_JAX_SHUFFLE, "auto"),
            FUGUE_CONF_JAX_SHUFFLE,
        )

    def _shuffle_overlap_mode(self) -> str:
        """``fugue.jax.shuffle.overlap`` normalized to auto/on/off —
        whether shuffled reductions double-buffer the next key-range's
        all-to-all behind the current range's local reduction."""
        from fugue_tpu.constants import FUGUE_CONF_JAX_SHUFFLE_OVERLAP

        return shuffle.shuffle_mode(
            self.conf.get(FUGUE_CONF_JAX_SHUFFLE_OVERLAP, "auto"),
            FUGUE_CONF_JAX_SHUFFLE_OVERLAP,
        )

    def _join_shuffle(self, mesh: Any, rows: int, num_segments: int) -> bool:
        """Shuffle decision for relational.py's join count reductions —
        same strategy column as aggregates, exposed so expand_join does
        not reach into conf itself."""
        return shuffle.choose_shuffle(
            self._shuffle_mode(), mesh, rows, num_segments
        )

    def _packed_agg_kind(
        self, jdf: JaxDataFrame, func: str, arg: Any
    ) -> Optional[str]:
        """How an aggregation rides the packed strategy kernels: "count",
        "float" (f32/f64 sum/avg payload), "int" (exact integer sum/avg
        payload — scatter/sort strategies only), or None (not packable:
        min/max/median and friends stay on the generic path)."""
        if func == "count":
            return "count"
        if func not in ("sum", "avg", "mean"):
            return None
        tp = arg.infer_type(jdf.schema) if arg is not None else None
        if tp is None and isinstance(arg, _NamedColumnExpr):
            col = jdf.schema[arg.name] if arg.name in jdf.schema else None
            tp = col.type if col is not None else None
        if tp is None:
            return None
        if pa.types.is_floating(tp):
            return "float"
        if pa.types.is_integer(tp):
            return "int"
        return None

    def _global_aggregate(
        self,
        jdf: JaxDataFrame,
        typed_plans: List[Tuple[str, str, Any, pa.DataType]],
        col_order: Optional[List[str]],
        distinct_args: Optional[Dict[str, str]] = None,
    ) -> DataFrame:
        """Keyless aggregation: plain masked jnp reductions — one program,
        no segments, no scatter. DISTINCT aggregates contribute only the
        first row of each value (a per-value factorize mask). The one
        result row is padded to the mesh inside the program."""
        blocks = jdf.blocks
        mesh = blocks.mesh
        pad_n = blocks.padded_nrows
        out_pad = padded_len(1, int(mesh.devices.size))
        dicts = expr_eval.dicts_of(blocks)
        dsegs, dfirsts = _distinct_factorize(blocks, [], distinct_args)

        def _prog(
            mcols: Dict[str, Any],
            dsegs_: Dict[str, Any],
            dfirsts_: Dict[str, Any],
            row_valid: Optional[Any],
            nrows_s: Any,
        ) -> Dict[str, Any]:
            valid = groupby.materialize_validity(row_valid, pad_n, nrows_s)
            outs: Dict[str, Any] = {}
            for name, func, arg, tp in typed_plans:
                if func == "count" and arg is None:
                    values: Any = jnp.ones((pad_n,), dtype=jnp.int32)
                    mask: Any = None
                else:
                    values, mask = expr_eval.eval_expr(
                        mcols, arg, pad_n, dicts
                    )
                mask = _apply_distinct_mask(
                    dsegs_, dfirsts_, name, pad_n, mask
                )
                eff = valid if mask is None else (mask & valid)
                cnt = jnp.sum(eff.astype(jnp.int32))
                if func == "count":
                    v: Any = cnt
                    m: Any = None
                elif func in ("sum", "avg", "mean"):
                    tot = jnp.sum(jnp.where(eff, values, 0))
                    v = (
                        tot
                        if func == "sum"
                        else tot / jnp.maximum(cnt, 1)
                    )
                    m = cnt > 0
                elif func == "median":
                    eff2 = eff
                    if jnp.issubdtype(values.dtype, jnp.floating):
                        eff2 = eff2 & ~jnp.isnan(values)
                    c2 = jnp.sum(eff2.astype(jnp.int32))
                    fv2 = values.astype(jnp.float64)
                    sv = jnp.sort(jnp.where(eff2, fv2, jnp.inf))
                    npad = sv.shape[0]
                    lo = jnp.clip((c2 - 1) // 2, 0, npad - 1)
                    hi = jnp.clip(c2 // 2, 0, npad - 1)
                    v = (sv[lo] + sv[hi]) * 0.5
                    m = c2 > 0
                elif func in VARIANCE_FUNCS:
                    eff2 = eff
                    if jnp.issubdtype(values.dtype, jnp.floating):
                        eff2 = eff2 & ~jnp.isnan(values)  # pandas skips NaN
                    c2 = jnp.sum(eff2.astype(jnp.int32))
                    fv = jnp.where(eff2, values.astype(jnp.float64), 0.0)
                    cf = c2.astype(jnp.float64)
                    mean = jnp.sum(fv) / jnp.maximum(cf, 1.0)
                    dev = jnp.where(
                        eff2, values.astype(jnp.float64) - mean, 0.0
                    )
                    ss = jnp.sum(dev * dev)
                    pop = func in ("stddev_pop", "var_pop")
                    var = ss / jnp.maximum(cf if pop else cf - 1.0, 1.0)
                    v = jnp.sqrt(var) if func.startswith("stddev") else var
                    m = c2 > (0 if pop else 1)
                elif func == "min":
                    v = jnp.min(
                        jnp.where(eff, values, groupby._type_max(values.dtype))
                    )
                    m = cnt > 0
                elif func == "max":
                    v = jnp.max(
                        jnp.where(eff, values, groupby._type_min(values.dtype))
                    )
                    m = cnt > 0
                else:  # first/last
                    idx = jnp.arange(pad_n, dtype=jnp.int32)
                    pick = (
                        jnp.argmin(jnp.where(valid, idx, pad_n))
                        if func == "first"
                        else jnp.argmax(jnp.where(valid, idx, -1))
                    )
                    v = values[pick]
                    # no valid row at all (e.g. filter removed everything
                    # from a lazy-count frame) -> NULL, not row-0 garbage
                    any_valid = jnp.any(valid)
                    m = (
                        any_valid
                        if mask is None
                        else (mask[pick] & any_valid)
                    )
                outs[f"a:{name}"] = _pad_to(
                    _cast_agg_result(jnp.asarray(v)[None], tp), out_pad
                )
                if m is not None:
                    outs[f"am:{name}"] = _pad_to(
                        jnp.asarray(m)[None], out_pad
                    )
            return outs

        prog_key = (
            "gagg",
            tuple(
                (n, f, None if a is None else a.__uuid__(), str(t))
                for n, f, a, t in typed_plans
            ),
            pad_n,
            tuple(sorted((distinct_args or {}).items())),
            expr_eval.dict_fingerprint(blocks),
            out_pad,
        )
        outs = self._jit_cached(prog_key, _prog)(
            expr_eval.blocks_to_masked(blocks),
            dsegs,
            dfirsts,
            blocks.row_valid,
            _nrows_arg(blocks),
        )
        outs = place_rows(outs, mesh, self._host_reshards)
        out_cols: Dict[str, JaxColumn] = {}
        schema_fields = []
        for name, func, arg, tp in typed_plans:
            out_cols[name] = JaxColumn(
                tp,
                outs[f"a:{name}"],
                outs.get(f"am:{name}"),
            )
            schema_fields.append(pa.field(name, tp))
        schema = Schema(schema_fields)
        if col_order is not None:
            schema = schema.extract(col_order)
            out_cols = {n: out_cols[n] for n in col_order}
        return JaxDataFrame(
            JaxBlocks(1, out_cols, blocks.mesh), schema
        )

    def _binned_packed_aggregate(
        self,
        jdf: JaxDataFrame,
        keys: List[str],
        typed_plans: List[Tuple[str, str, Any, pa.DataType]],
        bspec: "groupby.BinSpec",
        col_order: Optional[List[str]],
        strategy: str,
        distinct_args: Optional[Dict[str, str]] = None,
    ) -> DataFrame:
        """The group-by hot path: ONE jitted program computing mixed-radix
        segment ids inline, ALL sum/avg/count reductions (float, exact-int
        and DISTINCT variants) packed into a single strategy kernel —
        one-hot matmul / masked sums / packed scatter / sorted scatter,
        per ``groupby.choose_strategy`` — and key values decoded
        arithmetically from bin indices (gather-free). Zero host syncs on the matmul and
        scatter strategies; the group count stays a lazy device scalar.
        DISTINCT aggregates fold their first-occurrence-of-(keys, value)
        masks into the payloads, so they ride the same packed kernel.
        On a multi-device mesh the cross-device sum leaves the bins
        replicated; ``place_rows`` slices them on the devices."""
        blocks = jdf.blocks
        mesh = blocks.mesh
        pad_n = blocks.padded_nrows
        dicts = expr_eval.dicts_of(blocks)
        ndev = int(mesh.devices.size)
        total = bspec.total
        out_pad = padded_len(total, ndev)
        key_dtypes = {k: blocks.columns[k].data.dtype for k in keys}
        distinct_args = distinct_args or {}
        plan_kinds = [
            "c" if (func == "count") else (
                "i"
                if self._packed_agg_kind(jdf, func, arg) == "int"
                else "f"
            )
            for _, func, arg, _ in typed_plans
        ]

        def _prog(
            mcols: Dict[str, Any],
            key_data: Dict[str, Any],
            key_masks: Dict[str, Any],
            dsegs_: Dict[str, Any],
            dfirsts_: Dict[str, Any],
            row_valid: Optional[Any],
            nrows_s: Any,
        ) -> Dict[str, Any]:
            valid = groupby.materialize_validity(row_valid, pad_n, nrows_s)
            seg = groupby.inline_seg(
                bspec, key_data, key_masks, valid
            )
            float_payloads: List[Any] = []
            count_payloads: List[Any] = [valid]  # occupancy rides along
            int_payloads: List[Any] = []
            # payload DEDUP: kernel work scales with the payload count, and
            # real queries repeat payloads constantly — SUM(v)+AVG(v) share
            # one float payload; COUNT(*) / any unmasked count IS the
            # occupancy vector (slot 0). A sum+avg+count query drops from
            # 6 payload rows to 2 — a ~3x work cut on the hot path.
            # DISTINCT variants key separately (their effective mask also
            # carries the first-occurrence dedup mask).
            fkeys: Dict[str, int] = {}
            ckeys: Dict[str, int] = {"__valid__": 0}
            ikeys: Dict[str, int] = {}
            slots: List[Tuple[str, Any]] = []  # (kind, index-key) per plan

            def _count_slot(key: str, vec: Any) -> int:
                if key not in ckeys:
                    count_payloads.append(vec)
                    ckeys[key] = len(count_payloads) - 1
                return ckeys[key]

            def _float_slot(key: str, vec: Any) -> int:
                if key not in fkeys:
                    float_payloads.append(vec)
                    fkeys[key] = len(float_payloads) - 1
                return fkeys[key]

            def _int_slot(key: str, vec: Any) -> int:
                if key not in ikeys:
                    int_payloads.append(vec)
                    ikeys[key] = len(int_payloads) - 1
                return ikeys[key]

            for (name, func, arg, tp), kind in zip(typed_plans, plan_kinds):
                if func == "count" and arg is None:
                    slots.append(("c", 0))  # COUNT(*) == occupancy
                    continue
                akey = arg.__uuid__()
                dname = distinct_args.get(name)
                values, mask = expr_eval.eval_expr(mcols, arg, pad_n, dicts)
                mask = _apply_distinct_mask(
                    dsegs_, dfirsts_, name, pad_n, mask
                )
                parts = ([f"m:{akey}"] if mask is not None else [])
                if dname is not None:
                    parts.append(f"d:{dname}")
                eff_key = "|".join(parts) or "__valid__"
                eff = valid if mask is None else (mask & valid)
                if func == "count":
                    slots.append(("c", _count_slot(eff_key, eff)))
                    continue
                ci = _count_slot(eff_key, eff)
                pkey = f"{akey}|{eff_key}"
                if kind == "i":
                    ii = _int_slot(pkey, jnp.where(eff, values, 0))
                    slots.append(("i", (ii, ci)))
                else:
                    fi = _float_slot(pkey, jnp.where(eff, values, 0))
                    slots.append(("f", (fi, ci)))
            f_sums, c_sums, i_sums = groupby.segment_sums(
                float_payloads, count_payloads, seg, total,
                strategy=strategy, int_payloads=int_payloads,
            )
            occupied = c_sums[0] > 0
            outs: Dict[str, Any] = {
                "_occupied": _pad_to(occupied, out_pad),
                "_num": jnp.sum(occupied.astype(jnp.int32)),
            }
            decoded = groupby.decode_bin_keys(bspec, key_dtypes)
            for k in keys:
                kv, km = decoded[k]
                outs[f"k:{k}"] = _pad_to(kv, out_pad)
                if km is not None:
                    outs[f"km:{k}"] = _pad_to(km, out_pad)
            for (name, func, arg, tp), slot in zip(typed_plans, slots):
                kind, idx = slot
                if kind == "c":
                    outs[f"a:{name}"] = _pad_to(
                        _cast_agg_result(c_sums[idx], tp), out_pad
                    )
                    continue
                si, ci = idx
                tot = i_sums[si] if kind == "i" else f_sums[si]
                cnt = c_sums[ci]
                if func == "sum":
                    v = tot
                else:  # avg/mean
                    v = tot / jnp.maximum(cnt, 1)
                outs[f"a:{name}"] = _pad_to(_cast_agg_result(v, tp), out_pad)
                outs[f"am:{name}"] = _pad_to(cnt > 0, out_pad)
            return outs

        prog_key = (
            "bagg",
            tuple(
                (n, f, None if a is None else a.__uuid__(), str(t))
                for n, f, a, t in typed_plans
            ),
            bspec,
            pad_n,
            strategy,
            tuple(sorted(distinct_args.items())),
            expr_eval.dict_fingerprint(blocks),
        )
        self._count_strategy(strategy)
        dsegs, dfirsts = _distinct_factorize(blocks, keys, distinct_args)
        key_data = {k: blocks.columns[k].data for k in keys}
        key_masks = {
            k: blocks.columns[k].mask
            for k in keys
            if blocks.columns[k].mask is not None
        }
        outs = self._jit_cached(prog_key, _prog)(
            expr_eval.blocks_to_masked(blocks),
            key_data,
            key_masks,
            dsegs,
            dfirsts,
            blocks.row_valid,
            _nrows_arg(blocks),
        )
        outs = place_rows(outs, mesh, self._host_reshards)
        out_cols: Dict[str, JaxColumn] = {}
        schema_fields = [jdf.schema[k] for k in keys]
        for k in keys:
            src_col = blocks.columns[k]
            out_cols[k] = JaxColumn(
                src_col.pa_type,
                outs[f"k:{k}"],
                outs.get(f"km:{k}"),
                src_col.host_dict,
                src_col.stats,
            )
        for name, func, arg, tp in typed_plans:
            out_cols[name] = JaxColumn(
                tp,
                outs[f"a:{name}"],
                outs.get(f"am:{name}"),
            )
            schema_fields.append(pa.field(name, tp))
        schema = Schema(schema_fields)
        if col_order is not None:
            schema = schema.extract(col_order)
            out_cols = {n: out_cols[n] for n in col_order}
        return JaxDataFrame(
            JaxBlocks(
                None,
                out_cols,
                mesh,
                row_valid=outs["_occupied"],
                nrows_dev=outs["_num"],
            ),
            schema,
        )


def _devices_from_conf(conf: Any) -> Optional[List[Any]]:
    """Parse ``fugue.jax.devices`` — a comma-separated list of indices
    into ``jax.devices()`` — into the device slice the engine's mesh
    should cover. Empty/unset means all devices. Out-of-range or
    non-integer indices raise: a replica silently grabbing the whole pod
    because of a typo'd slice would defeat the isolation the knob
    exists for."""
    from fugue_tpu.constants import FUGUE_CONF_JAX_DEVICES

    raw = str(conf.get(FUGUE_CONF_JAX_DEVICES, "") or "").strip()
    if raw == "":
        return None
    devs = jax.devices()
    out: List[Any] = []
    for part in raw.split(","):
        part = part.strip()
        if part == "":
            continue
        try:
            idx = int(part)
        except ValueError:
            raise ValueError(
                f"{FUGUE_CONF_JAX_DEVICES}={raw!r}: {part!r} is not an "
                "integer device index"
            )
        if not (0 <= idx < len(devs)):
            raise ValueError(
                f"{FUGUE_CONF_JAX_DEVICES}={raw!r}: index {idx} is out of "
                f"range for {len(devs)} visible devices"
            )
        out.append(devs[idx])
    if len(out) == 0:
        return None
    return out


def _host_mesh_like(mesh: Any) -> Any:
    """A mesh over the CPU backend for the host placement tier. When the
    default platform already is CPU (tests, CPU-only boxes) the accelerator
    mesh IS the host mesh — return the same object so placement becomes a
    no-op and mesh identity checks stay cheap."""
    try:
        cpu_devs = jax.devices("cpu")
    except RuntimeError:  # pragma: no cover - no CPU backend registered
        return mesh
    if list(mesh.devices.flat) == list(cpu_devs[: mesh.devices.size]) and (
        mesh.devices.size == len(cpu_devs)
    ):
        return mesh
    return make_mesh(list(cpu_devs))


def blocks_with_columns(
    blocks: JaxBlocks, new_cols: Dict[str, JaxColumn]
) -> JaxBlocks:
    """New column set, same row membership (lazy state passes through)."""
    return JaxBlocks(
        blocks._nrows,
        new_cols,
        blocks.mesh,
        row_valid=blocks.row_valid,
        nrows_dev=blocks._nrows_dev,
    )


# the aggregate families the device paths accept (one definition so the
# can-select gate and the plan builder cannot drift apart)
_DEVICE_AGGS = (
    "min", "max", "sum", "avg", "mean", "count", "first", "last",
    "median", *VARIANCE_FUNCS,
)
_DEVICE_DISTINCT_AGGS = (
    "min", "max", "sum", "avg", "mean", "count", "median",
    *VARIANCE_FUNCS,
)


def _distinct_factorize(
    blocks: JaxBlocks, keys: List[str], distinct_args: Optional[Dict[str, str]]
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Per-(keys, value) factorizations backing DISTINCT aggregates —
    shared by the keyed and global aggregate paths."""
    dsegs: Dict[str, Any] = {}
    dfirsts: Dict[str, Any] = {}
    for name, argname in (distinct_args or {}).items():
        fr2 = groupby.factorize_keys(blocks, keys + [argname])
        dsegs[name] = fr2.seg
        dfirsts[name] = fr2.first_idx
    return dsegs, dfirsts


def _apply_distinct_mask(
    dsegs: Dict[str, Any],
    dfirsts: Dict[str, Any],
    name: str,
    pad_n: int,
    mask: Optional[Any],
) -> Optional[Any]:
    """Fold the first-occurrence-of-(keys, value) mask into an agg's
    validity mask (inside a traced program)."""
    if name not in dsegs:
        return mask
    pos_ = jnp.arange(pad_n, dtype=jnp.int32)
    dmask = dfirsts[name][dsegs[name]] == pos_
    return dmask if mask is None else (mask & dmask)


def _nrows_arg(blocks: JaxBlocks) -> Any:
    """Row count as a program argument with no host sync: a known int (jax
    converts per call, no retrace) or the pending device scalar."""
    if blocks._nrows is not None:
        return np.int32(blocks._nrows)
    if blocks._nrows_dev is not None:
        return blocks._nrows_dev
    return np.int32(-1)  # row_valid is set; programs use the mask directly


class _StringDictUnavailable(Exception):
    """A compiled map produced string-typed output codes with no decode
    table (neither passthrough-inherited nor fn-returned) — the caller
    falls back to the host map path."""


def _is_dict_key(k: str) -> bool:
    return k.startswith("_") and k.endswith("_dict")


def _path_leaf_key(path: Any) -> Optional[str]:
    """Dict key of a pytree leaf path like (DictKey('k'),) -> 'k'."""
    if len(path) == 0:
        return None
    last = path[-1]
    key = getattr(last, "key", None)
    return key if isinstance(key, str) else None


def _as_aval(x: Any) -> Any:
    """Shape/dtype signature of a program argument (for AOT re-lowering in
    program_cost_analysis; keeps no reference to the data)."""
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


def _pad_to(v: jnp.ndarray, target: int) -> jnp.ndarray:
    n = int(v.shape[0])
    if n == target:
        return v
    return jnp.concatenate([v, jnp.zeros((target - n,), dtype=v.dtype)])


def _cast_agg_result(v: jnp.ndarray, tp: pa.DataType) -> jnp.ndarray:
    target = tp.to_pandas_dtype()
    try:
        return v.astype(target)
    except Exception:  # pragma: no cover
        return v
