"""FugueWorkflow: the lazy DAG programming interface (reference
fugue/workflow/workflow.py:88-2302 re-built on our own runner/tasks).

``FugueWorkflow()`` collects operations as deterministic tasks;
``run(engine)`` executes them (nothing is compiled before that)."""

from typing import Any, Callable, Dict, Iterable, List, Optional, Union
from uuid import uuid4

from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.collections.sql import StructuredRawSQL
from fugue_tpu.collections.yielded import PhysicalYielded, Yielded
from fugue_tpu.column.expressions import ColumnExpr
from fugue_tpu.column.sql import SelectColumns
from fugue_tpu.constants import (
    FUGUE_CONF_ANALYSIS,
    FUGUE_CONF_WORKFLOW_CONCURRENCY,
    FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE,
    FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT,
    FUGUE_GLOBAL_CONF,
)
from fugue_tpu.dataframe import DataFrame
from fugue_tpu.dataframe.dataframe import YieldedDataFrame
from fugue_tpu.execution.factory import make_execution_engine
from fugue_tpu.extensions.builtins import (
    Aggregate,
    AlterColumns,
    Assign,
    AssertEqFunc,
    AssertNotEqFunc,
    CreateData,
    Distinct,
    DropColumns,
    Dropna,
    Fillna,
    Filter,
    Load,
    Rename,
    RunJoin,
    RunOutputTransformer,
    RunSetOperation,
    RunSQLSelect,
    RunTransformer,
    Sample,
    Save,
    SaveAndUse,
    Select,
    SelectColumnsP,
    Show,
    Take,
    Zip,
)
from fugue_tpu.obs import (
    activate,
    current_span,
    finalize_trace,
    obs_options,
    open_trace,
    start_span,
    tracing_suppressed,
)
from fugue_tpu.obs.profile import (
    Profiler,
    profiling_forced,
    profiling_requested,
    task_scope,
)
from fugue_tpu.obs.trace import NULL_CM
from fugue_tpu.rpc import make_rpc_server, to_rpc_handler
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw
from fugue_tpu.utils.exception import (
    add_error_note,
    extract_user_callsite,
    prune_traceback,
)
from fugue_tpu.utils.hash import to_uuid
from fugue_tpu.utils.params import ParamDict
from fugue_tpu.workflow.checkpoint import (
    Checkpoint,
    CheckpointPath,
    StrongCheckpoint,
    TableCheckpoint,
    WeakCheckpoint,
)
from fugue_tpu.workflow.fault import (
    CancelToken,
    RetryPolicy,
    RunStats,
    execute_with_policy,
)
from fugue_tpu.workflow.manifest import RunManifest
from fugue_tpu.workflow.runner import DAGRunner, TaskNode
from fugue_tpu.workflow.tasks import (
    CreateTask,
    FugueTask,
    OutputTask,
    ProcessTask,
    TaskContext,
)


class WorkflowDataFrame:
    """Lazy handle to a dataframe inside a workflow DAG (reference
    workflow.py:88). All methods add tasks; nothing executes until
    ``workflow.run``."""

    def __init__(self, workflow: "FugueWorkflow", task: FugueTask):
        self._workflow = workflow
        self._task = task
        self._pending_partition: Optional[PartitionSpec] = None

    @property
    def workflow(self) -> "FugueWorkflow":
        return self._workflow

    @property
    def task(self) -> FugueTask:
        return self._task

    @property
    def partition_spec(self) -> PartitionSpec:
        return self._pending_partition or PartitionSpec()

    def __uuid__(self) -> str:
        return self._task.__uuid__()

    # ---- partition hints -------------------------------------------------
    def partition(self, *args: Any, **kwargs: Any) -> "WorkflowDataFrame":
        res = WorkflowDataFrame(self._workflow, self._task)
        res._pending_partition = PartitionSpec(*args, **kwargs)
        return res

    def partition_by(self, *keys: str, **kwargs: Any) -> "WorkflowDataFrame":
        return self.partition(by=list(keys), **kwargs)

    def per_partition_by(self, *keys: str) -> "WorkflowDataFrame":
        return self.partition(by=list(keys), algo="coarse")

    def per_row(self) -> "WorkflowDataFrame":
        return self.partition("per_row")

    # ---- transform -------------------------------------------------------
    def transform(
        self,
        using: Any,
        schema: Any = None,
        params: Any = None,
        pre_partition: Any = None,
        ignore_errors: Optional[List[type]] = None,
        callback: Any = None,
    ) -> "WorkflowDataFrame":
        if pre_partition is None and self._pending_partition is not None:
            pre_partition = self._pending_partition
        task = ProcessTask(
            RunTransformer,
            params=dict(
                transformer=using,
                schema=schema,
                params=ParamDict(params),
                ignore_errors=ignore_errors or [],
                rpc_handler=None if callback is None else to_rpc_handler(callback),
            ),
            partition_spec=PartitionSpec(pre_partition),
            input_tasks=[self._task],
        )
        return self._workflow.add(task)

    def out_transform(
        self,
        using: Any,
        params: Any = None,
        pre_partition: Any = None,
        ignore_errors: Optional[List[type]] = None,
        callback: Any = None,
    ) -> None:
        if pre_partition is None and self._pending_partition is not None:
            pre_partition = self._pending_partition
        task = OutputTask(
            RunOutputTransformer,
            params=dict(
                transformer=using,
                params=ParamDict(params),
                ignore_errors=ignore_errors or [],
                rpc_handler=None if callback is None else to_rpc_handler(callback),
            ),
            partition_spec=PartitionSpec(pre_partition),
            input_tasks=[self._task],
        )
        self._workflow.add(task)

    def process(
        self,
        using: Any,
        schema: Any = None,
        params: Any = None,
        pre_partition: Any = None,
    ) -> "WorkflowDataFrame":
        return self._workflow.process(
            self, using=using, schema=schema, params=params,
            pre_partition=pre_partition or self._pending_partition,
        )

    def output(self, using: Any, params: Any = None, pre_partition: Any = None) -> None:
        self._workflow.output(
            self, using=using, params=params,
            pre_partition=pre_partition or self._pending_partition,
        )

    # ---- relational ------------------------------------------------------
    def join(
        self, *dfs: "WorkflowDataFrame", how: str, on: Optional[List[str]] = None
    ) -> "WorkflowDataFrame":
        return self._workflow.join(self, *dfs, how=how, on=on)

    def inner_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="inner", on=on)

    def semi_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="semi", on=on)

    def anti_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="anti", on=on)

    def left_outer_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="left_outer", on=on)

    def right_outer_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="right_outer", on=on)

    def full_outer_join(self, *dfs: Any, on: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self.join(*dfs, how="full_outer", on=on)

    def cross_join(self, *dfs: Any) -> "WorkflowDataFrame":
        return self.join(*dfs, how="cross")

    def union(self, *dfs: Any, distinct: bool = True) -> "WorkflowDataFrame":
        return self._workflow.set_op("union", self, *dfs, distinct=distinct)

    def subtract(self, *dfs: Any, distinct: bool = True) -> "WorkflowDataFrame":
        return self._workflow.set_op("subtract", self, *dfs, distinct=distinct)

    def intersect(self, *dfs: Any, distinct: bool = True) -> "WorkflowDataFrame":
        return self._workflow.set_op("intersect", self, *dfs, distinct=distinct)

    def distinct(self) -> "WorkflowDataFrame":
        return self._add_process(Distinct)

    def dropna(
        self, how: str = "any", thresh: Optional[int] = None,
        subset: Optional[List[str]] = None,
    ) -> "WorkflowDataFrame":
        params: Dict[str, Any] = dict(how=how, subset=subset)
        if thresh is not None:
            params["thresh"] = thresh
        return self._add_process(Dropna, params=params)

    def fillna(self, value: Any, subset: Optional[List[str]] = None) -> "WorkflowDataFrame":
        return self._add_process(Fillna, params=dict(value=value, subset=subset))

    def sample(
        self, n: Optional[int] = None, frac: Optional[float] = None,
        replace: bool = False, seed: Optional[int] = None,
    ) -> "WorkflowDataFrame":
        params: Dict[str, Any] = dict(replace=replace)
        if n is not None:
            params["n"] = n
        if frac is not None:
            params["frac"] = frac
        if seed is not None:
            params["seed"] = seed
        return self._add_process(Sample, params=params)

    def take(
        self, n: int, presort: str = "", na_position: str = "last"
    ) -> "WorkflowDataFrame":
        return self._add_process(
            Take,
            params=dict(n=n, presort=presort, na_position=na_position),
            partition_spec=self._pending_partition,
        )

    def select(
        self,
        *columns: Union[str, ColumnExpr],
        where: Optional[ColumnExpr] = None,
        having: Optional[ColumnExpr] = None,
        distinct: bool = False,
    ) -> "WorkflowDataFrame":
        from fugue_tpu.column.expressions import col as _col

        cols = SelectColumns(
            *[_col(c) if isinstance(c, str) else c for c in columns],
            arg_distinct=distinct,
        )
        return self._add_process(
            Select, params=dict(columns=cols, where=where, having=having)
        )

    def filter(self, condition: ColumnExpr) -> "WorkflowDataFrame":
        return self._add_process(Filter, params=dict(condition=condition))

    def assign(self, **columns: Any) -> "WorkflowDataFrame":
        from fugue_tpu.column.expressions import lit

        cols = [
            (v if isinstance(v, ColumnExpr) else lit(v)).alias(k)
            for k, v in columns.items()
        ]
        return self._add_process(Assign, params=dict(columns=cols))

    def aggregate(self, **agg_kwcols: ColumnExpr) -> "WorkflowDataFrame":
        cols = [v.alias(k) for k, v in agg_kwcols.items()]
        return self._add_process(
            Aggregate,
            params=dict(columns=cols),
            partition_spec=self._pending_partition,
        )

    # ---- schema ops ------------------------------------------------------
    def rename(self, *args: Dict[str, str], **kwargs: str) -> "WorkflowDataFrame":
        columns: Dict[str, str] = {}
        for a in args:
            columns.update(a)
        columns.update(kwargs)
        return self._add_process(Rename, params=dict(columns=columns))

    def alter_columns(self, columns: Any) -> "WorkflowDataFrame":
        return self._add_process(AlterColumns, params=dict(columns=str(columns)))

    def drop(self, columns: List[str], if_exists: bool = False) -> "WorkflowDataFrame":
        return self._add_process(
            DropColumns, params=dict(columns=columns, if_exists=if_exists)
        )

    def __getitem__(self, columns: List[Any]) -> "WorkflowDataFrame":
        return self._add_process(SelectColumnsP, params=dict(columns=columns))

    # ---- zip -------------------------------------------------------------
    def zip(
        self,
        *dfs: "WorkflowDataFrame",
        how: str = "inner",
        partition: Any = None,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> "WorkflowDataFrame":
        return self._workflow.zip(
            self, *dfs, how=how,
            partition=partition or self._pending_partition,
            temp_path=temp_path, to_file_threshold=to_file_threshold,
        )

    # ---- checkpoints / persist / broadcast ------------------------------
    def persist(self) -> "WorkflowDataFrame":
        self._task.checkpoint = WeakCheckpoint(lazy=False)
        return self

    def weak_checkpoint(self, lazy: bool = False, **kwargs: Any) -> "WorkflowDataFrame":
        self._task.checkpoint = WeakCheckpoint(lazy=lazy, **kwargs)
        return self

    def checkpoint(self, **kwargs: Any) -> "WorkflowDataFrame":
        # non-deterministic strong checkpoint lives in the per-run TEMP dir
        # (cleaned up after run); only deterministic ones are permanent
        self._task.checkpoint = StrongCheckpoint(
            obj_id=str(uuid4()), deterministic=False, permanent=False, **kwargs
        )
        return self

    def strong_checkpoint(self, **kwargs: Any) -> "WorkflowDataFrame":
        return self.checkpoint(**kwargs)

    def deterministic_checkpoint(
        self, namespace: Any = None, **kwargs: Any
    ) -> "WorkflowDataFrame":
        self._task.checkpoint = StrongCheckpoint(
            obj_id=self._task.__uuid__(),
            deterministic=True,
            permanent=True,
            namespace=namespace,
            **kwargs,
        )
        return self

    def broadcast(self) -> "WorkflowDataFrame":
        self._task.broadcast_result = True
        return self

    # ---- fault tolerance -------------------------------------------------
    def fault_tolerant(
        self,
        max_attempts: Optional[int] = None,
        backoff: Optional[float] = None,
        jitter: Optional[float] = None,
        timeout: Optional[float] = None,
        retry_on: Any = None,
    ) -> "WorkflowDataFrame":
        """Per-task override of the workflow fault policy
        (``fugue.workflow.retry.*`` / ``fugue.workflow.timeout``):
        retry the task producing THIS dataframe up to ``max_attempts``
        times on transient errors with exponential ``backoff`` (+
        ``jitter``), abandon it after ``timeout`` seconds of wall clock
        (parallel runner), and additionally treat the ``retry_on``
        exception types as transient."""
        if isinstance(retry_on, type):  # a bare exception class is fine
            retry_on = (retry_on,)
        ov = dict(self._task.fault_override or {})
        for k, v in (
            ("max_attempts", max_attempts),
            ("backoff", backoff),
            ("jitter", jitter),
            ("timeout", timeout),
            ("retry_on", None if retry_on is None else tuple(retry_on)),
        ):
            if v is not None:
                ov[k] = v
        self._task.fault_override = ov
        return self

    # ---- yields ----------------------------------------------------------
    def yield_dataframe_as(self, name: str, as_local: bool = False) -> None:
        y = YieldedDataFrame(self._task.__uuid__())
        self._task.yields.append(y)
        self._task.yield_as_local = as_local
        self._workflow.register_yield(name, y)

    def yield_file_as(self, name: str, **kwargs: Any) -> None:
        if not isinstance(self._task.checkpoint, StrongCheckpoint):
            # reference workflow.py:1006: a RANDOM namespace per DAG build =
            # permanent but effectively non-deterministic checkpoint, so a
            # rebuilt workflow with different data never serves stale yields
            # (task uuids hash dataframes weakly); an EXPLICIT deterministic
            # checkpoint before the yield opts back into skip-on-rerun
            kwargs.setdefault("namespace", str(uuid4()))
            self._task.checkpoint = StrongCheckpoint(
                obj_id=self._task.__uuid__(), deterministic=True, permanent=True,
                **kwargs,
            )
        y = PhysicalYielded(self._task.__uuid__(), "file")
        self._task.checkpoint.yielded = y  # type: ignore
        self._workflow.register_yield(name, y)

    def yield_table_as(self, name: str, **kwargs: Any) -> None:
        if not isinstance(self._task.checkpoint, TableCheckpoint):
            # same random-namespace guard as yield_file_as
            kwargs.setdefault("namespace", str(uuid4()))
            self._task.checkpoint = TableCheckpoint(
                obj_id=self._task.__uuid__(), deterministic=True, **kwargs
            )
        y = PhysicalYielded(self._task.__uuid__(), "table")
        self._task.checkpoint.yielded = y  # type: ignore
        self._workflow.register_yield(name, y)

    # ---- io / output sugar ----------------------------------------------
    def save(
        self,
        path: str,
        fmt: str = "",
        mode: str = "overwrite",
        partition: Any = None,
        single: bool = False,
        **kwargs: Any,
    ) -> None:
        task = OutputTask(
            Save,
            params=dict(path=path, fmt=fmt, mode=mode, single=single, params=kwargs),
            partition_spec=PartitionSpec(partition or self._pending_partition),
            input_tasks=[self._task],
        )
        self._workflow.add(task)

    def save_and_use(
        self,
        path: str,
        fmt: str = "",
        mode: str = "overwrite",
        partition: Any = None,
        single: bool = False,
        **kwargs: Any,
    ) -> "WorkflowDataFrame":
        return self._add_process(
            SaveAndUse,
            params=dict(
                path=path, fmt=fmt, mode=mode, single=single, params=kwargs
            ),
            partition_spec=PartitionSpec(partition or self._pending_partition),
        )

    def show(
        self, n: int = 10, with_count: bool = False, title: Optional[str] = None
    ) -> None:
        task = OutputTask(
            Show,
            params=dict(n=n, with_count=with_count, title=title or ""),
            input_tasks=[self._task],
        )
        self._workflow.add(task)

    def assert_eq(self, *dfs: "WorkflowDataFrame", **params: Any) -> None:
        self._workflow.assert_eq(self, *dfs, **params)

    def assert_not_eq(self, *dfs: "WorkflowDataFrame", **params: Any) -> None:
        self._workflow.assert_not_eq(self, *dfs, **params)

    # ---- internals -------------------------------------------------------
    def _add_process(
        self,
        ext: Any,
        params: Any = None,
        partition_spec: Optional[PartitionSpec] = None,
    ) -> "WorkflowDataFrame":
        task = ProcessTask(
            ext,
            params=params,
            partition_spec=partition_spec or PartitionSpec(),
            input_tasks=[self._task],
        )
        return self._workflow.add(task)


class FugueWorkflow:
    """Build and run a workflow DAG (reference workflow.py:1499)."""

    def __init__(self, compile_conf: Any = None):
        self._tasks: List[FugueTask] = []
        self._yields: Dict[str, Yielded] = {}
        self._conf = ParamDict(FUGUE_GLOBAL_CONF)
        self._conf.update(ParamDict(compile_conf))
        self._computed = False
        self._last_df: Optional[WorkflowDataFrame] = None
        # the most recent profiled run's RunProfile (None otherwise) —
        # run()'s finalize reads it for the slow-query top-tasks block
        self._last_run_profile: Any = None

    @property
    def yields(self) -> Dict[str, Yielded]:
        return self._yields

    @property
    def tasks(self) -> List[FugueTask]:
        """The DAG's tasks in build (= dependency) order."""
        return list(self._tasks)

    @property
    def last_df(self) -> Optional[WorkflowDataFrame]:
        return self._last_df

    def register_yield(self, name: str, y: Yielded) -> None:
        assert_or_throw(
            name not in self._yields, ValueError(f"duplicated yield {name}")
        )
        self._yields[name] = y

    def add(self, task: FugueTask) -> WorkflowDataFrame:
        task.callsite = extract_user_callsite(
            self._conf.get(FUGUE_CONF_WORKFLOW_EXCEPTION_INJECT, 3),
            [self._conf.get(FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE, "fugue_tpu.")],
        )
        self._tasks.append(task)
        res = WorkflowDataFrame(self, task)
        if not isinstance(task, OutputTask):
            self._last_df = res
        return res

    # ---- creation --------------------------------------------------------
    def create(
        self, using: Any, schema: Any = None, params: Any = None
    ) -> WorkflowDataFrame:
        import pandas as pd

        if isinstance(using, (DataFrame, pd.DataFrame)):
            # a dataframe input IS the data: identical spec (and uuid) to
            # dag.df(data) — reference builtin_suite.py:106 equivalence
            assert_or_throw(
                params is None or len(ParamDict(params)) == 0,
                ValueError("params not allowed when creating from a dataframe"),
            )
            return self.create_data(using, schema)
        task = CreateTask(using, params=ParamDict(params), schema=schema)
        return self.add(task)

    def df(self, data: Any, schema: Any = None) -> WorkflowDataFrame:
        return self.create_data(data, schema)

    def create_data(self, data: Any, schema: Any = None) -> WorkflowDataFrame:
        if isinstance(data, WorkflowDataFrame):
            assert_or_throw(
                data.workflow is self, ValueError("dataframe from another workflow")
            )
            return data
        task = CreateTask(
            CreateData,
            params=dict(
                data=data, schema=None if schema is None else str(Schema(schema))
            ),
        )
        return self.add(task)

    def load(
        self, path: str, fmt: str = "", columns: Any = None, **kwargs: Any
    ) -> WorkflowDataFrame:
        task = CreateTask(
            Load,
            params=dict(path=path, fmt=fmt, columns=columns, params=kwargs),
        )
        return self.add(task)

    # ---- generic ---------------------------------------------------------
    def process(
        self,
        *dfs: Any,
        using: Any,
        schema: Any = None,
        params: Any = None,
        pre_partition: Any = None,
    ) -> WorkflowDataFrame:
        inputs, names = self._resolve_dfs(*dfs)
        task = ProcessTask(
            using,
            params=ParamDict(params),
            schema=schema,
            partition_spec=PartitionSpec(pre_partition),
            input_tasks=inputs,
            input_names=names,
        )
        return self.add(task)

    def output(
        self, *dfs: Any, using: Any, params: Any = None, pre_partition: Any = None
    ) -> None:
        inputs, names = self._resolve_dfs(*dfs)
        task = OutputTask(
            using,
            params=ParamDict(params),
            partition_spec=PartitionSpec(pre_partition),
            input_tasks=inputs,
            input_names=names,
        )
        self.add(task)

    def transform(self, *dfs: Any, using: Any, **kwargs: Any) -> WorkflowDataFrame:
        assert_or_throw(len(dfs) == 1, ValueError("transform takes 1 df"))
        return self.create_data(dfs[0]).transform(using, **kwargs)

    def out_transform(self, *dfs: Any, using: Any, **kwargs: Any) -> None:
        assert_or_throw(len(dfs) == 1, ValueError("out_transform takes 1 df"))
        self.create_data(dfs[0]).out_transform(using, **kwargs)

    # ---- multi-df ops ----------------------------------------------------
    def join(
        self, *dfs: Any, how: str, on: Optional[List[str]] = None
    ) -> WorkflowDataFrame:
        inputs, names = self._resolve_dfs(*dfs)
        task = ProcessTask(
            RunJoin,
            params=dict(how=how, on=on or []),
            input_tasks=inputs,
            input_names=names,
        )
        return self.add(task)

    def set_op(self, how: str, *dfs: Any, distinct: bool = True) -> WorkflowDataFrame:
        inputs, names = self._resolve_dfs(*dfs)
        task = ProcessTask(
            RunSetOperation,
            params=dict(how=how, distinct=distinct),
            input_tasks=inputs,
            input_names=names,
        )
        return self.add(task)

    def union(self, *dfs: Any, distinct: bool = True) -> WorkflowDataFrame:
        return self.set_op("union", *dfs, distinct=distinct)

    def subtract(self, *dfs: Any, distinct: bool = True) -> WorkflowDataFrame:
        return self.set_op("subtract", *dfs, distinct=distinct)

    def intersect(self, *dfs: Any, distinct: bool = True) -> WorkflowDataFrame:
        return self.set_op("intersect", *dfs, distinct=distinct)

    def zip(
        self,
        *dfs: Any,
        how: str = "inner",
        partition: Any = None,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> WorkflowDataFrame:
        inputs, names = self._resolve_dfs(*dfs)
        task = ProcessTask(
            Zip,
            params=dict(
                how=how, temp_path=temp_path, to_file_threshold=to_file_threshold
            ),
            partition_spec=PartitionSpec(partition),
            input_tasks=inputs,
            input_names=names,
        )
        return self.add(task)

    def select(
        self,
        *statements: Any,
        statement: Any = None,
        dfs: Optional[Dict[str, Any]] = None,
        dialect: Optional[str] = None,
    ) -> WorkflowDataFrame:
        """Raw SQL SELECT via the engine's SQLEngine. Accepts either one
        statement (positional or ``statement=``) plus ``dfs={name: df}``,
        or the reference's interleaved form mixing fragments and
        dataframes::

            dag.select("SELECT k, SUM(x) AS s FROM", df, "GROUP BY k")
        """
        if statement is not None:
            assert_or_throw(
                len(statements) == 0,
                ValueError("pass the statement positionally OR by keyword"),
            )
            statements = (statement,)
        if len(statements) == 1 and isinstance(
            statements[0], (str, StructuredRawSQL)
        ):
            statement = statements[0]
        else:
            from fugue_tpu.collections.sql import interleave_sql

            parts, inline = interleave_sql(statements)
            statement = StructuredRawSQL(parts, dialect=dialect)
            dfs = {**(dfs or {}), **inline}
        named = {k: self.create_data(v) for k, v in (dfs or {}).items()}
        inputs = [v.task for v in named.values()]
        names = list(named.keys())
        if isinstance(statement, str):
            statement = StructuredRawSQL([(False, statement)], dialect=dialect)
        task = ProcessTask(
            RunSQLSelect,
            params=dict(statement=statement),
            input_tasks=inputs,
            input_names=names if len(names) > 0 else None,
        )
        return self.add(task)

    def assert_eq(self, *dfs: Any, **params: Any) -> None:
        self.output(*dfs, using=AssertEqFunc, params=params)

    def assert_not_eq(self, *dfs: Any, **params: Any) -> None:
        self.output(*dfs, using=AssertNotEqFunc, params=params)

    def show(
        self, *dfs: Any, n: int = 10, with_count: bool = False,
        title: Optional[str] = None,
    ) -> None:
        self.output(
            *dfs, using=Show, params=dict(n=n, with_count=with_count,
                                          title=title or ""),
        )

    # ---- static analysis -------------------------------------------------
    def analyze(
        self,
        conf: Any = None,
        engine: Any = None,
        exclude_lint_only: bool = False,
    ) -> List[Any]:
        """Statically analyze the built (unexecuted) DAG and return the
        list of :class:`~fugue_tpu.analysis.Diagnostic` findings, most
        severe first — stable-coded rules over schemas, partition specs,
        conf keys and predicted engine behavior. Nothing executes.

        With no ``engine``, every rule scope runs (lint mode); pass the
        target engine — a live instance or the same name/spec ``run()``
        accepts (e.g. ``"jax"``) — to narrow engine-specific rules to the
        actual backend."""
        from fugue_tpu.analysis import Analyzer

        if engine is not None and not hasattr(engine, "conf"):
            # an engine NAME/spec, as run() accepts: resolve it the same
            # way — analyze(engine="jax") must not silently degrade to a
            # generic-only (false-clean) report
            engine = make_execution_engine(engine, conf)
        merged = ParamDict(self._conf)
        # a live engine brings its own conf (row_bucket, memory budget, …);
        # engine-dependent rules must read it, not the global defaults
        engine_conf = getattr(engine, "conf", None)
        if engine_conf is not None:
            merged.update(ParamDict(engine_conf))
        merged.update(ParamDict(conf))
        return Analyzer().analyze(
            self, conf=merged, engine=engine,
            exclude_lint_only=exclude_lint_only,
        )

    def explain(self, conf: Any = None, engine: Any = None) -> Any:
        """EXPLAIN: the static plan report for this DAG — the
        optimizer-rewritten task tree (clone-and-pin dry run; this
        workflow is untouched) with applied rewrites, propagated
        schemas and estimated device bytes, as a text tree
        (``.to_text()``) and JSON (``.to_dict()``). Nothing executes.
        ``engine`` accepts a live instance or the same name/spec
        ``run()`` accepts."""
        from fugue_tpu.analysis.explain import explain_workflow

        if engine is not None and not hasattr(engine, "conf"):
            engine = make_execution_engine(engine, conf)
        return explain_workflow(self, conf=conf, engine=engine)

    def _pre_run_analysis(self, e: Any, run_conf: Any = None) -> None:
        """The ``fugue.analysis`` gate at the top of ``run()``: ``off``
        skips, ``warn`` (default) logs findings and proceeds, ``error``
        raises :class:`WorkflowAnalysisError` before any task executes
        when error-level diagnostics exist. The analyzer itself is
        sandboxed — an internal analyzer failure never blocks a run."""
        # precedence: run/engine conf > workflow compile conf > default.
        # run() hands us its RAW conf argument, so an explicitly passed
        # run-level value always wins — even one equal to the default
        # (e.g. run-level "warn" relaxing a compile-level "error"). Only
        # the merged engine conf inherits the global default, so there an
        # inherited-default value is "not set" and yields to an explicit
        # compile-conf override.
        from fugue_tpu.constants import conf_default

        default = str(conf_default(FUGUE_CONF_ANALYSIS))
        raw_run = ParamDict(run_conf)
        e_val = str(e.conf.get(FUGUE_CONF_ANALYSIS, default))
        c_val = str(self._conf.get(FUGUE_CONF_ANALYSIS, default))
        if FUGUE_CONF_ANALYSIS in raw_run:
            mode = str(raw_run[FUGUE_CONF_ANALYSIS]).strip().lower()
        else:
            mode = (
                c_val if e_val == default and c_val != default else e_val
            ).strip().lower()
        if mode in ("off", "false", "0", "none", ""):
            return
        if mode not in ("warn", "error", "true", "on", "1"):
            # an unrecognized mode must NOT silently degrade to warn: the
            # user asked for a gate that doesn't exist
            raise ValueError(
                f"invalid {FUGUE_CONF_ANALYSIS} mode {mode!r}: "
                "expected off | warn | error"
            )
        from fugue_tpu.analysis import Severity
        from fugue_tpu.exceptions import WorkflowAnalysisError

        try:
            # lint_only rules (FWF501's optimizer dry-run) are skipped:
            # run() performs the rewrite for real right after this gate
            diags = self.analyze(
                conf=e.conf, engine=e, exclude_lint_only=True
            )
        except WorkflowAnalysisError:  # pragma: no cover - defensive
            raise
        except Exception as ex:  # analyzer bug: log VISIBLY (the user asked
            # for a gate that silently didn't run), never block the run
            e.log.warning(
                "fugue_tpu workflow analysis crashed and was skipped "
                "(the %s gate did not run): %s: %s",
                FUGUE_CONF_ANALYSIS,
                type(ex).__name__,
                ex,
            )
            return
        if mode == "error" and any(
            d.severity is Severity.ERROR for d in diags
        ):
            raise WorkflowAnalysisError(diags)
        for d in diags:
            if d.severity is Severity.ERROR or d.severity is Severity.WARN:
                e.log.warning("fugue_tpu analysis: %s", d.describe())
            else:
                e.log.info("fugue_tpu analysis: %s", d.describe(False))

    # ---- run -------------------------------------------------------------
    def run(
        self,
        engine: Any = None,
        conf: Any = None,
        cancel_token: Any = None,
    ) -> "FugueWorkflowResult":
        """Execute the DAG. ``cancel_token`` (optional): a caller-owned
        :class:`~fugue_tpu.workflow.fault.CancelToken` shared with the
        runner — setting it from another thread cancels the run at the
        next task boundary (how the serving daemon cancels a job
        mid-workflow). The token is a ONE-RUN object: the runner also
        sets it internally when any task fails (that is the sibling
        abort signal), so never reuse a token across runs — a re-run
        with a fired token cancels immediately."""
        e = make_execution_engine(engine, conf)
        # observability: under an AMBIENT trace (a serving daemon's job)
        # this run is one child span; embedded with fugue.obs.enabled it
        # OWNS a per-run trace — exported to fugue.obs.trace_path and
        # slow-query-checked at the end
        opts = obs_options(e.conf)
        owned_trace = obs_root = None
        # never under suppression: a serving daemon's job whose request
        # lost the sampling draw — re-drawing here would export
        # uncorrelated traces at ~double the configured rate
        if (
            opts.enabled
            and not tracing_suppressed()
            and current_span() is None
        ):
            owned_trace, obs_root = open_trace(
                opts,
                "workflow.run",
                workflow=self.__uuid__()[:12],
                tasks=len(self._tasks),
            )
        if obs_root is not None:
            run_scope: Any = activate(obs_root)
        else:
            # a child of the ambient trace, else a profiler event alone
            # (or the shared no-op)
            run_scope = start_span("workflow.run", tasks=len(self._tasks))
        try:
            with run_scope:
                return self._run_inner(e, conf, cancel_token)
        finally:
            finalize_trace(
                owned_trace,
                opts,
                fs=e.fs,
                log=e.log,
                registry=e.metrics,
                profile=self._last_run_profile,
                what="workflow.run",
                workflow=self.__uuid__()[:12],
            )

    def _overlay_optimize_conf(self, base_conf: Any) -> ParamDict:
        """The ``fugue.optimize*`` precedence shared by run()'s rewrite
        phase, ``explain()`` and the EXPLAIN ANALYZE tree (they must
        all describe the SAME plan): a base/engine conf value that
        still equals the registered default is "not set", so an
        explicit workflow compile-conf value (``fugue.optimize`` and
        its per-rule keys) wins over the inherited default — the same
        dance as the ``fugue.analysis`` gate."""
        from fugue_tpu.constants import declared_conf_keys

        declared = declared_conf_keys()
        conf = ParamDict(base_conf)
        for k, v in self._conf.items():
            if not isinstance(k, str) or not k.startswith("fugue.optimize"):
                continue
            info = declared.get(k)
            if info is not None and str(conf.get(k, info.default)) == str(
                info.default
            ):
                conf[k] = v
        return conf

    def _optimized_tasks(self, e: Any) -> List[FugueTask]:
        """The task list execution runs: the optimizer's rewrite phase
        (``fugue.optimize``; ``auto`` = jax engines only) over a CLONED
        graph whose uuids are pinned to the original tasks — rewrites
        never change the identities deterministic checkpoints and
        manifest resume key on. The phase is sandboxed: an optimizer
        crash logs a warning and the pristine DAG runs instead."""
        from fugue_tpu.optimize import optimize_enabled, optimize_tasks

        conf = self._overlay_optimize_conf(e.conf)
        # an invalid fugue.optimize mode must raise (the user asked for
        # a gate that doesn't exist), so it is checked OUTSIDE the
        # sandbox below
        if not optimize_enabled(conf, e):
            return list(self._tasks)
        try:
            plan = optimize_tasks(self._tasks, conf=conf, engine=e)
            for note in plan.applied:
                e.log.info("fugue_tpu optimize: %s", note.describe())
            return plan.tasks
        except Exception as ex:
            e.log.warning(
                "fugue_tpu optimize crashed and was skipped (the DAG "
                "runs unoptimized): %s: %s",
                type(ex).__name__,
                ex,
            )
            return list(self._tasks)

    def _run_inner(
        self,
        e: Any,
        conf: Any = None,
        cancel_token: Any = None,
    ) -> "FugueWorkflowResult":
        self._pre_run_analysis(e, run_conf=conf)
        run_tasks = self._optimized_tasks(e)
        execution_id = str(uuid4())
        rpc_server = make_rpc_server(e.conf)
        checkpoint_path = CheckpointPath(e)
        token = cancel_token if cancel_token is not None else CancelToken()
        stats = RunStats(registry=e.metrics)
        ctx = TaskContext(e, rpc_server, checkpoint_path, cancel_token=token)
        base_policy = RetryPolicy.from_conf(e.conf)
        concurrency = e.conf.get(FUGUE_CONF_WORKFLOW_CONCURRENCY, 1)
        # per-task profiler (EXPLAIN ANALYZE): only constructed when
        # fugue.obs.profile is requested (conf gate needs fugue.obs.
        # enabled for the span-derived phase split; the serving daemon's
        # per-request flag forces it) — off means the task wrapper takes
        # the pre-existing path and nothing here allocates
        profiler = None
        if profiling_forced() or profiling_requested(e.conf):
            profiler = Profiler(
                self.__uuid__(), e, concurrency=int(concurrency)
            )
        # checkpoint-backed resume: None unless fugue.workflow.resume is on
        # AND a durable checkpoint dir exists to hold the run manifest
        manifest = RunManifest.from_conf(e, checkpoint_path, self.__uuid__())
        started_rpc = in_ctx = False
        try:
            rpc_server.start()
            started_rpc = True
            e.as_context()
            in_ctx = True
            checkpoint_path.init_temp_path(execution_id)
            index_of = {id(t): i for i, t in enumerate(run_tasks)}
            nodes = [
                TaskNode(
                    t.__uuid__() + f"_{i}",
                    self._make_task_func(
                        t, ctx, base_policy, token, manifest, stats,
                        profiler=profiler,
                    ),
                    [
                        inp.__uuid__() + f"_{index_of[id(inp)]}"
                        for inp in t.inputs
                    ],
                    name=t.name,
                    callsite=t.callsite,
                    timeout=self._task_policy(t, base_policy).timeout,
                )
                for i, t in enumerate(run_tasks)
            ]
            on_complete = None
            if manifest is not None:
                by_node_id = {
                    t.__uuid__() + f"_{i}": t
                    for i, t in enumerate(run_tasks)
                }
                on_complete = lambda node: manifest.mark_complete(  # noqa: E731
                    by_node_id[node.task_id]
                )
            try:
                DAGRunner(concurrency).run(
                    nodes, on_complete=on_complete, cancel_token=token
                )
            except Exception as ex:
                # prune at the outermost point: frames added during
                # propagation through the runner are framework noise too
                if self._conf.get("fugue.workflow.exception.optimize", True):
                    hide = [
                        self._conf.get(
                            FUGUE_CONF_WORKFLOW_EXCEPTION_HIDE, "fugue_tpu."
                        ),
                        "concurrent.futures.",
                        "threading",
                    ]
                    # ``from ex.__cause__`` (not ``from None``): both
                    # suppress the re-raise context, but this one keeps
                    # the cause an aggregated WorkflowRuntimeError chains
                    # to its first failure
                    raise ex.with_traceback(
                        prune_traceback(ex.__traceback__, hide)
                    ) from ex.__cause__
                raise
            self._computed = True
            if manifest is not None:
                manifest.finish()
            # governed jax engines: the memory ledger snapshot rides the
            # run's fault stats (same surface as retries/degradations)
            mem = getattr(e, "memory_stats", None)
            if isinstance(mem, dict) and mem.get("enabled"):
                stats.set_memory(mem)
        finally:
            if in_ctx:
                e.stop_context()
            checkpoint_path.remove_temp_path()
            if started_rpc:
                rpc_server.stop()
        run_profile = None
        if profiler is not None:
            run_profile = self._settle_profile(e, profiler, stats)
        return FugueWorkflowResult(
            self._yields, stats=stats, profile=run_profile
        )

    def _settle_profile(self, e: Any, profiler: Any, stats: Any) -> Any:
        """Finalize a profiled run: merge the span-derived phase split,
        attach the EXPLAIN tree (same deterministic rewrite dry run the
        plan executed, so uuids line up), persist the observation into
        the runtime-statistics store when ``fugue.stats.path`` is set,
        and stash the profile for the slow-query enrichment in
        ``run()``'s finalize. Every step is best-effort — profiling
        must never fail the run it measured."""
        cur = current_span()
        run_profile = profiler.finalize(
            trace=cur.trace if cur is not None else None, stats=stats
        )
        try:
            from fugue_tpu.analysis.explain import explain_tasks

            # the SAME conf overlay _optimized_tasks used: the attached
            # tree must describe the plan this run actually executed
            run_profile.report = explain_tasks(
                self._tasks,
                conf=self._overlay_optimize_conf(e.conf),
                engine=e,
            )
        except Exception as ex:  # plan report is additive
            e.log.warning(
                "fugue_tpu profile: EXPLAIN tree build failed (%s: %s); "
                "the runtime profile stands alone",
                type(ex).__name__, ex,
            )
        try:
            from fugue_tpu.constants import (
                FUGUE_CONF_STATS_HISTORY,
                FUGUE_CONF_STATS_PATH,
                typed_conf_get,
            )

            stats_path = typed_conf_get(e.conf, FUGUE_CONF_STATS_PATH)
            if str(stats_path or "").strip():
                from fugue_tpu.obs.stats_store import get_stats_store

                get_stats_store(
                    e,
                    stats_path,
                    history=typed_conf_get(e.conf, FUGUE_CONF_STATS_HISTORY),
                ).record(self.__uuid__(), run_profile.observation())
        except Exception as ex:  # pragma: no cover - store is best-effort
            e.log.warning(
                "fugue_tpu profile: statistics-store record failed "
                "(%s: %s); the run is unaffected",
                type(ex).__name__, ex,
            )
        self._last_run_profile = run_profile
        return run_profile

    def _task_policy(self, task: FugueTask, base: RetryPolicy) -> RetryPolicy:
        if not task.fault_override:
            return base
        return base.override(**task.fault_override)

    def _make_task_func(
        self,
        task: FugueTask,
        ctx: TaskContext,
        base_policy: RetryPolicy,
        token: CancelToken,
        manifest: Optional[RunManifest],
        stats: RunStats,
        profiler: Any = None,
    ) -> Callable:
        policy = self._task_policy(task, base_policy)

        def attempt(inputs: List[Any]) -> Any:
            # fault-injection site INSIDE the attempt loop: "task" faults
            # fire per attempt, so nth-invocation plans exercise retries
            from fugue_tpu.testing.faults import fault_point

            fault_point("task", task.name)
            return task.execute(ctx, inputs)

        def run_task(inputs: List[Any]) -> Any:
            # one span per TaskNode execution (the runner worker thread
            # inherits the run's context via DAGRunner._spawn); attempt
            # spans nest under it from execute_with_policy. With the
            # profiler off (None), this is the pre-existing path plus
            # one is-None check — nothing is allocated.
            with start_span(
                "task", task=task.name, type=task.task_type
            ) as sp:
                rec = None if profiler is None else profiler.begin(task, sp)
                # NULL_CM when off: the shared no-op, nothing allocated
                with NULL_CM if rec is None else task_scope(rec):
                    return _execute(inputs, rec)

        def _execute(inputs: List[Any], rec: Any) -> Any:
            try:
                # manifest resume is OBSERVED here but served by the
                # task's own checkpoint short-circuit inside
                # execute(): validations still fire and there is
                # only one load path
                if manifest is not None and manifest.can_resume(
                    task, ctx, stats=stats
                ):
                    stats.note_resumed(task.name)
                # each attempt inside holds the engine's dispatch
                # guard (task_execution_lock): shared-engine device
                # programs serialize per attempt, host phases overlap
                result = execute_with_policy(
                    lambda: attempt(inputs),
                    policy,
                    engine=ctx.engine,
                    token=token,
                    task_name=task.name,
                    stats=stats,
                    log=ctx.engine.log,
                )
            except Exception as ex:
                if rec is not None:
                    profiler.finish(rec, inputs, None, error=ex)
                self._reraise_with_callsite(task, ex)
            if rec is not None:
                profiler.finish(rec, inputs, result)
            return result

        return run_task

    def _reraise_with_callsite(self, task: FugueTask, ex: Exception) -> None:
        """Attach the failing task's name and the USER's workflow callsite
        to the error, so a failing transform points at the line that
        defined it rather than runner internals (notes survive retry
        wrapping, pruning and aggregation)."""
        note = f"in task {task.name}"
        if task.callsite:
            note += ", defined at:\n" + "\n".join(task.callsite)
        add_error_note(ex, note)
        raise ex

    def __enter__(self) -> "FugueWorkflow":
        return self

    def __exit__(self, exc_type: Any, *args: Any) -> None:
        if exc_type is None:
            self.run()

    def __uuid__(self) -> str:
        return to_uuid([t.__uuid__() for t in self._tasks])

    def _resolve_dfs(self, *dfs: Any) -> Any:
        if len(dfs) == 1 and isinstance(dfs[0], dict):
            named = {k: self.create_data(v) for k, v in dfs[0].items()}
            return [v.task for v in named.values()], list(named.keys())
        inputs = [self.create_data(d).task for d in dfs]
        return inputs, None


class FugueWorkflowResult:
    """Run result: access yielded dataframes (reference workflow.py:1609)
    plus the run's fault-tolerance stats (retries/recoveries/degradations
    per task and manifest-resumed tasks) and — for profiled runs — the
    per-task runtime profile (EXPLAIN ANALYZE)."""

    def __init__(
        self,
        yields: Dict[str, Yielded],
        stats: Any = None,
        profile: Any = None,
    ):
        self._yields = yields
        self._stats = stats
        self._profile = profile

    @property
    def yields(self) -> Dict[str, Yielded]:
        return self._yields

    @property
    def fault_stats(self) -> Dict[str, Any]:
        return self._stats.as_dict() if self._stats is not None else {}

    def profile(self) -> Any:
        """The run's :class:`~fugue_tpu.obs.profile.RunProfile` — per
        task rows in/out, device bytes, wall/compile/execute/transfer
        split, queue wait, retries and cache events, with the EXPLAIN
        plan tree attached (``.to_text()`` renders EXPLAIN ANALYZE).
        None unless the run was profiled (``fugue.obs.profile`` with
        ``fugue.obs.enabled``, or the serve ``profile`` flag)."""
        return self._profile

    def __getitem__(self, name: str) -> Any:
        y = self._yields[name]
        if isinstance(y, YieldedDataFrame):
            return y.result
        return y
