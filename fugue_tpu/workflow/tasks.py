"""Workflow tasks: uuid-deterministic units executed by the DAG runner
(reference fugue/workflow/_tasks.py:85-347 behavior on our own runner)."""

from typing import Any, Callable, Dict, List, Optional

from fugue_tpu.extensions.validation import (
    validate_input_schema,
    validate_partition_spec,
)
from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.collections.yielded import PhysicalYielded, Yielded
from fugue_tpu.dataframe import DataFrame, DataFrames
from fugue_tpu.dataframe.dataframe import YieldedDataFrame
from fugue_tpu.extensions.convert import (
    _to_creator,
    _to_outputter,
    _to_processor,
)
from fugue_tpu.extensions.interfaces import Creator, Outputter, Processor
from fugue_tpu.obs.profile import note_cache_event
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw
from fugue_tpu.utils.hash import to_uuid
from fugue_tpu.utils.params import ParamDict
from fugue_tpu.workflow.checkpoint import Checkpoint


def _ext_uuid(ext: Any) -> str:
    if hasattr(ext, "__uuid__"):
        return ext.__uuid__()
    if isinstance(ext, type):
        return to_uuid(f"{ext.__module__}.{ext.__qualname__}")
    return to_uuid(ext)


class FugueTask:
    """A node in the workflow DAG; identity is deterministic from the spec so
    identical DAGs produce identical task uuids across runs/processes (the
    determinism backbone used by deterministic checkpoints)."""

    def __init__(
        self,
        extension: Any,
        params: Any = None,
        schema: Any = None,
        partition_spec: Optional[PartitionSpec] = None,
        input_tasks: Optional[List["FugueTask"]] = None,
        input_names: Optional[List[str]] = None,
    ):
        self.extension = extension
        self.params = ParamDict(params)  # passed to the extension verbatim
        self.schema = schema  # for interfaceless conversion only
        self.partition_spec = partition_spec or PartitionSpec()
        self.inputs = input_tasks or []
        self.input_names = input_names
        self.checkpoint: Checkpoint = Checkpoint()
        self.broadcast_result = False
        self.yields: List[Yielded] = []
        self.yield_as_local = False
        self.callsite: List[str] = []
        # per-task fault-policy override kwargs (max_attempts/backoff/
        # jitter/timeout/retry_on), resolved against the conf-level
        # RetryPolicy at run time. Execution-only: NOT part of the task
        # uuid (retry settings must not invalidate deterministic
        # checkpoints, same as checkpoint config itself).
        self.fault_override: Optional[Dict[str, Any]] = None
        self._uuid: Optional[str] = None

    def __uuid__(self) -> str:
        if self._uuid is None:
            self._uuid = to_uuid(
                type(self).__name__,
                _ext_uuid(self.extension),
                self._params_uuid(),
                str(self.schema),
                self.partition_spec.__uuid__(),
                [t.__uuid__() for t in self.inputs],
                self.input_names,
            )
        return self._uuid

    def _params_uuid(self) -> Any:
        res: Dict[str, Any] = {}
        for k, v in self.params.items():
            if hasattr(v, "__uuid__"):
                res[k] = v.__uuid__()
            elif isinstance(v, (list, dict, str, int, float, bool, type(None))):
                res[k] = v
            else:
                res[k] = to_uuid(v)
        return res

    @property
    def task_type(self) -> str:
        """``"create"`` / ``"process"`` / ``"output"`` — the task's role in
        the DAG, used by static analysis and display tooling without
        isinstance-ing against concrete task classes."""
        if isinstance(self, CreateTask):
            return "create"
        if isinstance(self, OutputTask):
            return "output"
        return "process"

    @property
    def name(self) -> str:
        # the extension is usually a CLASS (builtins) — use its own name,
        # not "type"; instances/functions fall back to their type/name.
        # This display name keys error reports and fault-injection task
        # sites ("task", "RunTransformer*"), so it must be meaningful.
        ext = self.extension
        if isinstance(ext, type):
            base = ext.__name__
        elif callable(ext) and hasattr(ext, "__name__"):
            base = ext.__name__
        else:
            base = type(ext).__name__
        return f"{base}_{self.__uuid__()[:8]}"

    def execute(self, ctx: "TaskContext", inputs: List[DataFrame]) -> Any:
        raise NotImplementedError  # pragma: no cover

    # ---- shared result handling -----------------------------------------
    def _result_cache(self, ctx: "TaskContext") -> Any:
        """The optimizer's in-memory result tier over deterministic
        checkpoints (``fugue.optimize.result_cache``, opt-in), or None."""
        from fugue_tpu.optimize import cache as _plan_cache

        if not _plan_cache.task_result_cache_enabled(ctx.engine):
            return None
        return _plan_cache

    def _try_skip(self, ctx: "TaskContext") -> Optional[DataFrame]:
        """Deterministic-checkpoint short circuit: reuse the artifact and
        skip compute when an identical DAG already produced it. With
        ``fugue.optimize.result_cache`` on, a process-wide memory tier
        sits in front of the artifact: the previously loaded dataframe
        is served (artifact existence re-verified) without paying the
        parquet decode again."""
        cache = self._result_cache(ctx)
        if cache is not None:
            hit = cache.get_task_result(self, ctx)
            if hit is not None:
                # profiler attribution (thread-local; no-op when off)
                note_cache_event("result", "hit")
                return self._finalize(ctx, hit, run_checkpoint=False)
        cached = self.checkpoint.try_load(ctx.checkpoint_path)
        if cached is None:
            return None
        note_cache_event("checkpoint", "hit")
        if cache is not None:
            cache.put_task_result(self, ctx, cached)
        return self._finalize(ctx, cached, run_checkpoint=False)

    def _finalize(
        self, ctx: "TaskContext", df: DataFrame, run_checkpoint: bool = True
    ) -> DataFrame:
        if run_checkpoint:
            df = self.checkpoint.run(df, ctx.checkpoint_path)
            cache = self._result_cache(ctx)
            if cache is not None:
                cache.put_task_result(self, ctx, df)
        if self.broadcast_result:
            df = ctx.engine.broadcast(df)
        for y in self.yields:
            if isinstance(y, YieldedDataFrame):
                y.set_value(
                    ctx.engine.convert_yield_dataframe(df, self.yield_as_local)
                )
        return df

    def _setup_extension(self, ext: Any, ctx: "TaskContext") -> None:
        ext._params = self.params
        ext._workflow_conf = ctx.engine.conf
        ext._execution_engine = ctx.engine
        ext._partition_spec = self.partition_spec
        ext._rpc_server = ctx.rpc_server


class TaskContext:
    def __init__(
        self,
        engine: Any,
        rpc_server: Any,
        checkpoint_path: Any,
        cancel_token: Any = None,
    ):
        self.engine = engine
        self.rpc_server = rpc_server
        self.checkpoint_path = checkpoint_path
        # cooperative cancellation: long-running extensions may poll
        # ctx.cancel_token.cancelled / raise_if_cancelled() to stop early
        # when a sibling task failed or timed out
        self.cancel_token = cancel_token


class CreateTask(FugueTask):
    """Wrap a Creator (reference _tasks.py:214)."""

    def execute(self, ctx: TaskContext, inputs: List[DataFrame]) -> DataFrame:
        cached = self._try_skip(ctx)
        if cached is not None:
            return cached
        creator = _to_creator(self.extension, self.schema)
        self._setup_extension(creator, ctx)
        df = creator.create()
        return self._finalize(ctx, ctx.engine.to_df(df))


class ProcessTask(FugueTask):
    """Wrap a Processor (reference _tasks.py:243)."""

    def execute(self, ctx: TaskContext, inputs: List[DataFrame]) -> DataFrame:
        # validations are declarations about the WORKFLOW, not the data:
        # they must fire even when the task result is checkpoint-cached.
        # Schemas validate DIRECTLY on the inputs (no conversion) and
        # _make_dfs runs only past the checkpoint check, so a
        # deterministic-cache hit never pays input conversion — EXCEPT a
        # raw (non-DataFrame) input under declared input-schema rules,
        # which has no schema to validate until converted
        processor = _to_processor(self.extension, self.schema)
        self._setup_extension(processor, ctx)
        rules = processor.validation_rules
        validate_partition_spec(rules, self.partition_spec)
        if "input_has" in rules or "input_is" in rules:
            inputs = [
                i if isinstance(i, DataFrame) else ctx.engine.to_df(i)
                for i in inputs
            ]
            for i in inputs:
                validate_input_schema(rules, i.schema)
        cached = self._try_skip(ctx)
        if cached is not None:
            return cached
        df = processor.process(self._make_dfs(ctx, inputs))
        return self._finalize(ctx, ctx.engine.to_df(df))

    def _make_dfs(self, ctx: TaskContext, inputs: List[DataFrame]) -> DataFrames:
        engine_inputs = [ctx.engine.to_df(i) if not isinstance(i, DataFrame) else i
                         for i in inputs]
        if self.input_names is not None:
            return DataFrames(dict(zip(self.input_names, engine_inputs)))
        return DataFrames(engine_inputs)


class OutputTask(FugueTask):
    """Wrap an Outputter (reference _tasks.py:297)."""

    def execute(self, ctx: TaskContext, inputs: List[DataFrame]) -> Optional[DataFrame]:
        outputter = _to_outputter(self.extension)
        self._setup_extension(outputter, ctx)
        validate_partition_spec(outputter.validation_rules, self.partition_spec)
        if self.input_names is not None:
            dfs = DataFrames(dict(zip(self.input_names, inputs)))
        else:
            dfs = DataFrames(inputs)
        for in_df in dfs.values():
            validate_input_schema(outputter.validation_rules, in_df.schema)
        outputter.process(dfs)
        # pass through the first input so dependents can still reference it
        return inputs[0] if len(inputs) > 0 else None
