"""FugueSQLWorkflow and the ``fugue_sql`` / ``fugue_sql_flow`` entry points
(reference fugue/sql/workflow.py:16-68, fugue/sql/api.py:18,111)."""

import sys
from typing import Any, Dict, Optional, Tuple

from fugue_tpu.constants import FUGUE_CONF_SQL_DIALECT
from fugue_tpu.dataframe import DataFrame
from fugue_tpu.execution.factory import make_execution_engine
from fugue_tpu.obs.trace import start_span
from fugue_tpu.sql_frontend.fugue_parser import FugueSQLCompiler
from fugue_tpu.workflow.workflow import FugueWorkflow, WorkflowDataFrame

__all__ = [
    "FugueSQLWorkflow", "fugue_sql", "fugue_sql_flow", "fill_sql_template",
    "explain_sql", "lint_sql",
]


def fill_sql_template(template: str, params: Dict[str, Any]) -> str:
    """Jinja-fill ``{{var}}`` references in a FugueSQL script."""
    if "{{" not in template and "{%" not in template:
        return template
    try:
        from jinja2 import Template
    except ImportError:  # pragma: no cover - jinja2 is in the base image
        return template
    return Template(template).render(**params)


def _caller_vars(depth: int) -> Dict[str, Any]:
    frame = sys._getframe(depth)
    out: Dict[str, Any] = {}
    out.update(frame.f_globals)
    out.update(frame.f_locals)
    return out


def _split_params(kwargs: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Split kwargs into template params and dataframe sources."""
    params: Dict[str, Any] = {}
    dfs: Dict[str, Any] = {}
    for k, v in kwargs.items():
        if isinstance(v, (DataFrame, WorkflowDataFrame)) or \
                FugueSQLCompiler._is_dataframe_like(v):
            dfs[k] = v
        else:
            params[k] = v
    return params, dfs


class FugueSQLWorkflow(FugueWorkflow):
    """A workflow whose DAG can be built from FugueSQL scripts; usable
    incrementally::

        dag = FugueSQLWorkflow()
        dag("a = CREATE [[0]] SCHEMA x:long")
        dag("SELECT x+1 AS x FROM a PRINT")
        dag.run()
    """

    def __init__(self, compile_conf: Any = None):
        super().__init__(compile_conf)
        self._sql_vars: Dict[str, WorkflowDataFrame] = {}

    @property
    def sql_vars(self) -> Dict[str, WorkflowDataFrame]:
        return self._sql_vars

    def __call__(self, code: str, *args: Any, **kwargs: Any) -> None:
        self._sql(code, _caller_vars(2), *args, **kwargs)

    def _sql(
        self,
        code: str,
        caller_vars: Optional[Dict[str, Any]],
        *args: Any,
        **kwargs: Any,
    ) -> Dict[str, WorkflowDataFrame]:
        params: Dict[str, Any] = {}
        for a in args:
            if not isinstance(a, dict):
                raise ValueError(f"args can only contain dicts: {a}")
            params.update(a)
        params.update(kwargs)
        params, sources = _split_params(params)
        local_vars = dict(caller_vars or {})
        local_vars.update(params)
        code = fill_sql_template(code, params)
        with start_span("sql.compile"):
            compiler = FugueSQLCompiler(
                workflow=self,
                variables=self._sql_vars,
                sources=sources,
                local_vars=local_vars,
                dialect=self._conf.get(FUGUE_CONF_SQL_DIALECT, "spark"),
                last=self.last_df,
            )
            variables = compiler.compile(code)
        for k, v in variables.items():
            if isinstance(v, WorkflowDataFrame) and v.workflow is self:
                self._sql_vars[k] = v
        if compiler.last is not None:
            self._last_df = compiler.last
        return variables


def fugue_sql_flow(query: str, *args: Any, **kwargs: Any) -> FugueSQLWorkflow:
    """Build (but don't run) a FugueSQLWorkflow from a full FugueSQL script;
    use YIELD inside the script to expose results."""
    dag = FugueSQLWorkflow()
    dag._sql(query, _caller_vars(2), *args, **kwargs)
    return dag


def _fugue_sql_impl(
    query: str,
    caller_vars: Dict[str, Any],
    args: Any,
    kwargs: Dict[str, Any],
    engine: Any = None,
    engine_conf: Any = None,
    as_fugue: bool = False,
    as_local: bool = False,
) -> Any:
    dag = FugueSQLWorkflow()
    dag._sql(query, caller_vars, *args, **kwargs)
    if dag.last_df is None:
        raise ValueError(f"no dataframe to output from\n{query}")
    dag.last_df.yield_dataframe_as("result", as_local=as_local)
    e = make_execution_engine(engine, engine_conf)
    dag.run(e)
    result = dag.yields["result"].result  # type: ignore
    if as_fugue:
        return result
    from fugue_tpu.dataframe.api import get_native_as_df

    return result.native if result.is_local else get_native_as_df(result)


def lint_sql(query: str, *args: Any, conf: Any = None, **kwargs: Any) -> Any:
    """Compile a FugueSQL script into a DAG and statically analyze it
    WITHOUT executing anything: returns the list of
    :class:`~fugue_tpu.analysis.Diagnostic` findings (most severe first).
    The same compilation path as :func:`fugue_sql_flow`, so FugueSQL
    syntax errors surface as usual; column/partition/conf problems come
    back as stable-coded diagnostics instead of mid-run failures. Also
    available from the shell: ``python -m fugue_tpu.analysis script.fsql``."""
    dag = FugueSQLWorkflow(conf)
    dag._sql(query, _caller_vars(2), *args, **kwargs)
    return dag.analyze(conf=conf)


def explain_sql(
    query: str,
    *args: Any,
    conf: Any = None,
    engine: Any = None,
    **kwargs: Any,
) -> Any:
    """EXPLAIN a FugueSQL script WITHOUT executing it: compile the DAG
    (same path as :func:`fugue_sql_flow`, so caller-local dataframes
    resolve as usual) and return the
    :class:`~fugue_tpu.analysis.explain.ExplainReport` — the
    optimizer-rewritten task tree with applied rewrites, propagated
    schemas and estimated device bytes (``.to_text()`` /
    ``.to_dict()``). Pair with ``fugue.obs.profile`` and
    ``FugueWorkflowResult.profile()`` for EXPLAIN ANALYZE."""
    dag = FugueSQLWorkflow(conf)
    dag._sql(query, _caller_vars(2), *args, **kwargs)
    return dag.explain(conf=conf, engine=engine)


def fugue_sql(
    query: str,
    *args: Any,
    engine: Any = None,
    engine_conf: Any = None,
    as_fugue: bool = False,
    as_local: bool = False,
    **kwargs: Any,
) -> Any:
    """Run a FugueSQL script and return its LAST dataframe (use
    :func:`fugue_sql_flow` + YIELD for multiple outputs)."""
    return _fugue_sql_impl(
        query, _caller_vars(2), args, kwargs,
        engine=engine, engine_conf=engine_conf,
        as_fugue=as_fugue, as_local=as_local,
    )
