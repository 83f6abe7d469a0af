"""The obs-off contract: with ``fugue.obs.enabled`` off (the default)
every instrumentation site is an allocation-free no-op — no spans exist
anywhere, no trace is opened, and a hot loop through the span sites
performs no metrics-registry writes. Tier-1 compatible; select with
``-m obs``."""

import pandas as pd
import pytest

from fugue_tpu.obs import obs_options
from fugue_tpu.obs.trace import (
    NULL_CM,
    NULL_SPAN,
    activate,
    begin_span,
    current_span,
    start_span,
)
from fugue_tpu.workflow import FugueWorkflow

pytestmark = pytest.mark.obs


def test_sites_return_the_shared_singletons():
    # no active trace on this thread: every site must hand back the ONE
    # shared no-op object — this is the no-allocation contract
    assert current_span() is None
    assert start_span("anything", attr=1) is NULL_CM
    assert begin_span("anything", attr=1) is NULL_SPAN
    assert activate(None) is NULL_CM
    with start_span("x") as sp:
        assert sp is NULL_SPAN
        sp.set_attr(ignored=True)  # swallowed
    assert not NULL_SPAN  # falsy, so `if span:` guards stay cheap


def test_hot_loop_records_no_spans_and_no_registry_writes():
    from fugue_tpu.obs.metrics import MetricsRegistry

    registry = MetricsRegistry()
    before = registry.snapshot()
    for _ in range(10_000):
        with start_span("engine.execute", program="p"):
            pass
        m = begin_span("engine.transfer", bytes=1)
        if m:  # the real sites guard exactly like this
            m.set_attr(bytes=2)
            m.finish()
    assert current_span() is None
    # the loop touched the registry zero times
    assert registry.snapshot() == before


def test_obs_off_run_opens_no_trace_and_writes_no_file():
    from fugue_tpu.execution import make_execution_engine

    # trace_path set but enabled off (the FWF404 misconfiguration):
    # the run must not open a trace, let alone write one
    e = make_execution_engine(
        "native", {"fugue.obs.trace_path": "memory://obs_off_probe"}
    )
    opts = obs_options(e.conf)
    assert not opts.enabled
    dag = FugueWorkflow()
    dag.df(pd.DataFrame({"x": [1, 2]})).yield_dataframe_as(
        "o", as_local=True
    )
    res = dag.run(e)
    assert res["o"].as_array() == [[1], [2]]
    assert not e.fs.exists("memory://obs_off_probe") or (
        e.fs.listdir("memory://obs_off_probe") == []
    )
    # no span-derived families ever materialized on the registry
    assert e.metrics.get("fugue_obs_traces_exported_total") is None
    assert e.metrics.get("fugue_obs_slow_queries_total") is None


def test_obs_off_jax_run_keeps_back_compat_counters_only():
    from fugue_tpu.execution import make_execution_engine

    e = make_execution_engine("jax")
    dag = FugueWorkflow()
    dag.df(pd.DataFrame({"x": [1, 2, 3]})).yield_dataframe_as(
        "o", as_local=True
    )
    dag.run(e)
    # migrated counters still work with obs off (they replaced the
    # ad-hoc dicts, they are not gated behind tracing)...
    assert isinstance(e.fallbacks, dict)
    assert isinstance(e.compile_cache_stats["hits"], int)
    # ...but nothing trace-shaped exists
    assert e.metrics.get("fugue_obs_traces_exported_total") is None


def test_profiler_off_and_no_trace_is_still_the_singleton(tmp_path):
    # the profiler bridge costs the off path one is_enabled() call: with
    # no profiler recording and no trace, every site is the singleton
    # again, before and after a profiler session
    import jax

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert start_span("engine.fetch", rows=1) is NULL_CM
    jax.profiler.start_trace(str(tmp_path))
    try:
        with start_span("engine.fetch") as sp:
            assert sp is not NULL_SPAN and current_span() is None
        assert begin_span("engine.transfer") is NULL_SPAN  # obs-only
    finally:
        jax.profiler.stop_trace()
    assert start_span("engine.fetch", rows=1) is NULL_CM
    with start_span("x") as sp:
        assert sp is NULL_SPAN
