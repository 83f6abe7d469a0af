"""ProcessTask on a deterministic-checkpoint hit: validations still fire
(they are workflow declarations), but engine input conversion is skipped —
a cache hit must not pay ``to_df`` on every input."""

import pandas as pd
import pytest

from fugue_tpu.dataframe import PandasDataFrame
from fugue_tpu.utils.params import ParamDict
from fugue_tpu.workflow.tasks import ProcessTask, TaskContext


class _CountingEngine:
    def __init__(self):
        self.conf = ParamDict()
        self.to_df_calls = 0

    def to_df(self, df, schema=None):
        self.to_df_calls += 1
        if isinstance(df, PandasDataFrame):
            return df
        return PandasDataFrame(df)


class _HitCheckpoint:
    """Always-hit deterministic checkpoint stub."""

    def __init__(self, df):
        self._df = df
        self.loads = 0

    def try_load(self, path):
        self.loads += 1
        return self._df


def _processor(df: pd.DataFrame) -> pd.DataFrame:
    raise AssertionError("processor must not run on a checkpoint hit")


def test_checkpoint_hit_skips_to_df():
    cached = PandasDataFrame(pd.DataFrame({"a": [7]}), "a:long")
    task = ProcessTask(_processor, schema="a:long")
    task.checkpoint = _HitCheckpoint(cached)
    engine = _CountingEngine()
    ctx = TaskContext(engine, rpc_server=None, checkpoint_path=None)
    inp = PandasDataFrame(pd.DataFrame({"a": [1, 2]}), "a:long")
    res = task.execute(ctx, [inp])
    assert res is cached
    assert task.checkpoint.loads == 1
    assert engine.to_df_calls == 0, "cache hit paid input conversion"


def test_checkpoint_miss_still_runs_processor():
    class _MissCheckpoint:
        def try_load(self, path):
            return None

        def run(self, df, path):
            return df

    ran = []

    def proc(df: pd.DataFrame) -> pd.DataFrame:
        ran.append(len(df))
        return df

    task = ProcessTask(proc, schema="a:long")
    task.checkpoint = _MissCheckpoint()
    engine = _CountingEngine()
    ctx = TaskContext(engine, rpc_server=None, checkpoint_path=None)
    inp = PandasDataFrame(pd.DataFrame({"a": [1, 2]}), "a:long")
    res = task.execute(ctx, [inp])
    assert ran == [2]
    assert res.as_array() == [[1], [2]]
