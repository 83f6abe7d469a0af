"""The embedded engine: ``fugue_tpu.fugue_sql`` on persisted
``JaxDataFrame``s in this process (the configuration's ``system``
``embedded``)."""

from typing import Any, Dict, List

import pandas as pd

from benchmark.checks import check_engine


class Driver:
    def __init__(self, config: Dict[str, Any], tables: Dict[str, Any], annotate: Any):
        from fugue_tpu import ArrowDataFrame, make_execution_engine

        self._annotate = annotate
        self.engine = make_execution_engine("jax", dict(config["engine_conf"]))
        self.frames: Dict[str, Any] = {}
        for name, table in tables.items():
            df = self.engine.persist(self.engine.to_df(ArrowDataFrame(table)))
            for col in df.native.columns.values():
                if col.on_device:
                    col.data.block_until_ready()
            self.frames[name] = df
        self._results: List[Any] = []

    def run(self, stream: int, text: str) -> pd.DataFrame:
        from fugue_tpu import fugue_sql

        with self._annotate("bench.sql"):
            res = fugue_sql(text, engine=self.engine, as_fugue=True, **self.frames)
        with self._annotate("bench.fetch"):
            out = res.as_pandas()
        if len(self._results) < 4:  # a few result frames for the placement check
            self._results.append(res)
        return out

    def counters(self) -> Dict[str, Any]:
        return {
            "fallbacks": dict(self.engine.fallbacks),
            "strategy_counts": dict(self.engine.strategy_counts),
        }

    def check(self, platform: str) -> None:
        check_engine(self.engine, list(self.frames.values()) + self._results, platform)

    def close(self) -> None:
        self._results.clear()
        self.frames.clear()
        self.engine.stop()
