"""The serve daemon: a ``ServeDaemon(engine="jax")`` in this process,
queried over HTTP through ``ServeClient.sql``, one client per stream,
all streams in one session (the configuration's ``system`` ``serve``).

Set-up writes each table to parquet in the in-process ``memory://``
filesystem (nothing reaches the disk) and loads it into the session with
``LOAD ... save_as=<table>``, as ``chip_smoke.py`` phase (c) does."""

import threading
from typing import Any, Dict

import pandas as pd
import pyarrow.parquet as pq

from benchmark.checks import check_engine

JOB_SECONDS = "fugue_serve_job_seconds"


class QueryFailed(RuntimeError):
    pass


class Driver:
    def __init__(self, config: Dict[str, Any], tables: Dict[str, Any], annotate: Any):
        from fugue_tpu.fs import make_default_registry
        from fugue_tpu.serve import ServeDaemon

        self._annotate = annotate
        self.daemon = ServeDaemon(dict(config["engine_conf"]), engine="jax").start()
        self._address = self.daemon.address
        self._local = threading.local()
        client = self._client()
        self.sid = client.create_session()
        fs = make_default_registry()
        for name, table in tables.items():
            uri = f"memory://fugue_bench/{name}.parquet"
            fs.write_file_atomic(uri, lambda f, t=table: pq.write_table(t, f))
            snap = client.sql(self.sid, f'LOAD "{uri}"', save_as=name, collect=False)
            fs.rm(uri)
            if snap["status"] != "done":
                raise QueryFailed(f"LOAD {name}: {snap.get('error')}")

    def _client(self) -> Any:
        c = getattr(self._local, "client", None)
        if c is None:
            from fugue_tpu.serve import ServeClient

            c = self._local.client = ServeClient(*self._address, timeout=600)
        return c

    def run(self, stream: int, text: str) -> pd.DataFrame:
        with self._annotate("bench.http"):
            snap = self._client().sql(self.sid, text)
        if snap["status"] != "done":
            raise QueryFailed(str(snap.get("error")))
        r = snap["result"]
        return pd.DataFrame(r["rows"], columns=r["columns"])

    def counters(self) -> Dict[str, Any]:
        engine = self.daemon.engine
        job = {"sum": 0.0, "count": 0}
        fam = engine.metrics.snapshot().get(JOB_SECONDS, {})
        for s in fam.get("samples", []):
            if s["labels"].get("status") == "done":
                job = {"sum": s["sum"], "count": s["count"]}
        cache = self.daemon.status().get("plan_cache", {}).get("serve_result", {})
        return {
            "fallbacks": dict(engine.fallbacks),
            "strategy_counts": dict(engine.strategy_counts),
            "job_seconds": job,
            "result_cache_hits": cache.get("hit", 0),
        }

    def check(self, platform: str) -> None:
        frames = list(self.daemon.sessions.get(self.sid).table_frames().values())
        check_engine(self.daemon.engine, frames, platform)

    def close(self) -> None:
        try:
            self._client().close_session(self.sid)
        finally:
            self.daemon.stop()
