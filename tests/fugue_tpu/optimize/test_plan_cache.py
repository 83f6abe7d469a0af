"""Process-wide plan & result cache tests: LRU bounds, cross-engine
compiled-program sharing, the opt-in deterministic-checkpoint result
tier, and the serving daemon's cross-request query cache (epoch-keyed
invalidation, /v1/status counters)."""

import os
import tempfile

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.column.expressions import col
from fugue_tpu.execution import make_execution_engine
from fugue_tpu.optimize import PlanCache, get_plan_cache
from fugue_tpu.workflow.workflow import FugueWorkflow

pytestmark = pytest.mark.optimize


# ---- PlanCache unit ---------------------------------------------------------
def test_program_lru_bound():
    c = PlanCache(max_programs=2)
    c.put_program("a", 1)
    c.put_program("b", 2)
    assert c.get_program("a") == 1  # refreshes a
    c.put_program("c", 3)  # evicts b (LRU)
    assert c.get_program("b") is None
    assert c.get_program("a") == 1 and c.get_program("c") == 3
    assert c.evictions == 1


def test_result_bounds_entries_and_bytes():
    c = PlanCache(max_entries=8, max_result_bytes=100)
    assert c.put_result("x", "vx", 60)
    assert c.put_result("y", "vy", 60)  # over 100 bytes: x evicts
    assert c.get_result("x") is None
    assert c.get_result("y") == "vy"
    # an entry alone over the cap is refused, not destructive
    assert not c.put_result("huge", "v", 1000)
    assert c.get_result("y") == "vy"
    # byte_cap tightens further (the HBM-ledger clamp path)
    assert not c.put_result("z", "vz", 60, byte_cap=50)


def test_result_invalidate_tag():
    c = PlanCache()
    c.put_result(("s", 1), "a", 10, tag="sess1")
    c.put_result(("s", 2), "b", 10, tag="sess2")
    assert c.invalidate_tag("sess1") == 1
    assert c.get_result(("s", 1)) is None
    assert c.get_result(("s", 2)) == "b"


# ---- cross-engine program sharing ------------------------------------------
def test_fresh_same_conf_engine_reuses_compiled_programs():
    conf = {"fugue.optimize": "off"}  # sharing is unconditional

    def run(engine):
        dag = FugueWorkflow()
        df = dag.df([[i, float(i)] for i in range(64)], "a:int,b:double")
        df.filter(col("a") > 5).yield_dataframe_as("o", as_local=True)
        return dag.run(engine)["o"].as_array()

    e1 = make_execution_engine("jax", conf)
    r1 = run(e1)
    e2 = make_execution_engine("jax", conf)
    r2 = run(e2)
    assert r1 == r2
    stats = e2.plan_cache_stats
    assert stats["hits"] >= 1 and stats["misses"] == 0


def test_different_jax_conf_never_shares_a_slot():
    from fugue_tpu.optimize.cache import engine_plan_signature

    e1 = make_execution_engine("jax", {})
    e2 = make_execution_engine(
        "jax", {"fugue.jax.shuffle": "off"}
    )
    assert engine_plan_signature(e1) != engine_plan_signature(e2)


# ---- deterministic-checkpoint result tier -----------------------------------
def test_task_result_cache_serves_memory_tier_and_reverifies_artifact():
    ckpt = "memory://plan_cache_ckpt"
    conf = {
        "fugue.workflow.checkpoint.path": ckpt,
        "fugue.optimize.result_cache": True,
    }

    def build():
        dag = FugueWorkflow()
        df = dag.df([[i, float(i)] for i in range(32)], "a:int,b:double")
        out = df.filter(col("a") >= 16)
        out.deterministic_checkpoint()
        out.yield_dataframe_as("o", as_local=True)
        return dag

    engine = make_execution_engine("jax", conf)
    cache = get_plan_cache()
    r1 = build().run(engine)["o"].as_array()
    base = cache.stats()["result_hits"]
    r2 = build().run(engine)["o"].as_array()
    assert r2 == r1
    assert cache.stats()["result_hits"] > base
    # deleting the artifact invalidates the memory tier (existence is
    # re-verified on every hit) and the task recomputes
    ckpt_task = next(t for t in build().tasks if not t.checkpoint.is_null)
    artifact = f"{ckpt}/{ckpt_task.__uuid__()}.parquet"
    assert engine.fs.exists(artifact)
    engine.fs.rm(artifact, recursive=True)
    r3 = build().run(engine)["o"].as_array()
    assert r3 == r1


def test_task_result_cache_off_by_default():
    ckpt = "memory://plan_cache_ckpt_off"
    conf = {"fugue.workflow.checkpoint.path": ckpt}

    def build():
        dag = FugueWorkflow()
        df = dag.df([[1], [2]], "a:int")
        df.deterministic_checkpoint()
        df.yield_dataframe_as("o", as_local=True)
        return dag

    engine = make_execution_engine("jax", conf)
    cache = get_plan_cache()
    build().run(engine)
    before = cache.stats()["results"]
    build().run(engine)
    assert cache.stats()["results"] == before  # nothing stored


# ---- serving daemon cross-request cache -------------------------------------
@pytest.mark.serve
def test_serve_repeated_query_hits_result_cache():
    from fugue_tpu.serve import ServeClient, ServeDaemon

    rng = np.random.default_rng(5)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 8, 5000).astype(np.int64),
            "v": rng.random(5000),
        }
    )
    agg = "SELECT k, SUM(v) AS s FROM t GROUP BY k"
    with ServeDaemon({"fugue.serve.max_concurrent": 2}) as daemon:
        host, port = daemon.address
        c = ServeClient(host, port, timeout=600)
        sid = c.create_session()
        daemon.sessions.get(sid).save_table("t", daemon.engine.to_df(pdf))
        r1 = c.sql(sid, agg)
        assert r1["status"] == "done"
        hits0 = daemon.status()["plan_cache"]["serve_result"].get("hit", 0)
        r2 = c.sql(sid, agg)
        assert r2["status"] == "done"
        st = daemon.status()
        assert st["plan_cache"]["serve_result"].get("hit", 0) > hits0
        assert sorted(r2["result"]["rows"]) == sorted(r1["result"]["rows"])
        # /v1/status compile_cache now reads the EXACT plan-cache
        # counters (a served-from-cache resubmission adds no misses)
        assert set(st["compile_cache"]) == {"hits", "misses"}

        # a table update bumps the session epoch: the stale payload can
        # never be served again
        pdf2 = pdf.assign(v=pdf["v"] * 2.0)
        daemon.sessions.get(sid).save_table("t", daemon.engine.to_df(pdf2))
        r3 = c.sql(sid, agg)
        assert r3["status"] == "done"
        assert sorted(r3["result"]["rows"]) != sorted(r1["result"]["rows"])
        c.close_session(sid)


@pytest.mark.serve
def test_serve_cache_skips_impure_and_save_as_queries():
    from fugue_tpu.serve import ServeClient, ServeDaemon

    with ServeDaemon({"fugue.serve.max_concurrent": 2}) as daemon:
        host, port = daemon.address
        c = ServeClient(host, port, timeout=600)
        sid = c.create_session()
        # save_as has a side effect: both submissions must execute
        create = "CREATE [[1],[2]] SCHEMA a:long"
        assert c.sql(sid, create, save_as="t")["status"] == "done"
        e1 = daemon.sessions.get(sid).cache_epoch
        assert c.sql(sid, create, save_as="t")["status"] == "done"
        assert daemon.sessions.get(sid).cache_epoch > e1
        c.close_session(sid)


@pytest.mark.serve
def test_serve_cache_disable_conf():
    from fugue_tpu.serve import ServeClient, ServeDaemon

    with ServeDaemon(
        {"fugue.serve.max_concurrent": 1, "fugue.serve.result_cache": False}
    ) as daemon:
        host, port = daemon.address
        c = ServeClient(host, port, timeout=600)
        sid = c.create_session()
        assert (
            c.sql(sid, "CREATE [[1]] SCHEMA a:long", save_as="t")["status"]
            == "done"
        )
        base = daemon.status()["plan_cache"]["serve_result"]
        c.sql(sid, "SELECT COUNT(*) AS c FROM t")
        c.sql(sid, "SELECT COUNT(*) AS c FROM t")
        after = daemon.status()["plan_cache"]["serve_result"]
        assert after.get("hit", 0) == base.get("hit", 0)
        c.close_session(sid)
