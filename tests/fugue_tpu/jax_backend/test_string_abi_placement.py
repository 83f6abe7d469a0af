"""String columns in the compiled-map ABI (dictionary codes + host decode
tables) and the two-tier placement policy (verdict r3 items 1/2/6).

The ABI contract under test (execution_engine._compiled_map): a string
column enters a jax transformer as int32 codes (``arrs[name]``) plus a
STATIC host decode table (``arrs[f"_{name}_dict"]``); a string output
either passes codes through (inheriting the dictionary) or returns a
remapped ``_<name>_dict``.
"""

import tempfile
from typing import Dict

import jax
import numpy as np
import pandas as pd
import pytest

from fugue_tpu import transform
from fugue_tpu.constants import (
    FUGUE_CONF_JAX_MIN_DEVICE_BYTES,
    FUGUE_CONF_JAX_PLACEMENT,
)
from fugue_tpu.jax_backend import JaxExecutionEngine

MAPPING = {"A": "Apple", "B": "Banana", "C": "Carrot"}


def map_letter_to_food(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    d = arrs["_value_dict"]
    remapped = np.array(
        [MAPPING.get(s, s) for s in d.tolist()], dtype=object
    )
    return {
        "id": arrs["id"],
        "value": arrs["value"],
        "_value_dict": remapped,
    }


def _src(n: int = 100, nulls: bool = False) -> pd.DataFrame:
    rng = np.random.default_rng(0)
    vals = rng.choice(["A", "B", "C"], n).astype(object)
    if nulls:
        vals[::7] = None
    return pd.DataFrame({"id": np.arange(n), "value": vals})


def test_string_transform_stays_on_device():
    """Verdict r3 item 2 done-criterion: a string-column jax transformer
    runs with ``engine.fallbacks == {}``."""
    engine = JaxExecutionEngine()
    pdf = _src()
    out = transform(
        engine.to_df(pdf), map_letter_to_food, schema="*",
        engine=engine, as_fugue=True,
    )
    assert engine.fallbacks == {}, engine.fallbacks
    expect = pdf.assign(value=pdf["value"].map(MAPPING))
    pd.testing.assert_frame_equal(
        out.as_pandas().reset_index(drop=True), expect, check_dtype=False
    )


def test_string_transform_preserves_nulls():
    engine = JaxExecutionEngine()
    pdf = _src(nulls=True)
    out = transform(
        engine.to_df(pdf), map_letter_to_food, schema="*",
        engine=engine, as_fugue=True,
    ).as_pandas()
    assert engine.fallbacks == {}, engine.fallbacks
    expect = pdf["value"].map(MAPPING)
    assert out["value"].isna().tolist() == expect.isna().tolist()
    assert (out["value"].dropna() == expect.dropna()).all()


def test_string_passthrough_keeps_dictionary():
    engine = JaxExecutionEngine()
    pdf = _src()

    def double_id(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {"id": arrs["id"] * 2, "value": arrs["value"]}

    out = transform(
        engine.to_df(pdf), double_id, schema="*", engine=engine,
        as_fugue=True,
    ).as_pandas()
    assert engine.fallbacks == {}, engine.fallbacks
    assert (out["value"] == pdf["value"]).all()
    assert (out["id"] == pdf["id"] * 2).all()


def test_distinct_dictionaries_do_not_alias():
    """The map-program cache is keyed by dictionary identity: two frames
    with different decode tables through the SAME transformer must not
    reuse each other's stashed output dictionaries."""
    engine = JaxExecutionEngine()
    pad = 16

    def passthrough_remap(
        arrs: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        d = arrs["_value_dict"]
        return {
            "value": arrs["value"],
            "_value_dict": np.array(
                [s + "!" for s in d.tolist()], dtype=object
            ),
        }

    df1 = pd.DataFrame({"value": ["x", "y"] * pad})
    df2 = pd.DataFrame({"value": ["p", "q"] * pad})
    out1 = transform(
        engine.to_df(df1), passthrough_remap, schema="value:str",
        engine=engine, as_fugue=True,
    ).as_pandas()
    out2 = transform(
        engine.to_df(df2), passthrough_remap, schema="value:str",
        engine=engine, as_fugue=True,
    ).as_pandas()
    assert set(out1["value"]) == {"x!", "y!"}
    assert set(out2["value"]) == {"p!", "q!"}
    assert engine.fallbacks == {}, engine.fallbacks


def test_string_output_without_dictionary_falls_back():
    """A string-typed output computed from non-string inputs has no decode
    table -> the compiled path must decline (counted fallback), not emit
    garbage."""
    engine = JaxExecutionEngine()
    pdf = _src()

    def swap(arrs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        # 'value' output gets int codes derived from 'id': no dictionary
        return {"id": arrs["id"], "value": arrs["id"] % 3}

    with pytest.raises(Exception):
        transform(
            engine.to_df(pdf), swap, schema="*", engine=engine,
            as_fugue=True,
        ).as_pandas()
    assert "map" in engine.fallbacks  # declined BEFORE the host path raised


# ---- placement tier ------------------------------------------------------


def _tiered_engine(conf: Dict) -> JaxExecutionEngine:
    """Engine with a DISTINCT host mesh (a strict subset of the CPU
    devices) so tier routing is observable under the forced-CPU test
    env, where the host mesh normally coincides with the device mesh."""
    from fugue_tpu.jax_backend.blocks import make_mesh

    engine = JaxExecutionEngine(conf)
    assert not engine._mesh_pinned
    engine._host_mesh = make_mesh(list(jax.devices()[:4]))
    return engine


def test_auto_placement_routes_by_size():
    engine = _tiered_engine({FUGUE_CONF_JAX_MIN_DEVICE_BYTES: 1024})
    small = engine.to_df(pd.DataFrame({"v": np.arange(8, dtype=np.int64)}))
    big = engine.to_df(
        pd.DataFrame({"v": np.arange(1000, dtype=np.int64)})
    )
    assert small.blocks.mesh is engine.host_mesh
    assert big.blocks.mesh is engine.mesh
    # frames on either tier compute correctly
    assert small.as_pandas()["v"].sum() == 28
    assert big.as_pandas()["v"].sum() == 499500


def test_placement_pin_overrides_size():
    eng_dev = _tiered_engine(
        {FUGUE_CONF_JAX_MIN_DEVICE_BYTES: 1024,
         FUGUE_CONF_JAX_PLACEMENT: "device"}
    )
    assert (
        eng_dev.to_df(pd.DataFrame({"v": [1, 2]})).blocks.mesh
        is eng_dev.mesh
    )
    eng_host = _tiered_engine({FUGUE_CONF_JAX_PLACEMENT: "host"})
    assert (
        eng_host.to_df(
            pd.DataFrame({"v": np.arange(100000, dtype=np.int64)})
        ).blocks.mesh
        is eng_host.host_mesh
    )


def test_cross_mesh_join_aligns():
    """A join between host-tier and device-tier frames must align meshes
    and produce the exact relational result."""
    engine = _tiered_engine({FUGUE_CONF_JAX_MIN_DEVICE_BYTES: 4096})
    big = pd.DataFrame(
        {"k": np.arange(1000, dtype=np.int64) % 5,
         "v": np.arange(1000, dtype=np.float64)}
    )
    small = pd.DataFrame(
        {"k": np.array([0, 1, 2], dtype=np.int64),
         "w": np.array([10.0, 20.0, 30.0])}
    )
    j1, j2 = engine.to_df(big), engine.to_df(small)
    assert j1.blocks.mesh is engine.mesh
    assert j2.mesh is not engine.mesh  # pending on the host tier
    out = engine.join(j1, j2, how="inner", on=["k"]).as_pandas()
    expect = big.merge(small, on="k")
    assert len(out) == len(expect)
    assert out["v"].sum() == expect["v"].sum()
    assert out["w"].sum() == expect["w"].sum()


def test_groupby_matmul_and_scatter_paths_agree(force_strategy):
    """The matmul kernel (the accelerator path) and the scatter kernel
    (the CPU path) agree bit-for-bit on counts and to rounding on sums;
    the rule picks scatter on CPU meshes."""
    import pandas as pd

    from fugue_tpu.collections.partition import PartitionSpec
    from fugue_tpu.column import col as fcol
    from fugue_tpu.column import functions as ff
    from fugue_tpu.jax_backend import groupby

    rng = np.random.default_rng(5)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 16, 5000).astype(np.int32),
            "v": rng.random(5000).astype(np.float32),
        }
    )
    # the rule on a CPU mesh: scatter for the packed path and the counts
    platform = JaxExecutionEngine().mesh.devices.flat[0].platform
    assert groupby.choose_strategy(platform, 16, np.float32) == "scatter"
    assert groupby.choose_strategy(platform, 16, count_only=True) == "scatter"
    results = {}
    for strategy in ("matmul", "scatter"):
        force_strategy(strategy)
        e = JaxExecutionEngine()
        out = e.aggregate(
            e.to_df(pdf), PartitionSpec(by=["k"]),
            [ff.sum(fcol("v")).alias("s"), ff.count(fcol("k")).alias("c")],
        ).as_pandas().sort_values("k").reset_index(drop=True)
        assert e.fallbacks == {}, (strategy, e.fallbacks)
        assert e.strategy_counts == {strategy: 1}, e.strategy_counts
        results[strategy] = out
    a, b = results["matmul"], results["scatter"]
    assert a["k"].tolist() == b["k"].tolist()
    assert a["c"].tolist() == b["c"].tolist()
    assert np.allclose(a["s"], b["s"], rtol=1e-5)


def test_compile_cache_conf(monkeypatch):
    # the legacy key is a deprecation-logged ALIAS of the new disk tier
    # (fugue.optimize.cache.dir): it enables the SAME persistent
    # executable cache, and the new key wins when both are set — two
    # divergent caches never run side by side
    monkeypatch.delenv("FUGUE_JAX_COMPILE_CACHE", raising=False)
    from fugue_tpu.constants import (
        FUGUE_CONF_JAX_COMPILE_CACHE,
        FUGUE_CONF_OPTIMIZE_CACHE_DIR,
    )

    path = tempfile.mkdtemp(prefix="fugue_jax_cache_")
    e = JaxExecutionEngine({FUGUE_CONF_JAX_COMPILE_CACHE: path})
    assert e._exec_enabled
    assert e.exec_cache_stats["dir"] == path
    # precedence: the new key overrides the alias
    new_path = tempfile.mkdtemp(prefix="fugue_jax_cache_new_")
    e2 = JaxExecutionEngine(
        {
            FUGUE_CONF_JAX_COMPILE_CACHE: path,
            FUGUE_CONF_OPTIMIZE_CACHE_DIR: new_path,
        }
    )
    assert e2.exec_cache_stats["dir"] == new_path
    # neither key -> disk tier off
    e3 = JaxExecutionEngine()
    assert not e3._exec_enabled
    # the alias names WHERE executables are stored, not what they
    # compute: it must not split the plan signature (replicas spelling
    # the cache dir differently still share one namespace)
    from fugue_tpu.optimize.cache import engine_plan_signature

    assert engine_plan_signature(e) == engine_plan_signature(e3)
