"""Programs hand their row-axis outputs to columns already in the mesh's
row sharding: TPC-H Q1 (GROUP BY two string keys, 8 aggregates, ORDER
BY) matches the native engine on one device and on the 8-device test
mesh without resharding a single array through the host, and
``place_rows`` slices replicated outputs on the devices and counts only
what has to go through the host."""

import os
import sys

import jax
import jax._src.array as jax_array
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from fugue_tpu import ArrowDataFrame, fugue_sql, make_execution_engine
from fugue_tpu.jax_backend import blocks as B
from fugue_tpu.obs.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

RESHARDS = "fugue_engine_host_reshards_total"


def _reshards(engine) -> float:
    return engine.metrics.get(RESHARDS).labels().value


@pytest.fixture
def slow_path_calls(monkeypatch):
    """Counts entries into JAX's resharding slow path: a blocking fetch
    of the array, then one put per device."""
    calls = []
    real = jax_array.shard_sharded_device_array_slow_path

    def counted(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(jax_array, "shard_sharded_device_array_slow_path", counted)
    return calls


@pytest.mark.parametrize("devices", ["0", "0,1,2,3,4,5,6,7"])
def test_q1_results_stay_row_sharded_without_host_reshards(devices, slow_path_calls):
    from benchmark.gen import lineitem
    from benchmark.queries import q1

    table = lineitem.generate(24_000, 0.004, 5)
    with open(os.path.join(REPO, "benchmark", "queries", "q1.sql")) as f:
        text = f.read().format(**q1.literals({"DELTA": 90}))
    engine = make_execution_engine(
        "jax", {"fugue.jax.placement": "device", "fugue.jax.devices": devices}
    )
    df = engine.persist(engine.to_df(ArrowDataFrame(table)))
    mesh = df.native.mesh
    assert int(mesh.devices.size) == len(devices.split(","))
    before = _reshards(engine)
    slow_path_calls.clear()
    for _ in range(2):
        res = fugue_sql(text, lineitem=df, engine=engine, as_fugue=True)
        rows = res.native.columns
        for col in rows.values():
            assert col.data.sharding.is_equivalent_to(B.row_sharding(mesh), 1)
        got = res.as_pandas()
    assert slow_path_calls == []
    assert _reshards(engine) == before
    assert engine.fallbacks == {}
    want = fugue_sql(text, lineitem=table.to_pandas(), engine="native", as_fugue=True).as_pandas()
    assert list(got.columns) == list(want.columns)
    keys = ["l_returnflag", "l_linestatus", "count_order"]
    assert got[keys].values.tolist() == want[keys].values.tolist()
    floats = [c for c in want.columns if c not in keys]
    np.testing.assert_allclose(
        got[floats].to_numpy(np.float64), want[floats].to_numpy(np.float64), rtol=1e-9, atol=0
    )
    engine.stop()


def test_place_rows_slices_on_the_devices_and_counts_only_host_reshards(slow_path_calls):
    mesh = B.make_mesh(jax.devices()[:8])
    rows = B.row_sharding(mesh)
    counter = MetricsRegistry().counter(RESHARDS, "test").labels()
    replicated = jax.device_put(np.arange(16.0), NamedSharding(mesh, P()))
    mask = jax.device_put(np.arange(16) % 3 == 0, NamedSharding(mesh, P()))
    placed, placed_mask, none = B.place_rows((replicated, mask, None), mesh, counter)
    assert none is None
    for got, want in ((placed, np.arange(16.0)), (placed_mask, np.arange(16) % 3 == 0)):
        assert got.sharding.is_equivalent_to(rows, 1)
        np.testing.assert_array_equal(np.asarray(got), want)
    assert slow_path_calls == [] and counter.value == 0
    tree = {"a": placed, "n": jax.numpy.int32(3)}
    assert B.place_rows(tree, mesh, counter) is tree
    # an array on other devices than the mesh's can only go through the host
    elsewhere = jax.device_put(np.arange(16.0), NamedSharding(B.make_mesh(jax.devices()[:2]), P()))
    moved = B.place_rows(elsewhere, mesh, counter)
    assert moved.sharding.is_equivalent_to(rows, 1)
    np.testing.assert_array_equal(np.asarray(moved), np.arange(16.0))
    assert counter.value == 1 and len(slow_path_calls) == 1
