"""The declared-conf-key registry in constants.py: completeness (every
FUGUE_CONF_* constant is declared; the defaults table is derived from the
registry), typed getters, and runtime extensibility for plugin keys."""

import pytest

import fugue_tpu.constants as c
from fugue_tpu.constants import (
    FUGUE_GLOBAL_CONF,
    conf_default,
    declared_conf_keys,
    register_conf_key,
    typed_conf_get,
)

pytestmark = pytest.mark.analysis


def test_every_conf_constant_is_declared():
    declared = declared_conf_keys()
    for name in dir(c):
        if name.startswith("FUGUE_CONF_"):
            key = getattr(c, name)
            assert key in declared, f"{name} = {key!r} is not registered"


def test_defaults_table_matches_registry():
    declared = declared_conf_keys()
    for key, info in declared.items():
        if info.in_defaults:
            assert key in FUGUE_GLOBAL_CONF
            assert FUGUE_GLOBAL_CONF[key] == info.default
        # defaults must satisfy their own declared type
        if info.in_defaults:
            assert isinstance(info.default, info.type) or (
                info.type is float and isinstance(info.default, int)
            ), key


def test_previously_missing_keys_now_have_defaults():
    # the keys the registry satellite backfilled into the defaults table,
    # with the exact values their call sites already used as fallbacks
    assert FUGUE_GLOBAL_CONF["fugue.workflow.checkpoint.path"] == ""
    assert FUGUE_GLOBAL_CONF["fugue.rpc.server"] == "native"
    assert FUGUE_GLOBAL_CONF["fugue.jax.default.partitions"] == 0
    assert FUGUE_GLOBAL_CONF["fugue.jax.compile.cache"] == ""
    # legacy no-op key: declared (lints clean) but NOT seeded
    assert "fugue.jax.compile" in declared_conf_keys()
    assert "fugue.jax.compile" not in FUGUE_GLOBAL_CONF


def test_module_owned_keys_declared_but_not_seeded():
    # keys consumed with local fallbacks by their owning modules (dist
    # init, HTTP RPC): the analyzer must recognize them (no FWF201 on a
    # legitimate multihost/HTTP config) but they stay out of the global
    # defaults table
    declared = declared_conf_keys()
    for key in (
        "fugue.jax.dist.coordinator",
        "fugue.jax.dist.num_processes",
        "fugue.jax.dist.process_id",
        "fugue.rpc.http_server.host",
        "fugue.rpc.http_server.port",
        "fugue.rpc.http_server.timeout",
    ):
        assert key in declared, key
        assert not declared[key].in_defaults, key
        assert key not in FUGUE_GLOBAL_CONF, key
    from fugue_tpu.workflow.workflow import FugueWorkflow

    dag = FugueWorkflow()
    dag.df([[0]], "a:int")
    diags = dag.analyze(conf={"fugue.rpc.http_server.host": "10.0.0.1"})
    assert not any(d.code == "FWF201" for d in diags)


def test_descriptions_and_types_present():
    for key, info in declared_conf_keys().items():
        assert key.startswith("fugue."), key
        assert info.description != "", key
        assert isinstance(info.type, type), key


def test_typed_getters():
    assert conf_default("fugue.workflow.retry.max_attempts") == 1
    assert typed_conf_get({}, "fugue.workflow.retry.backoff") == 0.1
    assert (
        typed_conf_get({"fugue.workflow.retry.backoff": "0.5"},
                       "fugue.workflow.retry.backoff")
        == 0.5
    )
    with pytest.raises(ValueError):
        typed_conf_get({"fugue.workflow.retry.backoff": "soon"},
                       "fugue.workflow.retry.backoff")
    with pytest.raises(KeyError):
        conf_default("fugue.not.a.key")


@pytest.mark.parametrize("knob", ["strategy", "autotune", "matmul"])
def test_removed_groupby_keys_are_unknown(knob):
    """The group-by kernel follows the shape alone: its three former keys
    are undeclared, so the analyzer reports each as unknown (FWF201) and
    an engine given one still aggregates."""
    import pandas as pd

    from fugue_tpu.collections.partition import PartitionSpec
    from fugue_tpu.column import col
    from fugue_tpu.column import functions as ff
    from fugue_tpu.jax_backend import JaxExecutionEngine
    from fugue_tpu.workflow.workflow import FugueWorkflow

    key = "fugue.jax.groupby." + knob
    assert key not in declared_conf_keys()
    dag = FugueWorkflow()
    dag.df([[0]], "a:int")
    diags = dag.analyze(conf={key: "scatter"})
    assert any(d.code == "FWF201" and key in d.message for d in diags)
    e = JaxExecutionEngine({key: "scatter"})
    out = e.aggregate(
        e.to_df(pd.DataFrame({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]})),
        PartitionSpec(by=["k"]), [ff.sum(col("v")).alias("s")],
    ).as_pandas().sort_values("k")
    assert out["s"].tolist() == [3.0, 3.0]
    assert e.fallbacks == {}


def test_plugin_keys_extend_the_live_registry():
    key = "fugue.testplugin.knob"
    try:
        register_conf_key(key, int, 7, "test-only plugin knob")
        assert declared_conf_keys()[key].default == 7
        # the analyzer recognizes it immediately
        from fugue_tpu.workflow.workflow import FugueWorkflow

        dag = FugueWorkflow()
        dag.df([[0]], "a:int")
        diags = dag.analyze(conf={key: 7})
        assert not any(d.code == "FWF201" for d in diags)
    finally:
        c._CONF_REGISTRY.pop(key, None)


def test_engine_conf_inherits_registered_defaults():
    from fugue_tpu.execution.native_execution_engine import NativeExecutionEngine

    e = NativeExecutionEngine()
    assert e.conf["fugue.analysis"] == "warn"
    assert e.conf["fugue.rpc.server"] == "native"
