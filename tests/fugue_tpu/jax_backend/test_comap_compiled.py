"""Compiled comap: a jax-annotated cotransformer runs as ONE whole-shard
jitted program over the shared segment space (comap_compiled.py) — no
per-group host loop, no fallbacks — and matches the host group loop's
semantics for every zip type. Role to beat: the reference's
serialize-comap cliff (fugue/execution/execution_engine.py:1066-1118)."""

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from fugue_tpu.collections.partition import PartitionSpec
from fugue_tpu.dataframe import DataFrames
from fugue_tpu.execution.native_execution_engine import NativeExecutionEngine
from fugue_tpu.jax_backend import JaxExecutionEngine
from fugue_tpu.workflow import FugueWorkflow

I32MIN = -(2**31)


def make_engine(**conf: Any) -> JaxExecutionEngine:
    return JaxExecutionEngine(dict(test=True, **conf))


def seg_sum(d: Dict[str, jax.Array], col: str) -> jax.Array:
    return jax.ops.segment_sum(
        jnp.where(d["_row_valid"], d[col], 0),
        d["_segment_ids"],
        num_segments=d["_num_segments"],
    )


def seg_count(d: Dict[str, jax.Array]) -> jax.Array:
    return jax.ops.segment_sum(
        d["_row_valid"].astype(jnp.int32),
        d["_segment_ids"],
        num_segments=d["_num_segments"],
    )


def seg_key(d: Dict[str, jax.Array], col: str) -> jax.Array:
    return jax.ops.segment_max(
        jnp.where(d["_row_valid"], d[col].astype(jnp.int32), I32MIN),
        d["_segment_ids"],
        num_segments=d["_num_segments"],
    )


def cm_sums(
    a: Dict[str, jax.Array], b: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    # per-key: k, SUM(a.v) + SUM(b.w) — the bench config-4 computation
    return {
        "k": seg_key(a, "k"),
        "s": seg_sum(a, "v") + seg_sum(b, "w"),
    }


def cm_counts(
    a: Dict[str, jax.Array], b: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    # key present in EITHER member (outer zips): max over both sides
    return {
        "k": jnp.maximum(seg_key(a, "k"), seg_key(b, "k")),
        "na": seg_count(a),
        "nb": seg_count(b),
    }


def cm_rows(
    a: Dict[str, jax.Array], b: Dict[str, jax.Array]
) -> Dict[str, jax.Array]:
    # row-aligned with member a: each row plus its key's total b-weight
    S = a["_num_segments"]
    sw = seg_sum(b, "w")
    return {
        "k": a["k"],
        "d": a["v"] + sw[jnp.clip(a["_segment_ids"], 0, S - 1)],
    }


def _run_both(cm: Any, schema: str, a: pd.DataFrame, b: pd.DataFrame,
              how: str = "inner") -> Any:
    """The user-level dag zip+transform on both engines; assert the jax
    engine never fell back and both agree."""
    outs = []
    je = make_engine()
    for eng in (je, NativeExecutionEngine()):
        dag = FugueWorkflow()
        za = dag.df(a, "k:long,v:double")
        zb = dag.df(b, "k:long,w:double")
        z = za.partition_by("k").zip(zb, how=how)
        res = z.transform(cm, schema=schema)
        res.yield_dataframe_as("out", as_local=True)
        dag.run(eng)
        rows = [
            tuple(None if v is None else round(float(v), 6) for v in r)
            for r in dag.yields["out"].result.as_array()
        ]
        outs.append(sorted(rows))
    assert je.fallbacks == {}, je.fallbacks
    assert outs[0] == outs[1], (how, outs)
    return outs[0]


def test_segment_output_inner():
    rng = np.random.default_rng(7)
    a = pd.DataFrame(
        {"k": rng.integers(0, 50, 400), "v": rng.random(400)}
    )
    b = pd.DataFrame({"k": np.arange(60), "w": rng.random(60)})
    rows = _run_both(cm_sums, "k:long,s:double", a, b)
    # oracle: straight pandas
    sa = a.groupby("k").v.sum()
    sb = b.groupby("k").w.sum()
    want = sorted(
        (float(k), round(float(sa[k] + sb[k]), 6)) for k in sa.index
    )
    got = sorted((float(r[0]), r[1]) for r in rows)
    assert got == want


@pytest.mark.parametrize(
    "how", ["inner", "left_outer", "right_outer", "full_outer"]
)
def test_presence_rules_match_host(how: str) -> None:
    a = pd.DataFrame({"k": [1, 1, 2, 5], "v": [1.0, 2.0, 3.0, 4.0]})
    b = pd.DataFrame({"k": [2, 3, 3], "w": [10.0, 20.0, 30.0]})
    rows = _run_both(cm_counts, "k:long,na:long,nb:long", a, b, how=how)
    keys = sorted(r[0] for r in rows)
    expect = {
        "inner": [2.0],
        "left_outer": [1.0, 2.0, 5.0],
        "right_outer": [2.0, 3.0],
        "full_outer": [1.0, 2.0, 3.0, 5.0],
    }[how]
    assert keys == expect, (how, rows)


def test_row_aligned_output():
    rng = np.random.default_rng(8)
    a = pd.DataFrame({"k": rng.integers(0, 8, 100), "v": rng.random(100)})
    b = pd.DataFrame({"k": np.arange(8), "w": rng.random(8)})
    rows = _run_both(cm_rows, "k:long,d:double", a, b)
    assert len(rows) == 100
    wmap = dict(zip(b.k, b.w))
    want = sorted(
        (float(k), round(float(v + wmap[k]), 6)) for k, v in zip(a.k, a.v)
    )
    assert sorted((float(r[0]), r[1]) for r in rows) == want


def test_empty_intersection_yields_empty():
    a = pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0]})
    b = pd.DataFrame({"k": [3, 4], "w": [1.0, 2.0]})
    rows = _run_both(cm_sums, "k:long,s:double", a, b)
    assert rows == []


def test_engine_comap_uses_compiled_path():
    # the engine-level path: the runner-wrapped jax cotransformer must hit
    # compiled_comap (no host loop, zero fallbacks), and downstream device
    # ops keep working on its output
    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    a = e.to_df([[1, 1.0], [1, 2.0], [2, 5.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0], [2, 20.0]], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_sums, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"  # set by RunTransformer normally
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    res = e.comap(
        z, runner.run, "k:long,s:double", PartitionSpec(by=["k"])
    )
    from fugue_tpu.jax_backend.dataframe import JaxDataFrame

    assert isinstance(res, JaxDataFrame)
    assert e.fallbacks == {}, e.fallbacks
    rows = sorted(map(tuple, res.as_array()))
    assert rows == [(1, 13.0), (2, 25.0)], rows


def test_presort_falls_back_to_host_loop():
    # presort means per-group row order matters: the compiled whole-shard
    # program can't honor it, so the host loop runs (counted fallback)
    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    a = e.to_df([[1, 2.0], [1, 1.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0]], "k:long,w:double")
    z = e.zip(
        DataFrames(a, b),
        partition_spec=PartitionSpec(by=["k"], presort="v asc"),
    )
    tf = _to_transformer(cm_sums, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    res = e.comap(z, runner.run, "k:long,s:double", PartitionSpec(by=["k"]))
    assert sorted(map(tuple, res.as_array())) == [(1, 13.0)]
    assert e.fallbacks.get("comap", 0) == 1, e.fallbacks


def test_ambiguous_length_falls_back_to_host_loop():
    # S == member-0 padded length: output length can't distinguish
    # per-segment from row-aligned results, so the host loop (always
    # correct: the ABI runs per group there) must run, counted. Repro
    # shape from review: 96 rows, distinct keys 0..95, key 95 shuffled
    # to position 0 — a wrong interpretation emits/drops the wrong keys.
    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    n = 96
    ks = list(range(n))
    ks[0], ks[95] = ks[95], ks[0]
    a = e.to_df([[k, float(k)] for k in ks], "k:long,v:double")
    b = e.to_df([[k, 1.0] for k in range(95)], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_rows, schema="k:long,d:double")
    tf._output_schema = "k:long,d:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    res = e.comap(z, runner.run, "k:long,d:double", PartitionSpec(by=["k"]))
    rows = sorted(map(tuple, res.as_array()))
    # inner zip drops key 95 (absent from b); every kept row gains w=1
    assert len(rows) == 95
    assert (0, 1.0) in rows and not any(r[0] == 95 for r in rows), rows[:3]
    assert e.fallbacks.get("comap", 0) == 1, e.fallbacks


def test_ignore_errors_counts_fallback():
    # per-group error swallowing can't run whole-shard: host loop, counted
    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    a = e.to_df([[1, 1.0], [2, 5.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0], [2, 20.0]], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_sums, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [ValueError])
    res = e.comap(z, runner.run, "k:long,s:double", PartitionSpec(by=["k"]))
    assert sorted(map(tuple, res.as_array())) == [(1, 11.0), (2, 25.0)]
    assert e.fallbacks.get("comap", 0) == 1, e.fallbacks


def test_untraceable_cotransformer_falls_back_to_host_loop():
    # valid in the host's one-segment mode but not jit-traceable
    # (data-dependent float()): host group loop, counted fallback
    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    def cm_concrete(
        a: Dict[str, jax.Array], b: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        total = float(jnp.sum(jnp.where(a["_row_valid"], a["v"], 0.0)))
        total += float(jnp.sum(jnp.where(b["_row_valid"], b["w"], 0.0)))
        k = int(jnp.max(jnp.where(a["_row_valid"], a["k"], 0)))
        return {"k": jnp.array([k]), "s": jnp.array([total])}

    e = make_engine()
    a = e.to_df([[1, 1.0], [1, 2.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0]], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_concrete, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    res = e.comap(z, runner.run, "k:long,s:double", PartitionSpec(by=["k"]))
    assert sorted(map(tuple, res.as_array())) == [(1, 13.0)]
    assert e.fallbacks.get("comap", 0) == 1, e.fallbacks


def test_over_reporting_nrows_is_rejected():
    # A cotransformer claiming more rows than its output
    # columns hold would turn garbage padding rows into real rows — the
    # compiled path must validate like the host group loop does
    def cm_over(
        a: Dict[str, jax.Array], b: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        k = seg_key(a, "k")
        s = seg_sum(a, "v") + seg_sum(b, "w")
        return {"k": k, "s": s, "_nrows": jnp.int32(k.shape[0] + 3)}

    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    a = e.to_df([[1, 1.0], [1, 2.0], [2, 5.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0], [2, 20.0]], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_over, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    with pytest.raises(Exception, match="_nrows"):
        e.comap(z, runner.run, "k:long,s:double", PartitionSpec(by=["k"]))


def test_explicit_nrows_at_bound_is_accepted():
    # _nrows == output length is the valid boundary (all rows real)
    def cm_exact(
        a: Dict[str, jax.Array], b: Dict[str, jax.Array]
    ) -> Dict[str, jax.Array]:
        k = seg_key(a, "k")
        s = seg_sum(a, "v") + seg_sum(b, "w")
        return {"k": k, "s": s, "_nrows": jnp.int32(k.shape[0])}

    from fugue_tpu.extensions.builtins import _CoTransformerRunner
    from fugue_tpu.extensions.convert import _to_transformer

    e = make_engine()
    a = e.to_df([[1, 1.0], [1, 2.0], [2, 5.0]], "k:long,v:double")
    b = e.to_df([[1, 10.0], [2, 20.0]], "k:long,w:double")
    z = e.zip(DataFrames(a, b), partition_spec=PartitionSpec(by=["k"]))
    tf = _to_transformer(cm_exact, schema="k:long,s:double")
    tf._output_schema = "k:long,s:double"
    tf._partition_spec = PartitionSpec(by=["k"])
    runner = _CoTransformerRunner(z, tf, [])
    res = e.comap(z, runner.run, "k:long,s:double", PartitionSpec(by=["k"]))
    assert len(res.as_array()) == 2
    assert e.fallbacks == {}, e.fallbacks
