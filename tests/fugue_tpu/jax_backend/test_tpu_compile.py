"""Compile the main path's XLA programs for a described (not attached) TPU
v5e chip. Nothing runs: these guard what the chip's compiler would refuse
(lowering, partitioning, memory) at no chip time.

The v5e:2x2 topology is described inside a module fixture, never at import,
and only the worker that runs this file loads the TPU compiler. The
persistent compilation cache is off around these compiles: entries written
for a described chip cannot be read back without one.

Sort-bearing programs compile in seconds only below ~8K rows on this
compiler (64K rows already take 20-70 s), so they run at 8,192 rows; the
one-hot matmul and scatter programs run at the headline's 16M rows."""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from fugue_tpu.jax_backend import groupby, shuffle

ROWS = 1 << 24
SORT_ROWS = 1 << 13
SEGMENTS = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), axis_names=("p",))
    return mesh, NamedSharding(mesh, P("p"))


def _shape(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("strategy", groupby.STRATEGIES)
def test_segment_sums_compiles(one_chip, strategy):
    # every kernel in the engine's f64
    n = SORT_ROWS if strategy == "sort" else ROWS
    dtype = jnp.float64

    def prog(v, seg, valid):
        f, c, _ = groupby.segment_sums([v], [valid], seg, SEGMENTS, strategy)
        return f[0], c[0]

    compiled = _compile(
        prog,
        _shape(n, dtype, one_chip),
        _shape(n, jnp.int32, one_chip),
        _shape(n, jnp.bool_, one_chip),
    )
    assert compiled.memory_analysis() is not None


def test_headline_transform_and_segment_reduce_compiles(one_chip):
    # chip_smoke phase (a): v2 = v * 2 + 1 over int64 keys binned into
    # 1,024 segments, then sum/count/mean on the one-hot matmul
    spec = groupby.BinSpec(("k",), (0,), (SEGMENTS,), (False,), SEGMENTS)

    def prog(k, v, nrows):
        valid = groupby.materialize_validity(None, ROWS, nrows)
        v2 = v * 2.0 + 1.0
        seg = groupby.inline_seg(spec, {"k": k}, {"k": None}, valid)
        f, c, _ = groupby.segment_sums([v2], [valid], seg, SEGMENTS, "matmul")
        return f[0], c[0], f[0] / c[0]

    compiled = _compile(
        prog,
        _shape(ROWS, jnp.int64, one_chip),
        _shape(ROWS, jnp.float64, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    )
    assert compiled.memory_analysis() is not None


def test_shuffle_rows_compiles_on_four_chips(four_chips):
    mesh, rows = four_chips

    def prog(seg, valid, v):
        return shuffle.shuffle_rows(mesh, seg, valid, {"v": v})

    compiled = _compile(
        prog,
        _shape(SORT_ROWS, jnp.int32, rows),
        _shape(SORT_ROWS, jnp.bool_, rows),
        _shape(SORT_ROWS, jnp.float64, rows),
    )
    assert "all-to-all" in compiled.as_text()


def test_preagg_segment_aggs_compiles_on_four_chips(four_chips):
    # chip_smoke --chips 4, phase (b): the GROUP BY's sum/count/avg/min/max
    # as a map-side combine, partial tables crossing one all-to-all
    mesh, rows = four_chips
    funcs = ["sum", "count", "avg", "min", "max"]

    def prog(seg, valid, v):
        vals = [None if f == "count" else v for f in funcs]
        return shuffle.preagg_segment_aggs(
            mesh, funcs, seg, valid, vals, [None] * len(funcs), 100
        )

    compiled = _compile(
        prog,
        _shape(ROWS, jnp.int32, rows),
        _shape(ROWS, jnp.bool_, rows),
        _shape(ROWS, jnp.float64, rows),
    )
    assert "all-to-all" in compiled.as_text()


def test_masked_segment_sums_compiles_on_four_chips(four_chips):
    # TPC-H Q1's reduction on a row-sharded frame: 8 f64 payloads and a
    # count over 12 bins, each chip's partial sums added across chips
    mesh, rows = four_chips

    def prog(seg, valid, *payloads):
        f, c, _ = groupby.segment_sums(list(payloads), [valid], seg, 12, "masked")
        return f, c

    compiled = _compile(
        prog,
        _shape(ROWS, jnp.int32, rows),
        _shape(ROWS, jnp.bool_, rows),
        *[_shape(ROWS, jnp.float64, rows)] * 8,
    )
    assert "all-reduce" in compiled.as_text()


def test_sharded_cumsum_compiles_on_four_chips(four_chips):
    mesh, rows = four_chips
    compiled = _compile(
        lambda x: shuffle.sharded_cumsum(mesh, x),
        _shape(SORT_ROWS, jnp.int32, rows),
    )
    # the chunk totals' all-gather lowers to an all-reduce on this chip
    assert re.search(r"all-(gather|reduce)", compiled.as_text())


def test_f64_sort_factorize_compiles(one_chip):
    # chip_smoke phase (d): -0.0/0.0/NaN float keys through the sort
    # factorization that distinct and group-by share
    def prog(v, nrows):
        codes = tuple(groupby.float_sort_codes(v))
        return groupby._sort_factorize_core(codes, None, nrows)

    compiled = _compile(
        prog,
        _shape(SORT_ROWS, jnp.float64, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    )
    assert compiled.memory_analysis() is not None
