"""The four-chip SF30 cell's own files on the CPU: ``gen/lineitem_chunked.py``
gives ``gen/lineitem.py``'s values with every string column in chunks
under the limit, ``tpch_sf30_4chip.q1`` runs through ``run_cell`` on four
virtual devices at a few thousand rows, and ``benchmark/collectives.py``
takes the union of collective operations per plane and their mean over
the planes."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

from bench_tiny import REPO, assert_unchanged, run_tiny, snapshot, tiny_root


def _gens(root=REPO):
    from benchmark import run

    return run.plugin("gen", "lineitem", root), run.plugin("gen", "lineitem_chunked", root)


def test_same_values_as_lineitem():
    plain, chunked = _gens()
    a = plain.generate(30_000, 0.005, 11)
    b = chunked.generate(30_000, 0.005, 11)
    assert b.schema == a.schema
    for name in a.column_names:
        assert b.column(name).num_chunks == a.column(name).num_chunks == 1
        assert b.column(name).chunk(0).equals(a.column(name).chunk(0)), name


def test_string_columns_come_in_chunks_under_the_limit(monkeypatch):
    plain, chunked = _gens()
    monkeypatch.setattr(chunked.BASE, "CHUNK_BYTES", 50_000)
    a = plain.generate(30_000, 0.005, 12)
    b = chunked.generate(30_000, 0.005, 12)
    b.validate(full=True)
    for name in ("l_shipinstruct", "l_shipmode", "l_comment"):
        col = b.column(name)
        assert col.num_chunks > 1, name
        for chunk in col.chunks:
            assert chunk.buffers()[2].size < 50_000
        assert col.to_pylist() == a.column(name).to_pylist(), name
    # one byte a row: 30,000 bytes, one chunk
    assert b.column("l_returnflag").num_chunks == 1
    assert plain.CHUNK_BYTES == 2**31  # the harness's own module is untouched


def test_large_columns_come_in_row_blocks(monkeypatch):
    plain, chunked = _gens()
    monkeypatch.setattr(chunked, "BLOCK_ROWS", 7_000)
    a = plain.generate(30_000, 0.005, 13)
    b = chunked.generate(30_000, 0.005, 13)
    b.validate(full=True)
    for name in ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode"):
        col = b.column(name)
        assert col.num_chunks == 5 and max(len(c) for c in col.chunks) == 7_000, name
        assert col.to_pylist() == a.column(name).to_pylist(), name
    assert b.column("l_comment").equals(a.column("l_comment"))


def test_query_and_reference_are_q1s():
    from benchmark import run

    q1, q1c = run.plugin("queries", "q1"), run.plugin("queries", "q1_chunked")
    assert q1c.TABLE == "lineitem_chunked" and q1.TABLE == "lineitem"
    assert q1c.READS == q1.READS and q1c.MAX_REL_ERR == q1.MAX_REL_ERR
    rng_a, rng_b = np.random.default_rng(4), np.random.default_rng(4)
    assert q1c.draw(rng_a) == q1.draw(rng_b)
    with open(os.path.join(REPO, "benchmark", "queries", "q1.sql")) as f:
        plain = f.read()
    with open(os.path.join(REPO, "benchmark", "queries", "q1_chunked.sql")) as f:
        assert f.read() == plain.replace("FROM lineitem\n", "FROM lineitem_chunked\n")


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_reference_is_q1s_value_for_value(dtype, monkeypatch):
    from benchmark import run

    chunked = _gens()[1]
    monkeypatch.setattr(chunked.BASE, "CHUNK_BYTES", 20_000)
    monkeypatch.setattr(chunked, "BLOCK_ROWS", 6_000)
    table = chunked.generate(25_000, 0.004, 2)
    assert table.column("l_shipinstruct").num_chunks > 1
    assert table.column("l_returnflag").num_chunks > 1
    for delta in (60, 95, 120):
        want = run.plugin("reference", "q1").answer(table, {"DELTA": delta}, dtype)
        got = run.plugin("reference", "q1_chunked").answer(table, {"DELTA": delta}, dtype)
        assert got.equals(want)
        assert len(got) == 4 and got["count_order"].dtype == want["count_order"].dtype


def test_cell_is_declared_as_the_deployment():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == "tpch_sf30_4chip.q1"]
    assert cell["chips"] == 4 and cell["config"] == "tpch_sf30_4chip"
    with open(os.path.join(REPO, "benchmark", "configs", "tpch_sf30_4chip.json")) as f:
        config = json.load(f)
    assert config["reduced"] == {} and config["chips"] == 4
    assert config["tables"]["lineitem_chunked"]["rows"] == 179_998_372
    assert config["engine_conf"]["fugue.jax.devices"] == "0,1,2,3"
    (metric,) = [m for m in spec["per_layer"] if m["name"] == "collective_pct"]
    assert metric["workloads"] == ["tpch_sf30_4chip.q1"]


def test_sf30_cell_on_four_virtual_devices(tmp_path, monkeypatch):
    from benchmark import run

    root = tiny_root(tmp_path, rows=40_000)
    before = snapshot(root)
    gen = run.plugin("gen", "lineitem_chunked", root)
    monkeypatch.setattr(gen.BASE, "CHUNK_BYTES", 120_000)
    monkeypatch.setattr(gen, "BLOCK_ROWS", 15_000)
    table = gen.generate(40_000, 40_000 / 6_000_000, 5)
    assert all(table.column(c).num_chunks > 1
               for c in ("l_returnflag", "l_shipinstruct", "l_comment"))
    lines = []
    res = run.run_cell("tpch_sf30_4chip.q1", 2**31 + 12345, 0.3, require_tpu=False, root=root,
                       log=lambda s: None, out=lines.append)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"rows_per_s", "setup_s"} <= set(res["metrics"])
    assert "q1_chunked.max_rel_err" in res["checks"]
    counters = next(json.loads(s)["counters"] for s in lines if s.startswith('{"counters"'))
    assert counters["after"]["fallbacks"] == {}
    assert_unchanged(before)


def test_control_in_float32_fails_on_the_new_query(tmp_path):
    root = tiny_root(tmp_path, rows=200_000)
    res = run_tiny(root, "tpch_sf30_4chip.q1", control_dtype=np.float32)
    assert res["correct"] is True
    c = res["control"]["checks"]["q1_chunked.max_rel_err"]
    assert res["control"]["correct"] is False and c["value"] > c["limit"]


@pytest.mark.parametrize(
    "name,want",
    [
        ("all-reduce", True), ("all-reduce.3", True), ("all-reduce-start", True),
        ("all-reduce-done.12", True), ("%all-gather.1", True), ("all-to-all.2", True),
        ("reduce-scatter.7", True), ("collective-permute-start.1", True),
        ("fusion.3", False), ("all-reduce-fusion", False), ("copy.2", False),
        ("reduce.4", False), ("my-all-reduce", False),
        # on the chip an event is named by its whole HLO instruction
        ("%all-gather.13 = f32[4,1,12]{2,1,0:T(1,128)S(1)} all-gather(f32[1,1,12]"
         "{2,1,0:T(1,128)S(1)} %bitcast.58), channel_id=2, replica_groups=[1,4]<=[4]", True),
        ("%all-reduce = s32[12]{0:T(128)S(1)} all-reduce(s32[12]{0:T(128)S(1)} "
         "%select_reduce_fusion.5), channel_id=1, to_apply=%region_0.1.clone", True),
        ("%select_reduce_fusion.4 = (f32[12]{0:T(128)S(1)}, f32[12]{0:T(128)S(1)}) "
         "fusion(f32[44999593]{0:T(1024)} %get-tuple-element.148), kind=kLoop", False),
        ("%fusion.2 = f32[12]{0} fusion(f32[12]{0} %all-reduce.1), kind=kLoop", False),
    ],
)
def test_opcode_match(name, want):
    from benchmark.collectives import is_collective

    assert is_collective(name) is want


def test_union_per_plane_and_mean_over_planes():
    from benchmark.collectives import collective_seconds

    s = 1e9  # seconds in ns
    planes = [
        # overlapping start/done pair unions to 2 s; the fusion is not counted
        [("all-reduce-start.1", 1 * s, 2.5 * s), ("all-reduce-done.1", 2 * s, 3 * s),
         ("fusion.1", 0, 10 * s)],
        # clipped to the window [0, 10 s]: 1 s inside
        [("all-gather.2", 9 * s, 12 * s), ("fusion.2", 0, 5 * s)],
        # ran, no collective: counts as 0
        [("fusion.3", 1 * s, 2 * s)],
        # nothing in the window: not a plane that ran
        [("all-reduce.9", 20 * s, 21 * s)],
        [],
    ]
    seconds, n = collective_seconds(planes, 0, 10 * s)
    assert n == 3
    assert seconds == pytest.approx((2 + 1 + 0) / 3)
    assert collective_seconds([[], []], 0, 1) == (0.0, 0)


def test_reader_on_a_one_chip_trace(tmp_path):
    """A chip trace with no collective reads 0; an untraced run reads None."""
    from benchmark import run
    from benchmark.trace import reduce

    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    trace = root / ".bench_trace" / "run" / "serve.xplane.pb"
    trace.parent.mkdir(parents=True)
    src = os.path.join(REPO, "tests", "benchmark", "data", "serve_trace.xplane.pb.gz")
    with gzip.open(src) as f, open(trace, "wb") as g:
        shutil.copyfileobj(f, g)
    reader = run.plugin("metrics", "collective_pct", str(root))
    ctx = run.RunContext(trace=reduce(str(trace)))
    assert reader.read(ctx) == 0.0
    assert reader.read(run.RunContext()) is None
