"""The harness on the CPU at a few thousand rows: every cell's queries
match the plain reference with no host fallback, new configurations,
mixes and metrics are found from new files alone, the run refuses the
CPU, and the comparison fails its control and each fault a cell can have."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import REPO, add_config, assert_unchanged, run_tiny, snapshot, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize(
    "workload", ["tpch_sf10.q1", "tpch_sf10.q6", "tpch_serve_sf1.streams2"]
)
def test_cell_matches_reference(root, workload):
    res = run_tiny(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) >= {"rows_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_fallbacks_empty_through_fugue_sql(root):
    """Q1 and Q6 stay on the device through ``fugue_sql`` (no fallback)."""
    from benchmark import run
    from benchmark.gen import lineitem
    from fugue_tpu import ArrowDataFrame, fugue_sql, make_execution_engine

    table = lineitem.generate(5_000, 0.001, 3)
    engine = make_execution_engine("jax", {"fugue.jax.placement": "device", "fugue.jax.devices": "0"})
    df = engine.persist(engine.to_df(ArrowDataFrame(table)))
    for q, params in (("q1", {"DELTA": 90}), ("q6", {"DATE": 1994, "DISCOUNT": 6, "QUANTITY": 24})):
        mod = run.plugin("queries", q)
        with open(os.path.join(REPO, "benchmark", "queries", f"{q}.sql")) as f:
            text = f.read().format(**mod.literals(params))
        got = fugue_sql(text, lineitem=df, engine=engine, as_fugue=True).as_pandas()
        want = run.plugin("reference", q).answer(table, params)
        from benchmark.compare import compare

        mism, rel = compare(got, want)
        assert mism == 0 and rel <= mod.MAX_REL_ERR
    assert engine.fallbacks == {}


def test_traced_run_on_cpu_has_no_device_numbers(root):
    res = run_tiny(root, "tpch_sf10.q6", trace=True)
    assert res["correct"]
    assert "device_idle_pct" not in res["metrics"]  # no device plane on the CPU
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_config_mix_and_metric_from_files_alone(tmp_path):
    root = tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    before = snapshot(root)
    add_config(root, "tpch_tiny_extra",
               {"name": "tpch_tiny_extra.q6_then_q1", "traffic": "q6_then_q1", "chips": 1})
    with open(os.path.join(bench, "traffic", "q6_then_q1.json"), "w") as f:
        json.dump({"streams": 1, "queries": ["q6", "q1"]}, f)
    with open(os.path.join(bench, "metrics", "answers_per_s.py"), "w") as f:
        f.write("def read(run):\n    return sum(r['ok'] for r in run.records) / run.window_s\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["end_to_end"].append({"name": "answers_per_s", "unit": "1/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["tpch_tiny_extra.q6_then_q1"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    res = run_tiny(root, "tpch_tiny_extra.q6_then_q1")
    assert res["correct"], res["checks"]
    assert res["metrics"]["answers_per_s"]["value"] > 0
    assert {"q1.max_rel_err", "q6.max_rel_err"} <= set(res["checks"])
    assert_unchanged(before)  # no file that was there changed


def test_four_chip_config_from_files_alone(tmp_path, monkeypatch):
    """A four-chip configuration and cell added as new files run through
    ``run_cell`` on four of the suite's virtual CPU devices, with
    ``l_comment`` in several chunks: every frame sits on a four-device
    mesh, nothing falls back to the host, and the answers match
    ``reference/q1.py``."""
    from benchmark import run

    rows = 48_000  # above the engine's 32,768-row floor for auto-shuffle
    root = tiny_root(tmp_path, rows=rows)
    before = snapshot(root)
    with open(os.path.join(root, "benchmark", "configs", "tpch_sf10.json")) as f:
        conf = json.load(f)["engine_conf"]
    config = add_config(root, "tpch_4chip",
                        {"name": "tpch_4chip.q1", "traffic": "q1", "chips": 4},
                        chips=4, engine_conf=dict(conf, **{"fugue.jax.devices": "0,1,2,3"}))

    gen = run.plugin("gen", "lineitem", root)
    gen.CHUNK_BYTES = 200_000  # ~1.3 MB of comments: several chunks
    assert gen.generate(rows, config["scale_factor"], 5).column("l_comment").num_chunks > 4
    driver = run.plugin("drivers", "embedded", root)
    meshes = []

    def check_engine(engine, frames, platform):
        frames = list(frames)
        meshes.extend(int(df.native.mesh.devices.size) for df in frames)
        return real_check(engine, frames, platform)

    real_check = driver.check_engine
    monkeypatch.setattr(driver, "check_engine", check_engine)
    lines = []
    res = run.run_cell("tpch_4chip.q1", 5, 0.3, require_tpu=False, root=root,
                       log=lambda s: None, out=lines.append)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["answers_compared"]["value"] == res["attempted"]
    assert meshes and set(meshes) == {4}
    counters = next(json.loads(s)["counters"] for s in lines if s.startswith('{"counters"'))
    assert counters["after"]["fallbacks"] == {}
    assert_unchanged(before)


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tpch_sf10.q1", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "not 'tpu'" in proc.stderr
    assert "{" not in proc.stdout


def test_fails_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's paths has
    no system under test: the run ends non-zero with no result line."""
    root = tiny_root(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); from benchmark import run; "
            "print(run.run_cell('tpch_sf10.q6', 1, 0.2, require_tpu=False))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "No module named 'fugue_tpu'" in proc.stderr
    assert "correct" not in proc.stdout


def test_control_in_float32_fails(tmp_path):
    """The reference computed in float32 in the program's place, reduced
    by the same checks, comes out not correct: above every float limit it
    is held to (its readings at the cells' sizes are in PERF.md)."""
    root = tiny_root(tmp_path, rows=200_000)
    res = run_tiny(root, "tpch_serve_sf1.streams2", control_dtype=np.float32)
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    for q in ("q1", "q6"):
        c = res["control"]["checks"][f"{q}.max_rel_err"]
        assert c["value"] > c["limit"], c


def _alter_results(monkeypatch):
    """Every float64 answer off by one part in ten million where the
    engine produces it (device blocks -> arrow)."""
    import pyarrow as pa

    import fugue_tpu.jax_backend.dataframe as jdf

    real = jdf.to_arrow

    def altered(blocks, schema):
        t = real(blocks, schema)
        cols = [c.cast(pa.float64()).to_numpy() * (1 + 1e-7) if pa.types.is_float64(c.type) else c
                for c in t.columns]
        return pa.table(cols, names=t.column_names)

    monkeypatch.setattr(jdf, "to_arrow", altered)


def _drop_half(monkeypatch):
    """Half of the table's rows never reach the engine."""
    import fugue_tpu

    real = fugue_tpu.ArrowDataFrame

    def half(table, *a, **kw):
        return real(table.slice(0, table.num_rows // 2), *a, **kw)

    monkeypatch.setattr(fugue_tpu, "ArrowDataFrame", half)


@pytest.mark.parametrize(
    "workload,fault",
    [
        ("tpch_sf10.q1", _alter_results),
        ("tpch_sf10.q6", _alter_results),
        ("tpch_serve_sf1.streams2", _alter_results),
        ("tpch_sf10.q1", _drop_half),
        ("tpch_sf10.q6", _drop_half),
    ],
)
def test_fault_makes_correct_false(root, monkeypatch, workload, fault):
    fault(monkeypatch)
    res = run_tiny(root, workload)
    assert res["correct"] is False
    assert res["failed"] > 0
