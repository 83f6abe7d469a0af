"""Shared helpers of the benchmark's tests: a copy of the harness whose
configurations hold a few thousand rows, run on the CPU."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def tiny_root(tmp_path, rows: int = 20_000) -> str:
    """A scratch checkout: ``BENCHMARK.json`` and ``benchmark/`` copied,
    every configuration's tables cut to ``rows`` (scale factor to match)."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), os.path.join(root, "BENCHMARK.json"))
    configs = os.path.join(root, "benchmark", "configs")
    for name in os.listdir(configs):
        path = os.path.join(configs, name)
        with open(path) as f:
            c = json.load(f)
        for t in c["tables"].values():
            t["rows"] = rows
        c["scale_factor"] = rows / 6_000_000
        with open(path, "w") as f:
            json.dump(c, f)
    return root


def snapshot(root: str) -> dict:
    """Every file under ``root``'s ``benchmark/`` and its bytes."""
    files = {}
    for d, _, names in os.walk(os.path.join(root, "benchmark")):
        for name in names:
            with open(os.path.join(d, name), "rb") as f:
                files[os.path.join(d, name)] = f.read()
    return files


def assert_unchanged(files: dict) -> None:
    for path, data in files.items():
        with open(path, "rb") as f:
            assert f.read() == data, path


def add_config(root: str, name: str, cell: dict, **changes) -> dict:
    """A new configuration file (``tpch_sf10``'s with ``changes``) and a
    cell on it, both added to ``BENCHMARK.json``; the configuration."""
    configs = os.path.join(root, "benchmark", "configs")
    with open(os.path.join(configs, "tpch_sf10.json")) as f:
        config = dict(json.load(f), name=name, **changes)
    with open(os.path.join(configs, f"{name}.json"), "w") as f:
        json.dump(config, f)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name=name,
                                file=f"benchmark/configs/{name}.json"))
    spec["workloads"].append(dict(cell, config=name, why="test"))
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    return config


def run_tiny(root: str, workload: str, seed: int = 5, seconds: float = 0.3, **kw):
    from benchmark import run

    return run.run_cell(workload, seed, seconds, require_tpu=False, root=root,
                        log=lambda s: None, out=lambda s: None, **kw)
