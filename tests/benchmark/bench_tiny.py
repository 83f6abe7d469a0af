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
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # Q6 alone on the embedded engine: a cell the chip budget left out
    # (PERF.md, Open questions), kept here as a test of the Q6 path
    if "tpch_sf10.q6" not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append({"name": "tpch_sf10.q6", "config": "tpch_sf10",
                                  "traffic": "q6", "chips": 1, "why": "tests"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
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


def run_tiny(root: str, workload: str, seed: int = 5, seconds: float = 0.3, **kw):
    from benchmark import run

    return run.run_cell(workload, seed, seconds, require_tpu=False, root=root,
                        log=lambda s: None, out=lambda s: None, **kw)
