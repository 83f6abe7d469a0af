"""``chip_smoke.py`` on the CPU: ``--rehearse`` runs every phase end to end
at 1/1000 of its size, and without it the script refuses to run, so there
is no silent CPU fallback. Each run is a subprocess (the script owns its
JAX process) and writes only under ``tmp_path``: the parquet tables through
``--data-dir`` and the compile cache through ``JAX_COMPILATION_CACHE_DIR``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, tmp_path, devices, cwd=_REPO, script=_SMOKE):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["XLA_FLAGS"] = " ".join(
        [
            t
            for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith("--xla_force_host_platform_device_count")
        ]
        + [f"--xla_force_host_platform_device_count={devices}"]
    )
    return subprocess.run(
        [sys.executable, script, "--data-dir", str(tmp_path / "data"), *args],
        env=env,
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=600,
    )


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearse_runs_every_phase(tmp_path, chips):
    out = _run(["--rehearse", "--chips", str(chips)], tmp_path, devices=chips)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips},
    }
    phases = [line for line in lines[:-1] if "phase" in line and "fallbacks" in line]
    want = (
        ["a_headline_1dev", "a_headline_4dev", "b_sql_1dev", "b_sql_4dev"]
        if chips == 4
        else ["a_headline", "b_sql", "c_serve", "d_float_keys"]
    )
    assert [p["phase"] for p in phases] == want
    for p in phases:
        assert p["fallbacks"] == {} and p["mesh_platforms"] == ["cpu"], p
    if chips == 4:
        assert lines[-2]["phase"] == "four_chip_compare", lines[-2]
        assert lines[-2]["shuffle_counts"].get("aggregate", 0) > 0, lines[-2]
    assert os.listdir(tmp_path / "data")


def test_refuses_the_cpu_without_rehearse(tmp_path):
    out = _run([], tmp_path, devices=1)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "refusing to run" in out.stderr


def test_fails_outside_the_repo(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(_SMOKE, alone / "chip_smoke.py")
    out = _run([], tmp_path, devices=1, cwd=str(alone), script=str(alone / "chip_smoke.py"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
