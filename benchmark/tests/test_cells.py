"""Cells defined only by files run through discovery by name; the
harness refuses to run where there is no TPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH

ADVECTION = ("cell_updates_per_s", "setup_s")
PER_LAYER = ("plan_build_s", "compile_s", "device_idle_share.advection",
             "stencil_roofline")


@pytest.mark.parametrize("workload, trace, want", [
    ("tiny.advection.1dev", 0, ADVECTION),
    ("tiny.advection.4dev", 0, ADVECTION),
    ("tiny.advection.1dev", 1, PER_LAYER),
    ("tiny.advection.4dev", 1, PER_LAYER + ("exposed_collective_ms_per_step",)),
])
def test_cell_from_files(run_cell, workload, trace, want):
    res = run_cell(workload, trace=trace)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(want) <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]
    else:
        assert all(m["value"] > 0 for m in res["metrics"].values())


def test_refuses_without_tpu():
    """No CPU fallback: exit code 2 and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "advection512.f32.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "not tpu" in p.stderr


def test_benchmark_json_names_resolve():
    """Every cell of BENCHMARK.json finds its config, driver and
    traffic, and every metric its reader."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cfg = json.loads((BENCH.parent / configs[w["config"]]["file"]).read_text())
        assert (BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
