"""The adaptive-mesh advection cell on the CPU: an 8^3-base
configuration, defined only by the files in ``data/amr/``, runs through
``run.py`` by name and reads ``correct`` true at the committed limits;
the control and a broken projection read it false; its host-span and
compile readers read a CPU trace."""

import json
import shutil
from pathlib import Path

import pytest

from conftest import BENCH, CPU_TRACE, cpu_devices

from dccrg_tpu.grid import Grid

DATA = Path(__file__).resolve().parent / "data" / "amr"
# the tiny cell is judged by the committed criterion, limits and control
COMMITTED = BENCH / "configs" / "advection3d-amr1-64.json"
KEEP = ("max_refinement_level", "cfl", "dtype", "diff_threshold",
        "diff_increase", "unrefine_sensitivity", "limit", "control")
WORKLOAD = "tiny.amr.1dev"
SEED = 4294967301  # image 5: a rotation by 90 degrees and the z mirror


@pytest.fixture
def run_amr(run_mod, tmp_path, capsys):
    """Run the tiny AMR cell through run.run on a CPU device, in a
    checkout-shaped tree that holds only its files; return its result
    line."""
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    (tmp_path / "benchmark" / "traffic").mkdir()
    shutil.copy(DATA / "bench.json", tmp_path / "BENCHMARK.json")
    committed = json.loads(COMMITTED.read_text())
    cfg = json.loads((DATA / "tiny-amr.json").read_text())
    cfg.update({k: committed[k] for k in KEEP})
    (tmp_path / "benchmark" / "configs" / "tiny-amr.json").write_text(
        json.dumps(cfg))
    shutil.copy(DATA / "tiny_adapt.json", tmp_path / "benchmark" / "traffic")
    (tmp_path / "benchmark" / "drivers").symlink_to(BENCH / "drivers")

    def go(trace=0, control=0, seed=SEED):
        args = run_mod.parse_args([
            "--workload", WORKLOAD, "--seed", str(seed), "--seconds", "0.2",
            "--trace", str(trace), "--control", str(control)])
        capsys.readouterr()
        rc = run_mod.run(args, bench_path=tmp_path / "BENCHMARK.json",
                         devices_fn=cpu_devices, trace_kw=CPU_TRACE)
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


@pytest.mark.parametrize("trace", [0, 1])
def test_amr_cell_from_files(run_amr, trace):
    res = run_amr(trace=trace)
    assert res["correct"] is True
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["checks"]["level_mismatch"]["value"] == 0
    assert res["checks"]["max_abs_err"]["value"] <= 1e-6
    # the phase readers read a TPU's op lines, so hard_ms_per_step
    # reports nothing on the CPU
    want = (("plan_build_s", "compile_s", "device_idle_share.advection",
             "stencil_roofline", "adapt_ms_per_epoch",
             "recommit_ms_per_epoch", "compiles_in_window")
            if trace else ("cell_updates_per_s", "setup_s"))
    assert set(want) == set(res["metrics"])
    if trace:
        m = {k: v["value"] for k, v in res["metrics"].items()}
        # an 8^3 base still grows its row capacities in the window
        assert isinstance(m["compiles_in_window"], int)
        assert 0 < m["recommit_ms_per_epoch"] < m["adapt_ms_per_epoch"]


def test_control_reads_incorrect(run_amr):
    res = run_amr(control=1)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_broken_projection_reads_incorrect(run_amr, monkeypatch):
    """Unrefined parents that do not take their children's mean."""
    monkeypatch.setattr(Grid, "average_parents_from_children",
                        lambda self, fields=None: None)
    res = run_amr()
    assert res["correct"] is False
    assert res["checks"]["max_abs_err"]["value"] > 1e-6
