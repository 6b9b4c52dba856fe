"""The Poisson cell on the CPU: a 16^3 configuration, defined only by
the files in ``data/poisson/``, runs through ``run.py`` by name and reads
``correct`` true at the committed limits; a broken solve and the
control read it false; each of its metric readers reads a CPU trace.

Each fault is planted in the program with monkeypatch and the rest of
a run goes through as usual."""

import json
import shutil
from pathlib import Path

import jax
import pytest

from conftest import BENCH, CPU_PEAK, CPU_TRACE, cpu_devices, load

from dccrg_tpu.models.poisson import PoissonSolver

DATA = Path(__file__).resolve().parent / "data" / "poisson"
# the tiny cell is judged by the committed limits and control
COMMITTED = BENCH / "configs" / "poisson3d-uniform-128.json"
WORKLOAD = "tiny.poisson.1dev"
SEED = 2718281828459
PHASES = {"matvec_ms_per_iter": "dccrg.matvec", "dot_ms_per_iter": "dccrg.dot",
          "update_ms_per_iter": "dccrg.update",
          "unscoped_ms_per_step": "unscoped"}


def tiny_config():
    """The tiny configuration with the committed limit, iteration cap
    and control."""
    committed = json.loads(COMMITTED.read_text())
    cfg = json.loads((DATA / "tiny-poisson.json").read_text())
    cfg.update({k: committed[k] for k in ("limit", "max_iterations", "control")})
    return cfg


@pytest.fixture
def run_poisson(run_mod, tmp_path, capsys):
    """Run the tiny Poisson cell through run.run on a CPU device, in a
    checkout-shaped tree that holds only its files; return its result
    line."""
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    (tmp_path / "benchmark" / "traffic").mkdir()
    shutil.copy(DATA / "bench.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "benchmark" / "configs" / "tiny-poisson.json").write_text(
        json.dumps(tiny_config()))
    shutil.copy(DATA / "tiny_solve.json", tmp_path / "benchmark" / "traffic")
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
def test_poisson_cell_from_files(run_poisson, trace):
    res = run_poisson(trace=trace)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["checks"]) == {"max_rel_err", "rel_residual", "iterations"}
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    # the phase readers read a TPU's op lines, so on the CPU they
    # report nothing here (test_phase_readers_on_cpu_trace reads them)
    want = (("plan_build_s", "compile_s", "device_idle_share.advection",
             "stencil_roofline", "plan_tables_s", "poisson_prepare_s")
            if trace else ("cell_updates_per_s", "setup_s"))
    assert set(want) == set(res["metrics"])
    idle = res["metrics"].pop("device_idle_share.advection", {"value": 0.0})
    assert 0 <= idle["value"] < 100
    assert all(m["value"] > 0 for m in res["metrics"].values())
    if trace:
        assert res["metrics"]["stencil_roofline"]["value"] < 100
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]


def test_phase_readers_on_cpu_trace(tmp_path):
    """Solves through the driver under the harness's window span, traced
    on the CPU: every solve phase shows, no op of the window is missing
    from the program's table, and the four phase metrics sum to the busy
    time per iteration."""
    phases = load(BENCH / "phases.py")
    trace = load(BENCH / "trace.py")
    driver = load(BENCH / "drivers" / "poisson.py")
    model = driver.build(tiny_config(), {"mesh_devices": 1}, jax.devices()[:1])
    driver.load(model, SEED)
    driver.warm(model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            rec = driver.window(model, 0.0, lambda name: jax.profiler.TraceAnnotation(name))
    finally:
        jax.profiler.stop_trace()
    rec.update(peak=CPU_PEAK, trace=trace.reduce_trace(tmp_path, 1, **CPU_TRACE))
    out = phases.read(rec, tmp_path, **CPU_TRACE)
    assert {"dccrg.matvec", "dccrg.dot", "dccrg.update"} <= set(out["phase_s"])
    assert out["missing_s"] == 0, out["top_missing"]
    got = {m: load(BENCH / "metrics" / f"{m}.py").read(rec) for m in PHASES}
    assert all(got[m] > 0 for m in PHASES if m != "unscoped_ms_per_step"), got
    busy_ms = 1e3 * rec["trace"]["per_device"][0]["busy_s"] / rec["steps"]
    assert sum(got.values()) == pytest.approx(busy_ms, rel=0.01)
    roofline = load(BENCH / "metrics" / "stencil_roofline.py").read(rec)
    assert 0 < roofline < 100


def perturbed_solution(monkeypatch):
    """One cell of the returned solution moved by a hundredth of the
    solution's largest value."""
    orig = PoissonSolver.solve

    def solve(self, *a, **k):
        out = orig(self, *a, **k)
        x = self.grid.data["solution"]
        self.grid.data["solution"] = x.at[0, 37].add(0.01 * abs(x).max())
        return out

    monkeypatch.setattr(PoissonSolver, "solve", solve)


def stopped_early(monkeypatch):
    """Every solve stopped after 3 iterations."""
    orig = PoissonSolver.solve

    def solve(self, rtol=1e-5, max_iterations=1000, **k):
        return orig(self, rtol=rtol, max_iterations=min(max_iterations, 3), **k)

    monkeypatch.setattr(PoissonSolver, "solve", solve)


def face_factor_zeroed(monkeypatch):
    """The +x face factor of every cell zeroed after preparation: the
    solve runs a different operator."""
    orig = PoissonSolver.prepare

    def prepare(self, *a, **k):
        orig(self, *a, **k)
        self.grid.data["fxp"] = 0.0 * self.grid.data["fxp"]

    monkeypatch.setattr(PoissonSolver, "prepare", prepare)


@pytest.mark.parametrize("fault", [perturbed_solution, stopped_early,
                                   face_factor_zeroed])
def test_poisson_fault_is_not_correct(run_poisson, monkeypatch, fault):
    fault(monkeypatch)
    res = run_poisson()
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_poisson_control_is_not_correct(run_poisson):
    """The control (bfloat16 storage), through run.py's own comparison."""
    res = run_poisson(control=1)
    assert res["correct"] is False
    assert not all(c["value"] <= c["limit"] for c in res["checks"].values())
