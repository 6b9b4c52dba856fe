"""The phase reduction (phases.py): the busy-time split on its own, a
synthetic trace, and a trace recorded here of the tiny 4-device cell
with the overlap on, where the phase metrics sum to the read device's
busy time per step."""

import json

import jax
import pytest

from conftest import BENCH, CPU_TRACE, load

phases = load(BENCH / "phases.py")
trace = load(BENCH / "trace.py")

SCOPES = ("dccrg.exchange", "dccrg.bulk", "dccrg.repass", "dccrg.apply",
          "unscoped")
METRICS = ("exchange_ms_per_step", "bulk_ms_per_step", "repass_ms_per_step",
           "apply_ms_per_step", "unscoped_ms_per_step")


@pytest.mark.parametrize("ops, want", [
    # one op at a time: each label holds its ops' own time
    ([("a", 0, 10), ("b", 10, 30), ("a", 40, 45)], {"a": 15, "b": 20}),
    # overlapping ops share the overlap equally
    ([("a", 0, 10), ("b", 5, 15)], {"a": 7.5, "b": 7.5}),
    # three ops in [2, 4): two of label a, one of b
    ([("a", 0, 4), ("a", 2, 4), ("b", 2, 6)],
     {"a": 2 + 2 * 2 / 3, "b": 2 / 3 + 2}),
    ([], {}),
])
def test_split_shares_the_union(ops, want):
    got = phases.split(ops, lambda n: n)
    assert got == pytest.approx(want)
    union = trace.length(trace.union((s, e) for _, s, e in ops))
    assert sum(got.values()) == pytest.approx(union)


def test_reduce_synthetic():
    """Device 1 only is read; one op is missing from the table, one has
    no scope; grid.step spans outside the window are left out."""
    table = {"fusion.1": "dccrg.bulk", "cp-start": "dccrg.exchange",
             "fusion.2": "dccrg.apply", "copy": "unscoped"}
    doc = {
        "window": [(100, 1100)],
        "steps": [(50, 90), (100, 130), (600, 620), (1200, 1300)],
        "devices": {
            0: [[("fusion.1", 100, 1000)]],
            1: [[("while", 100, 900), ("cp-start", 100, 150),
                 ("fusion.1", 150, 550), ("fusion.2", 550, 600),
                 ("copy", 600, 620), ("mystery", 620, 700)],
                [("fusion.1", 1050, 1200)]],
        },
    }
    r = phases.reduce(doc, table, 1)
    assert r["device"] == 1
    assert r["phase_s"] == pytest.approx({
        "dccrg.exchange": 50e-9, "dccrg.bulk": 450e-9,
        "dccrg.apply": 50e-9, "unscoped": 100e-9})
    assert r["busy_s"] == pytest.approx(650e-9)
    assert sum(r["phase_s"].values()) == pytest.approx(r["busy_s"])
    assert r["missing_s"] == pytest.approx(80e-9)
    assert r["top_missing"] == [["mystery", pytest.approx(80e-9)]]
    assert [n for n, _ in r["top_unscoped"]] == ["mystery", "copy"]
    assert r["dispatch_s"] == pytest.approx([30e-9, 20e-9])


def test_readers_are_silent_without_a_trace():
    """An untraced run, or a program that publishes no table, reads
    None: the parent of this metric has neither."""
    assert phases.read({"steps": 3}) is None
    assert phases.ms_per_step({"steps": 3}, "dccrg.bulk") is None
    assert phases.dispatch_ms_per_call({"steps": 3}) is None


def test_phases_of_recorded_cpu_trace(tmp_path, monkeypatch):
    """4 virtual devices step a small grid with the overlap on under the
    harness's window span: every scope shows, the five phase metrics sum
    to the read device's busy time per step within 1%, no op of the
    window is missing from the program's table, and the grid.step spans
    give the dispatch time."""
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection import GridAdvection

    monkeypatch.setenv("DCCRG_OVERLAP", "1")
    solver = GridAdvection(n=16, nz=16, mesh=default_mesh(jax.devices()[:4]))
    dt = 0.5 * solver.max_time_step()
    solver.run(0, dt)
    solver.grid.data["density"].block_until_ready()
    steps = 3
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(steps):
                solver.run(1, dt)
            solver.grid.data["density"].block_until_ready()
    finally:
        jax.profiler.stop_trace()
    rec = {"steps": steps, "calls": steps,
           "trace": trace.reduce_trace(tmp_path, 4, **CPU_TRACE)}
    out = phases.read(rec, tmp_path, **CPU_TRACE)
    assert set(SCOPES[:4]) <= set(out["phase_s"])
    assert out["missing_s"] == 0, out["top_missing"]
    per_step = {m: phases.ms_per_step(rec, s) for m, s in zip(METRICS, SCOPES)}
    assert all(v > 0 for v in per_step.values()), per_step
    (dev,) = rec["trace"]["per_device"]
    busy_ms = 1e3 * dev["busy_s"] / steps
    assert sum(per_step.values()) == pytest.approx(busy_ms, rel=0.01)
    assert len(out["dispatch_s"]) == steps
    assert phases.dispatch_ms_per_call(rec) > 0


def test_metric_files_read_the_memo():
    """Each metric file reads its own entry of the memoised reduction."""
    rec = {"steps": 2, "phases": {
        "phase_s": {"dccrg.bulk": 0.004, "dccrg.apply": 0.002},
        "dispatch_s": [0.001, 0.003]}}
    read = {m: load(BENCH / "metrics" / f"{m}.py").read(rec)
            for m in METRICS + ("dispatch_ms_per_call",)}
    assert read == pytest.approx({
        "exchange_ms_per_step": 0.0, "bulk_ms_per_step": 2.0,
        "repass_ms_per_step": 0.0, "apply_ms_per_step": 1.0,
        "unscoped_ms_per_step": 0.0, "dispatch_ms_per_call": 2.0})


def test_traced_cell_reports_plan_phases(cell_tree, run_cell):
    """The tiny cells with this benchmark's new per-layer metrics: a
    traced run reports the plan phases its cell lists."""
    path = cell_tree / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    tiny = {"advection512.f32.1chip": "tiny.advection.1dev",
            "advection512.f32.4chip": "tiny.advection.4dev"}
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [{**m, "workloads": [tiny[w] for w in m["workloads"]]}
                           for m in real["per_layer"] if m["name"] not in have]
    path.write_text(json.dumps(bench))
    one = run_cell("tiny.advection.1dev", trace=1)["metrics"]
    four = run_cell("tiny.advection.4dev", trace=1)["metrics"]
    assert "plan_tables_s" in one and "plan_classify_s" not in one
    assert {"plan_tables_s", "plan_classify_s"} <= set(four)
    assert all(four[m]["value"] >= 0 for m in ("plan_tables_s", "plan_classify_s"))
