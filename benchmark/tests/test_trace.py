"""The trace reduction: interval arithmetic on its own, and the whole
reduction on a small trace recorded here on 4 virtual CPU devices."""

import jax
import pytest

from conftest import BENCH, CPU_TRACE, load

trace = load(BENCH / "trace.py")


@pytest.mark.parametrize("intervals, want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(4, 5), (0, 1), (1, 2)], [(0, 2), (4, 5)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([], []),
])
def test_union(intervals, want):
    assert trace.union(intervals) == want


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [], [(0, 4)]),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_leaves_drop_enclosing_events():
    ev = [("while", 0, 100), ("fusion.1", 10, 40), ("fusion.2", 50, 90),
          ("copy", 120, 130)]
    assert [e[0] for e in trace.leaves(ev)] == ["fusion.1", "fusion.2", "copy"]


def test_reduce_synthetic():
    """Two devices; device 1 waits 100 ns in a collective-permute-done."""
    doc = {
        "spans": [("window", 0, 1000), ("call", 0, 100), ("wait", 600, 1000)],
        "devices": {
            0: [[("fusion", 100, 500)]],
            1: [[("fusion", 100, 300), ("collective-permute-done", 300, 400),
                 ("fusion.2", 400, 450)]],
        },
    }
    r = trace.reduce(doc, 2)
    d0, d1 = r["per_device"]
    assert r["window_s"] == pytest.approx(1e-6)
    assert d0["busy_s"] == pytest.approx(400e-9)
    assert d0["collective_s"] == 0
    assert d1["busy_s"] == pytest.approx(350e-9)
    assert d1["collective_s"] == pytest.approx(100e-9)
    assert d1["other_s"] == pytest.approx(250e-9)
    assert r["busy_s"] == pytest.approx(375e-9)
    gaps = dict(r["idle_gaps"])
    # device 0: 0-100 in call, 500-600 in no span, 600-1000 in wait;
    # device 1: 0-100 in call, 450-600 in no span, 600-1000 in wait
    assert gaps["call"] == pytest.approx(100e-9)
    assert gaps["no span"] == pytest.approx(125e-9)
    assert gaps["wait"] == pytest.approx(400e-9)


def test_only_the_core_line_is_read():
    """The async start-to-done line is left out on every device."""
    assert trace.tpu_ops("/device:TPU:2", "XLA Ops") == 2
    assert trace.tpu_ops("/device:TPU:0", "Async XLA Ops") is None
    assert trace.tpu_ops("/host:CPU", "XLA Ops") is None


def test_op_name():
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] %p), kind=kLoop") == "fusion.3"
    assert trace.op_name("copy.7") == "copy.7"


def test_reduce_recorded_cpu_trace(tmp_path):
    """A real trace: 4 virtual devices step a small grid under the
    harness's spans; the reduction finds the window, device work,
    collectives (the halo ppermute) and labelled idle gaps."""
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection import GridAdvection

    solver = GridAdvection(n=16, nz=16, mesh=default_mesh(jax.devices()[:4]))
    dt = 0.5 * solver.max_time_step()
    solver.run(0, dt)
    solver.grid.data["density"].block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench:call"):
                    solver.run(1, dt)
            with jax.profiler.TraceAnnotation("bench:sync"):
                solver.grid.data["density"].block_until_ready()
    finally:
        jax.profiler.stop_trace()
    r = trace.reduce_trace(tmp_path, 4, **CPU_TRACE)
    assert r["window_s"] > 0
    assert 0 < r["busy_s"] <= r["window_s"]
    (dev,) = r["per_device"]
    assert dev["collective_s"] > 0, r["device_ops"]
    assert dev["other_s"] > 0
    assert r["device_ops"] and len(r["device_ops"]) <= trace.TOP
    assert {name for name, _ in r["idle_gaps"]} <= {"call", "sync", "no span"}
