"""Measurement inside the program: the step program's phase scopes and
the op -> phase table it publishes under a profiler session, and the
plan builders' phase gauge."""

import re

import jax
import jax.numpy as jnp
import pytest

from dccrg_tpu import Grid, telemetry
from dccrg_tpu.grid import default_mesh
from dccrg_tpu.models.advection import GridAdvection
from dccrg_tpu.models.advection_amr import AmrAdvection

pytestmark = pytest.mark.telemetry

SCOPES = {"dccrg.exchange", "dccrg.bulk", "dccrg.apply"}
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = ", re.M)


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.registry().reset()
    yield
    telemetry.registry().reset()


def _stepped_under_profiler(tmp_path, n_dev):
    """A small advection grid on ``n_dev`` devices, warmed, then stepped
    twice under a profiler session; returns (solver, dt)."""
    solver = GridAdvection(n=16, nz=16, mesh=default_mesh(jax.devices()[:n_dev]))
    dt = 0.5 * solver.max_time_step()
    solver.run(0, dt)
    jax.block_until_ready(solver.grid.data["density"])
    jax.profiler.start_trace(str(tmp_path))
    try:
        solver.run(1, dt)
        solver.run(1, dt)
        jax.block_until_ready(solver.grid.data["density"])
    finally:
        jax.profiler.stop_trace()
    return solver, dt


def _module_text(solver, dt):
    """The compiled step module's text, as the program compiled it."""
    g = solver.grid
    fn, tables, static_in = g.compile_step_loop(
        solver._kernel, ["density", "vx", "vy"], ["density"], n_extra=1)
    args = (jnp.int32(1), *tables, *(g.data[n] for n in static_in),
            g.data["density"], jnp.float32(dt))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("n_dev, want", [
    (4, SCOPES),
    (1, SCOPES - {"dccrg.exchange"}),
])
def test_published_table_maps_every_op(tmp_path, n_dev, want):
    """The table covers every instruction of the compiled
    ``jit_dccrg_step_loop`` module; on four devices all three scopes
    show, and one device has no exchange."""
    solver, dt = _stepped_under_profiler(tmp_path, n_dev)
    table = telemetry.program_scopes()["jit_dccrg_step_loop"]
    assert set(INSTRUCTION.findall(_module_text(solver, dt))) == set(table)
    assert set(table.values()) - {"unscoped"} == want
    assert telemetry.registry().gauge_value(
        "dccrg_scope_table_seconds", module="jit_dccrg_step_loop") > 0


AMR_FIELDS = ["density", "vx", "vy", "vz", "lx", "ly", "lz", "ilen"]


def _scopes_of(grid, kernel, fields_in, dt):
    """The op -> scope table of the compiled step program."""
    fn, tables, static_in = grid.compile_step_loop(
        kernel, fields_in, ["density"], n_extra=1)
    args = (jnp.int32(1), *tables, *(grid.data[n] for n in static_in),
            grid.data["density"], jnp.float32(dt))
    return telemetry.scope_table(fn.lower(*args).compile().as_text())


def test_hybrid_plan_scopes_its_hard_rows():
    """A refined grid's hybrid plan recomputes its hard rows in their
    own scope, beside the bulk pass."""
    amr = AmrAdvection((8, 8, 1), max_refinement_level=1,
                       mesh=default_mesh(jax.devices()[:1]))
    amr.adapt()
    assert amr.hard_rows() > 0
    scopes = set(_scopes_of(amr.grid, amr._fused_kernel, AMR_FIELDS,
                            1e-3).values())
    assert {"dccrg.bulk", "dccrg.hard", "dccrg.apply"} <= scopes


def test_uniform_plan_has_no_hard_scope():
    solver = GridAdvection(n=16, nz=16, mesh=default_mesh(jax.devices()[:1]))
    scopes = set(_scopes_of(solver.grid, solver._kernel,
                            ["density", "vx", "vy"], 1e-3).values())
    assert "dccrg.bulk" in scopes and "dccrg.hard" not in scopes


def test_adapt_counts_one_epoch_and_nests_the_recommit():
    """One adapt(): one epoch on the counters, the cells it made and
    removed, the new plan's hard rows on the gauge, and an ``amr.adapt``
    span that holds the plan rebuild's ``grid.recommit``."""
    amr = AmrAdvection((8, 8, 1), max_refinement_level=1,
                       mesh=default_mesh(jax.devices()[:1]))
    reg = telemetry.registry()
    telemetry.configure(trace=True)
    telemetry.clear_trace()
    try:
        created, removed = amr.adapt()
        evs = telemetry.events()
    finally:
        telemetry.configure(trace=None)
        telemetry.clear_trace()
    assert reg.counter_value("dccrg_amr_epochs_total") == 1
    assert reg.counter_value("dccrg_amr_cells_created_total") == len(created) > 0
    assert reg.counter_value("dccrg_amr_cells_removed_total") == len(removed)
    assert reg.gauge_value("dccrg_amr_hard_rows") == amr.hard_rows() > 0
    (outer,) = [e for e in evs if e["name"] == "amr.adapt"]
    (inner,) = [e for e in evs if e["name"] == "grid.recommit"]
    assert inner["depth"] > outer["depth"] == 0
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_no_table_without_a_session():
    """Outside a profiler session run_steps publishes nothing."""
    published = telemetry.program_scopes()
    solver = GridAdvection(n=8, nz=8, mesh=default_mesh(jax.devices()[:1]))
    solver.run(1, 0.5 * solver.max_time_step())
    assert telemetry.program_scopes() == published
    assert telemetry.registry().gauge_value("dccrg_scope_table_seconds",
                                            module="jit_dccrg_step_loop") is None


def test_scope_table_takes_the_outermost_dccrg_scope():
    text = "\n".join([
        "HloModule jit_dccrg_step_loop, is_scheduled=true",
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(dccrg_step_loop)/while/body/dccrg.exchange/'
        'dccrg.bulk/add" stack_frame_id=4}',
        '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)',
        '  %add.2 = s32[] add(%a, %b), metadata={op_name="jit(f)/while/body/add"}',
    ])
    assert telemetry.scope_table(text) == {
        "fusion.3": "dccrg.exchange", "copy.1": "unscoped",
        "add.2": "unscoped"}


@pytest.mark.parametrize("n_dev, want", [
    (4, {"partition", "classify", "tables", "fields"}),
    (1, {"partition", "tables", "fields"}),
])
def test_uniform_build_sets_plan_phase_gauge(n_dev, want):
    g = (Grid(cell_data={"density": jnp.float32})
         .set_initial_length((8, 8, 8))
         .set_maximum_refinement_level(0)
         .set_neighborhood_length(1)
         .initialize(default_mesh(jax.devices()[:n_dev]), partition="block"))
    assert g.n_dev == n_dev
    got = {dict(labels)["phase"]: v
           for (name, labels), v in telemetry.registry().gauges.items()
           if name == telemetry.PLAN_PHASE_GAUGE}
    assert set(got) == want
    assert all(v >= 0 for v in got.values())


def test_phase_timer_sums_repeated_marks_and_echoes(monkeypatch, capsys):
    clock = iter([10.0, 11.0, 13.0, 16.0])
    monkeypatch.setattr(telemetry.time, "perf_counter", lambda: next(clock))
    monkeypatch.setenv("DCCRG_TIMING", "1")
    mark = telemetry.phase_timer()
    mark("tables")
    mark("tables")
    mark("fields")
    reg = telemetry.registry()
    assert reg.gauge_value(telemetry.PLAN_PHASE_GAUGE, phase="tables") == 3.0
    assert reg.gauge_value(telemetry.PLAN_PHASE_GAUGE, phase="fields") == 3.0
    assert reg.gauge_value(telemetry.PLAN_PHASE_GAUGE, phase="classify") is None
    assert capsys.readouterr().out.splitlines() == [
        "[plan] tables: 1.000s", "[plan] tables: 2.000s", "[plan] fields: 3.000s"]
