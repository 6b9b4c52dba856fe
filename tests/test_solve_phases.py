"""Measurement inside the Poisson solve: the fused solve program's name
and phase scopes, the op -> phase table it publishes under a profiler
session, its span and counters, and the ``poisson_prepare`` plan-phase
gauge."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from dccrg_tpu import telemetry
from dccrg_tpu.grid import default_mesh
from dccrg_tpu.models.poisson import PoissonSolver

pytestmark = pytest.mark.telemetry

N = 16
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = ", re.M)
SOLVE_MODULE = "jit_dccrg_poisson_solve"


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.registry().reset()
    yield
    telemetry.registry().reset()


def _solver(n_dev=1):
    s = PoissonSolver((N, N, N), mesh=default_mesh(jax.devices()[:n_dev]))
    rhs = np.random.default_rng(7).standard_normal(N**3).astype(np.float32)
    s.set_rhs(rhs)
    return s


def _lowered(s):
    """The solve program lowered for the arguments the solve passes."""
    s.prepare()
    prog, bindings = s._fused_solve_fn()
    g = s.grid
    args = (g.data["solution"], g.data["rhs"], g.data["Ap0"],
            jnp.asarray(1e-5, dtype=s.dtype), jnp.int32(1000), *bindings)
    return prog.lower(*args)


def _compiled_text(s):
    """The compiled solve module's text, as the solve compiles it."""
    return _lowered(s).compile().as_text()


def _stripped(text):
    """``text`` without metadata, the module's name and the stack-frame
    tables: what the compiler makes of the program's operations alone."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^HloModule\s+[^\s,]+", "HloModule M", text, flags=re.M)
    return re.sub(r"^FileNames\n.*?\n(?=(?:ENTRY )?%)", "", text,
                  flags=re.M | re.S)


@pytest.mark.parametrize("n_dev, want", [
    (1, {"dccrg.matvec", "dccrg.dot", "dccrg.update"}),
    (4, {"dccrg.matvec", "dccrg.dot", "dccrg.update", "dccrg.exchange"}),
])
def test_solve_publishes_its_phase_table(tmp_path, n_dev, want):
    """Under a profiler session the first solve publishes the table of
    ``jit_dccrg_poisson_solve``; it covers every instruction of the
    compiled module, and on four devices the exchange scope shows
    too."""
    s = _solver(n_dev)
    s.solve(max_iterations=0)  # compiled outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.solve()
    finally:
        jax.profiler.stop_trace()
    table = telemetry.program_scopes()[SOLVE_MODULE]
    assert set(table.values()) - {"unscoped"} == want
    assert set(INSTRUCTION.findall(_compiled_text(s))) == set(table)
    assert telemetry.registry().gauge_value(
        "dccrg_scope_table_seconds", module=SOLVE_MODULE) > 0
    # the mean removal around the program is a published program too
    assert set(telemetry.program_scopes()["jit_dccrg_poisson_remove_mean"]
               .values()) >= {"dccrg.update"}


def test_no_span_or_table_without_a_session(monkeypatch):
    """Outside a profiler session and with DCCRG_TRACE unset the solve's
    span is the shared no-op and nothing is published."""
    monkeypatch.setitem(telemetry._TRACE, "on", False)
    assert telemetry.span("poisson.solve") is telemetry.NULL_SPAN
    published = telemetry.program_scopes()
    _solver().solve()
    assert telemetry.program_scopes() == published


def test_solve_counters_move_by_iterations_and_solves():
    reg = telemetry.registry()
    s = _solver()
    done = s.solve()
    s.grid.data["solution"] = jnp.zeros_like(s.grid.data["solution"])
    capped = s.solve(max_iterations=2)
    assert 0 < done["iterations"] < 1000 and capped["iterations"] == 2
    assert reg.counter_value("dccrg_poisson_iterations_total") == \
        done["iterations"] + capped["iterations"]
    assert reg.counter_value("dccrg_poisson_solves_total", converged="true") == 1
    assert reg.counter_value("dccrg_poisson_solves_total", converged="false") == 1


def test_solve_program_reads_neighbors_through_roll3d():
    """On one device both matvecs of the solve program are slot-wise on
    the 3-D roll gather: two ``roll3d`` programs, no gather op and no
    scatter but the write-backs of the three matvec calls."""
    text = _lowered(_solver()).as_text()
    assert telemetry.registry().counter_value(
        "dccrg_slot_gather_programs_total", gather="roll3d") == 2
    assert "stablehlo.gather" not in text
    assert text.count("stablehlo.scatter") <= 6


def test_prepare_sets_its_plan_phase_gauge():
    s = _solver()
    assert telemetry.registry().gauge_value(
        telemetry.PLAN_PHASE_GAUGE, phase="poisson_prepare") is None
    s.prepare()
    assert telemetry.registry().gauge_value(
        telemetry.PLAN_PHASE_GAUGE, phase="poisson_prepare") > 0


def test_scopes_change_only_metadata(monkeypatch):
    """The solve program at 16^3 with its phase scopes compiles to the
    same operations as with every scope left out, the program as it
    was before it had them: the scopes add metadata only."""
    # the persistent compile cache keys a program without its metadata,
    # so the second compile would read back the first: keep both out
    # (the cache decides once whether it is used, hence the resets)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        scoped = _compiled_text(_solver())
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = _compiled_text(_solver())
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    assert "dccrg.matvec" in scoped and "dccrg." not in bare
    assert _stripped(scoped) == _stripped(bare)
