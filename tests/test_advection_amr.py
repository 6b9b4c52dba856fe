"""AMR advection tests — the reference advection test's full loop
(solve + adapt + balance) on the general grid path."""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dccrg_tpu.models.advection import AdvectionSolver
from dccrg_tpu.models.advection_amr import AmrAdvection


def mesh_of(n):
    return Mesh(np.array(jax.devices()[:n]), ("dev",))


def test_uniform_matches_dense_solver():
    """max_refinement_level=0: the general-grid gather kernel must
    reproduce the dense fast path step for step (same math,
    solve.hpp:44-266)."""
    n = 16
    amr = AmrAdvection((n, n, 1), max_refinement_level=0, mesh=mesh_of(2))
    dense_mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1), ("x", "y", "z"))
    dense = AdvectionSolver(n=n, nz=1, mesh=dense_mesh)
    dt = 0.4 * amr.max_time_step()
    for _ in range(3):
        amr.step(dt)
        dense.step(dt)
    cells = amr.grid.get_cells()
    got = amr.grid.get("density", cells).astype(np.float64)
    # dense layout is [x, y, z]; cell ids are 1 + x + y*n on a 2-D grid
    want = np.asarray(dense.grid.arrays["rho"])
    idx = (cells - 1).astype(np.int64)
    x, y = idx % n, (idx // n) % n
    # atol covers the boundary cells: the dense path wraps periodically,
    # the general grid has walls — both see ~0 density there
    np.testing.assert_allclose(got, want[x, y, 0], rtol=2e-5, atol=1e-5)


def test_mass_conserved_uniform():
    amr = AmrAdvection((16, 16, 1), max_refinement_level=0, mesh=mesh_of(4))
    m0 = amr.total_mass()
    for _ in range(5):
        amr.step()
    assert amr.total_mass() == pytest.approx(m0, rel=1e-5)


def test_adapt_refines_hump_edge():
    """The relative-difference criterion refines where density varies
    (the hump edge) and leaves the far field coarse (adapter.hpp:47)."""
    amr = AmrAdvection((16, 16, 1), max_refinement_level=1, mesh=mesh_of(4))
    created, removed = amr.adapt()
    assert len(created) > 0
    cells = amr.grid.get_cells()
    lvl = amr.grid.mapping.get_refinement_level(cells)
    assert lvl.max() == 1
    # refined cells sit near the hump edge (distance from (0.25, 0.5))
    centers = amr.grid.geometry.get_center(cells[lvl == 1])
    r = np.sqrt((centers[:, 0] - 0.25) ** 2 + (centers[:, 1] - 0.5) ** 2)
    assert r.min() < 0.2
    # far corner stays coarse
    far = amr.grid.geometry.get_center(cells[lvl == 0])
    assert len(far) > 0


def test_fused_loop_matches_stepwise():
    """run_fused(n) (one device program, exchange+flux+apply inside
    lax.fori_loop) must reproduce n individual step() calls — on a
    refined grid so the fused exchange covers AMR gather tables."""
    a = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(4))
    b = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(4))
    a.adapt()
    b.adapt()
    dt = 0.4 * a.max_time_step()
    for _ in range(5):
        a.step(dt)
    b.run_fused(5, dt)
    cells = a.grid.get_cells()
    np.testing.assert_allclose(
        a.grid.get("density", cells), b.grid.get("density", cells),
        rtol=1e-6, atol=1e-7,
    )
    assert a.time == pytest.approx(b.time)


def test_run_fused_segments_match_run_stepwise():
    """run(fused=True) with adaptation events must match fused=False."""
    a = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(2))
    b = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(2))
    a.run(6, adapt_n=3, fused=False)
    b.run(6, adapt_n=3, fused=True)
    ca, cb = a.grid.get_cells(), b.grid.get_cells()
    np.testing.assert_array_equal(ca, cb)
    np.testing.assert_allclose(
        a.grid.get("density", ca), b.grid.get("density", cb),
        rtol=1e-5, atol=1e-6,
    )


def test_mass_conserved_across_adaptation():
    """Refinement copies, unrefinement averages — both preserve total
    mass exactly (children have 1/8 the volume)."""
    amr = AmrAdvection((8, 8, 1), max_refinement_level=2, mesh=mesh_of(2))
    m0 = amr.total_mass()
    amr.adapt()
    assert amr.total_mass() == pytest.approx(m0, rel=1e-5)
    for _ in range(3):
        amr.step()
    amr.adapt()
    m_now = amr.total_mass()
    # stepping conserves mass; adaptation conserves mass
    assert m_now == pytest.approx(m0, rel=1e-4)


def test_full_loop_with_balance():
    """The reference main loop: solve + adapt every 2 + balance every 4
    (2d.cpp:321-442); mass conserved, density stays bounded."""
    amr = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(4))
    m0 = amr.total_mass()
    amr.run(8, adapt_n=2, balance_n=4)
    assert amr.total_mass() == pytest.approx(m0, rel=1e-4)
    cells = amr.grid.get_cells()
    rho = amr.grid.get("density", cells)
    assert rho.min() >= -1e-5
    assert rho.max() <= 0.55


def test_long_loop_deep_refinement():
    """Longer run at max level 2: repeated adapts must never commit a
    structure violating the 2:1 invariant (regression: the unrefine
    check must use the parent's window, not the children's — a finer
    cell 2 child-lengths away blocks unrefinement)."""
    amr = AmrAdvection((12, 12, 1), max_refinement_level=2, mesh=mesh_of(8))
    m0 = amr.total_mass()
    amr.run(12, adapt_n=3, balance_n=6)  # raises StructureError on violation
    assert amr.total_mass() == pytest.approx(m0, rel=1e-4)
    lvl = amr.grid.mapping.get_refinement_level(amr.grid.get_cells())
    assert lvl.max() == 2


def test_device_count_invariance_with_amr():
    """Same physics on 1 vs 8 devices, including through adaptation
    (tests/README:5-6: any process count must agree)."""
    out = []
    for n in (1, 8):
        amr = AmrAdvection((8, 8, 1), max_refinement_level=1, mesh=mesh_of(n))
        dt = 0.4 * amr.max_time_step()
        for i in range(4):
            amr.step(dt)
            if i % 2 == 1:
                amr.adapt()
        cells = amr.grid.get_cells()
        out.append((cells, amr.grid.get("density", cells).astype(np.float64)))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    np.testing.assert_allclose(out[0][1], out[1][1], rtol=1e-5, atol=1e-6)


def test_adapt_epochs_reuse_compiled_programs():
    """Bucketed capacities + shape-keyed program caches: once warmed,
    further adapt epochs with stable buckets must reuse every compiled
    exchange/stencil/step-loop program instead of recompiling (on TPU a
    recompile is tens of seconds per epoch)."""
    amr = AmrAdvection((32, 32, 1), max_refinement_level=1, mesh=mesh_of(4))
    g = amr.grid
    # one full warm cycle: fused steps + one adapt epoch. dt=0 keeps
    # the density static so every later adapt reproduces the same
    # refinement pattern — drift-free, isolating the machinery.
    amr.run_fused(4, dt=0.0)
    amr.adapt()
    amr.run_fused(4, dt=0.0)
    amr.adapt()
    caps_before = dict(g._cap_memo)
    keys_before = set(g._program_cache)
    for _ in range(3):  # three more structure epochs
        amr.run_fused(4, dt=0.0)
        amr.adapt()
    assert dict(g._cap_memo) == caps_before, "capacities flapped"
    new = set(g._program_cache) - keys_before
    assert not new, f"programs recompiled: {[k[0] for k in new]}"


def test_drifting_partial_writes_reuse_one_scatter_program():
    """Partial writes above the small tier (an adapt epoch's projection
    at a large base) land in a grow-only sticky capacity: once it has
    grown, writes that drift in size add no scatter program."""
    amr = AmrAdvection((128, 128, 1), max_refinement_level=0,
                       mesh=mesh_of(1))
    g = amr.grid
    cells = g.get_cells()

    def scatters():
        return {k for k in g._program_cache if k[0] == "devscatter"}

    for n in (5000, 7000):  # the first bucket, then one growth
        g.set("density", cells[:n], np.ones(n, np.float32))
    before = scatters()
    for n in (4500, 9000, 6000, 13000):
        g.set("density", cells[:n], np.full(n, 2.0, np.float32))
    assert scatters() == before
    np.testing.assert_array_equal(g.get("density", cells[:13000]), 2.0)
    np.testing.assert_array_equal(g.get("density", cells[13000:]), 0.0)


# -- against the plain reference ---------------------------------------
# The AMR cell's driver holds the plain reference (numpy, importing
# nothing of dccrg_tpu) and the moves between the grid and its fine
# lattice; the tests load that one copy by path.

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
_spec = importlib.util.spec_from_file_location(
    "advection_amr_driver", BENCH / "drivers" / "advection_amr.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)
CONFIG = json.loads(
    (BENCH / "configs" / "advection3d-amr1-64.json").read_text())
CRITERION = ref.criterion(CONFIG)
BASE = 8
CYCLES = 3
STEPS = 4


def _amr_cycles(g, dtype=jnp.float32, n=BASE):
    """Image ``g`` refined as the reference refines it, then CYCLES of
    ``run_fused(STEPS)`` and ``adapt()`` on one device. Returns per
    epoch (cells, hard rows, created, removed, level mask) and the
    final fine-lattice density."""
    field = ref.image(ref.initial_density(n), g)
    amr = AmrAdvection((n, n, n), max_refinement_level=1, mesh=mesh_of(1),
                       dtype=dtype, **CRITERION)
    ref.put_density(amr.grid, ref.coarsen(field, np.zeros((n, n, n), np.int8)))
    amr.adapt()
    ref.put_density(amr.grid, field)
    dt = ref.time_step(n, CONFIG["cfl"])
    epochs = []
    for _ in range(CYCLES):
        amr.run_fused(STEPS, dt)
        created, removed = amr.adapt()
        rho, level = ref.take_density(amr.grid, n)
        epochs.append((len(amr.grid.get_cells()), amr.hard_rows(),
                       len(created), len(removed), level))
    return epochs, rho


def _reference_cycles(g, n=BASE):
    rho, level = ref.initial_state(ref.image(ref.initial_density(n), g),
                                   **CRITERION)
    dt = ref.time_step(n, CONFIG["cfl"])
    epochs = []
    for _ in range(CYCLES):
        for _ in range(STEPS):
            rho = ref.step(rho, level, dt)
        rho, level, created, removed = ref.adapt(rho, level, **CRITERION)
        epochs.append((ref.cell_count(level), created, removed, level))
    return epochs, rho


@pytest.fixture(scope="module")
def identity_run():
    return _amr_cycles(0)


def test_cycles_match_plain_reference(identity_run):
    """3 cycles of run_fused(4) and adapt() against the plain reference:
    the same roots refined at every epoch, the same counts, and the
    density within the configuration's limit."""
    epochs, rho = identity_run
    want, want_rho = _reference_cycles(0)
    for (cells, _, created, removed, level), (w_cells, w_cr, w_rm, w_level) \
            in zip(epochs, want):
        assert (cells, created, removed) == (w_cells, w_cr, w_rm)
        np.testing.assert_array_equal(level, w_level)
    assert any(e[1] for e in epochs), "no hard rows: nothing refined"
    assert np.max(np.abs(rho - want_rho)) <= CONFIG["limit"]["max_abs_err"]


def test_bf16_control_fails_the_comparison():
    """The configuration's control (bfloat16 density) must fail the
    comparison the float32 run passes."""
    assert CONFIG["control"] == {"dtype": "bfloat16"}
    epochs, rho = _amr_cycles(0, dtype=jnp.bfloat16)
    want, want_rho = _reference_cycles(0)
    mismatch = int(np.sum(epochs[-1][-1] != want[-1][-1]))
    err = float(np.max(np.abs(rho - want_rho)))
    assert mismatch > CONFIG["limit"]["level_mismatch"] \
        or err > CONFIG["limit"]["max_abs_err"]


@pytest.mark.parametrize("g", range(1, 8))
def test_seed_images_do_the_same_work(identity_run, g):
    """Every seed image refines, coarsens and steps the same number of
    cells, hard rows included, at every epoch, and its refined set is
    the image of the identity's."""
    base, _ = identity_run
    epochs, _ = _amr_cycles(g)
    assert [e[:4] for e in epochs] == [e[:4] for e in base]
    for e, b in zip(epochs, base):
        np.testing.assert_array_equal(e[4], ref.image(b[4], g))
