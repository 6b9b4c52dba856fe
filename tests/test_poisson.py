"""Poisson solver tests (the reference's tests/poisson suite):
convergence against analytic solutions in 1-D/2-D/3-D, comparison with
a serial reference solve, and the multi-field transfer selection."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dccrg_tpu import telemetry
from dccrg_tpu.dense import dense_mesh
from dccrg_tpu.models.poisson import (
    POISSON_NEIGHBORHOOD_ID, DensePoissonSolver, PoissonSolver)


def mesh1(n):
    return Mesh(np.array(jax.devices()[:n]), ("dev",))


def discrete_rel_error(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_1d_periodic_analytic():
    n = 32
    s = PoissonSolver((n, 1, 1), mesh=mesh1(4), periodic=(True, False, False))
    cells = s.grid.get_cells()
    x = s.grid.geometry.get_center(cells)[:, 0] / n  # NoGeometry: unit cells
    u = np.sin(2 * np.pi * x)
    # the DISCRETE operator's eigenvalue makes the test exact up to CG
    # tolerance: A u = lam u for the unit-cell discrete Laplacian
    lam = -(2 - 2 * np.cos(2 * np.pi / n))
    rhs = lam * u
    s.set_rhs(rhs.astype(np.float32))
    info = s.solve(rtol=1e-6, max_iterations=500)
    got = s.solution()
    got -= got.mean()
    assert discrete_rel_error(got, u - u.mean()) < 1e-3, info


def test_2d_matches_serial_reference():
    """Multi-device solve equals the single-device (serial) solve — the
    reference's reference_poisson_solve comparison strategy."""
    n = 8
    rng = np.random.default_rng(3)
    rhs = rng.standard_normal(n * n).astype(np.float32)
    rhs -= rhs.mean()
    sols = []
    for ndev in (1, 8):
        s = PoissonSolver((n, n, 1), mesh=mesh1(ndev), periodic=(True, True, False))
        s.set_rhs(rhs)
        info = s.solve(rtol=1e-6, max_iterations=1000)
        x = s.solution()
        sols.append(x - x.mean())
    assert discrete_rel_error(sols[1], sols[0]) < 1e-3


def test_residual_actually_small():
    n = 8
    s = PoissonSolver((n, n, n), mesh=mesh1(8))
    rng = np.random.default_rng(0)
    rhs = rng.standard_normal(n**3).astype(np.float32)
    s.set_rhs(rhs)
    info = s.solve(rtol=1e-5, max_iterations=2000)
    # verify A x = rhs - mean(rhs) by recomputing the matvec
    g = s.grid
    g.data["p0"] = g.data["solution"]
    s._exchange_p(["p0"])
    s._apply(transpose=False)
    cells = g.get_cells()
    Ax = g.get("Ap0", cells)
    want = rhs - rhs.mean()
    assert np.linalg.norm(Ax - want) / np.linalg.norm(want) < 1e-3, info


def test_dirichlet_boundary_cells():
    """Cells neither solved nor skipped are boundary cells whose
    solution is fixed Dirichlet data (poisson_solve.hpp:236-239,
    reference tests/poisson/poisson2d_boundary.cpp). The factor scheme
    is exact for linear solutions."""
    n = 8
    s = PoissonSolver((n, 1, 1), mesh=mesh1(2), periodic=(False, False, False))
    cells = s.grid.get_cells()
    x = s.grid.geometry.get_center(cells)[:, 0]
    interior = cells[(x > 1) & (x < n - 1)]
    boundary = cells[(x < 1) | (x > n - 1)]
    # u = 3x + 1: zero rhs, boundary holds the exact values
    s.grid.set("solution", boundary,
               (3 * s.grid.geometry.get_center(boundary)[:, 0] + 1).astype(np.float32))
    s.set_rhs(np.zeros(len(cells), dtype=np.float32))
    info = s.solve(rtol=1e-8, max_iterations=500, cells_to_solve=interior)
    got = s.solution()
    np.testing.assert_allclose(got, 3 * x + 1, rtol=1e-4, atol=1e-3, err_msg=str(info))


def test_skip_cells_decouple():
    """Skipped cells act as missing neighbors and keep their data
    (poisson_solve.hpp:229-235, the reference's skip-cells variant)."""
    n = 9
    s = PoissonSolver((n, 1, 1), mesh=mesh1(2), periodic=(False, False, False))
    cells = s.grid.get_cells()
    x = s.grid.geometry.get_center(cells)[:, 0]
    mid = cells[len(cells) // 2]
    s.grid.set("solution", np.array([mid]), np.array([123.0], np.float32))
    solve = cells[cells != mid]
    rng = np.random.default_rng(5)
    rhs = rng.standard_normal(len(cells)).astype(np.float32)
    # each decoupled half is a pure-Neumann (singular) system: make the
    # rhs compatible per half so a solution exists
    half_l = x < x[len(cells) // 2]
    half_r = x > x[len(cells) // 2]
    rhs[half_l] -= rhs[half_l].mean()
    rhs[half_r] -= rhs[half_r].mean()
    s.set_rhs(rhs)
    info = s.solve(rtol=1e-6, max_iterations=500,
                   cells_to_solve=solve, cells_to_skip=[mid])
    # the skipped cell is untouched
    assert float(s.grid.get("solution", np.uint64(mid))) == 123.0
    # and fully decoupled: its rhs never influenced either half; check
    # by verifying the residual of the solved system directly
    g = s.grid
    g.data["p0"] = g.data["solution"]
    s._exchange_p(["p0"])
    s._apply(transpose=False)
    Ax = g.get("Ap0", solve)
    r = Ax - rhs[cells != mid]
    # pure-Neumann halves: each half's rhs mean is a nullspace offset;
    # remove per-half means before comparing
    left = s.grid.geometry.get_center(solve)[:, 0] < x[len(cells) // 2]
    for m in (left, ~left):
        r[m] -= r[m].mean()
    assert np.linalg.norm(r) / max(np.linalg.norm(rhs), 1e-9) < 1e-3, info


def test_amr_linear_exact():
    """AMR grid: factors across coarse-fine faces (f/4 per finer
    neighbor, poisson_solve.hpp:332-338) reproduce a linear solution
    exactly (reference tests/poisson refinement variants)."""
    s = PoissonSolver((4, 1, 1), mesh=mesh1(2), periodic=(False, False, False),
                      max_refinement_level=1)
    s.grid.refine_completely(2)
    s.grid.stop_refining()
    cells = s.grid.get_cells()
    x = s.grid.geometry.get_center(cells)[:, 0]
    exact = (2.0 * x - 1.0).astype(np.float32)
    lo, hi = x.min(), x.max()
    boundary = cells[(x == lo) | (x == hi)]
    interior = cells[(x != lo) & (x != hi)]
    s.grid.set("solution", boundary, exact[(x == lo) | (x == hi)])
    s.set_rhs(np.zeros(len(cells), dtype=np.float32))
    info = s.solve(rtol=1e-10, max_iterations=500, cells_to_solve=interior)
    np.testing.assert_allclose(s.solution(), exact, rtol=1e-3, atol=2e-3, err_msg=str(info))


def test_stretched_linear_exact():
    """Stretched-Cartesian geometry feeds the factors through
    geometry.get_length (reference tests/poisson stretched variant)."""
    coords_x = [0.0, 0.5, 1.5, 3.0, 5.0, 7.5]
    from dccrg_tpu.grid import Grid
    from dccrg_tpu.models.poisson import POISSON_FIELDS

    g = (
        Grid(cell_data=dict(POISSON_FIELDS))
        .set_initial_length((5, 1, 1))
        .set_neighborhood_length(1)
        .set_geometry("stretched", coordinates=[coords_x, [0.0, 1.0], [0.0, 1.0]])
        .initialize(mesh1(2))
    )
    s = PoissonSolver(grid=g)
    cells = g.get_cells()
    x = g.geometry.get_center(cells)[:, 0]
    exact = (0.5 * x + 2.0).astype(np.float32)
    boundary = cells[(x == x.min()) | (x == x.max())]
    interior = cells[(x != x.min()) & (x != x.max())]
    g.set("solution", boundary, exact[(x == x.min()) | (x == x.max())])
    s.set_rhs(np.zeros(len(cells), dtype=np.float32))
    info = s.solve(rtol=1e-10, max_iterations=200, cells_to_solve=interior)
    np.testing.assert_allclose(s.solution(), exact, rtol=1e-4, atol=1e-3, err_msg=str(info))


def test_dense_poisson_3d():
    n = 32
    mesh = dense_mesh(jax.devices()[:8], (2, 2, 2))
    s = DensePoissonSolver((n, n, n), mesh=mesh)
    x = (np.arange(n) + 0.5) / n
    u = (
        np.sin(2 * np.pi * x)[:, None, None]
        * np.sin(2 * np.pi * x)[None, :, None]
        * np.ones((1, 1, n))
    )
    rhs = -2 * (2 * np.pi) ** 2 * u
    sol, info = s.solve(jnp.asarray(rhs, jnp.float32), rtol=1e-6, max_iterations=800)
    got = np.array(sol)
    got -= got.mean()
    # discretization error dominates at n=32
    err = discrete_rel_error(got, u - u.mean())
    assert err < 0.02, (err, info)


def test_dense_matches_general_small():
    """Dense and general paths agree on the same problem."""
    n = 8
    rng = np.random.default_rng(1)
    rhs3 = rng.standard_normal((n, n, n)).astype(np.float32)
    rhs3 -= rhs3.mean()

    dense_sol, _ = DensePoissonSolver(
        (n, n, n), mesh=dense_mesh(jax.devices()[:1], (1, 1, 1))
    ).solve(jnp.asarray(rhs3), rtol=1e-6, max_iterations=2000)

    s = PoissonSolver((n, n, n), mesh=mesh1(1))
    # general grid orders cells by id: x fastest -> index (i,j,k) = id-1
    cells = s.grid.get_cells()
    idx = s.grid.mapping.get_indices(cells).astype(np.int64)
    rhs_flat = rhs3[idx[:, 0], idx[:, 1], idx[:, 2]]
    # general path uses unit cells (NoGeometry): rescale rhs by dx^-2
    # equivalence: A_unit u = dx^2 * A_dx u with dx = 1/n
    s.set_rhs(rhs_flat * np.float32((1.0 / n) ** 2))
    s.solve(rtol=1e-6, max_iterations=2000)
    gen = s.solution()
    dense_at = np.asarray(dense_sol)[idx[:, 0], idx[:, 1], idx[:, 2]]
    gen -= gen.mean()
    dense_at -= dense_at.mean()
    assert discrete_rel_error(gen, dense_at) < 1e-3


def test_f64_parity_mode():
    """The reference solver family is double precision
    (tests/poisson/reference_poisson_solve.hpp); poisson_fields(f64)
    is the parity mode, and the measured gap documents the f32 error
    budget: f64 converges ~6 orders of magnitude deeper."""
    import jax.numpy as jnp
    from dccrg_tpu.models.poisson import PoissonSolver

    def run(dtype):
        s = PoissonSolver(length=(16, 16, 1), mesh=mesh1(4), dtype=dtype,
                          periodic=(True, True, True))
        cells = s.grid.get_cells()
        centers = s.grid.geometry.get_center(cells)
        rhs = np.sin(2 * np.pi * centers[:, 0] / 16) * np.sin(
            2 * np.pi * centers[:, 1] / 16
        )
        s.set_rhs(rhs)
        s.solve(rtol=1e-12, max_iterations=400)
        sol = s.grid.get("solution", cells).astype(np.float64)
        # the rhs is a discrete eigenfunction: the 5-point Laplacian's
        # eigenvalue at mode k=1 on unit cells is 2(cos(2*pi/16)-1) per
        # dimension, so the exact discrete solution is rhs / eigenvalue
        lam = 2 * (np.cos(2 * np.pi / 16) - 1) * 2
        exact = rhs / lam
        sol -= sol.mean()
        exact -= exact.mean()
        return float(np.abs(sol - exact).max() / np.abs(exact).max())

    err64 = run(jnp.float64)
    err32 = run(jnp.float32)
    # f64 resolves the discrete solution to near machine precision,
    # f32 bottoms out around its rounding floor — the error budget a
    # TPU (f32) run should expect
    assert err64 < 1e-9, err64
    assert err64 < err32, (err64, err32)
    assert err32 < 1e-4, err32


def test_fused_solve_matches_host_loop():
    """The single-program lax.while_loop solve must walk the same
    Krylov trajectory as the host-driven loop (same ops, same order)."""
    import jax.numpy as jnp
    from dccrg_tpu.models.poisson import PoissonSolver

    def make():
        s = PoissonSolver(length=(8, 8, 4), mesh=mesh1(4),
                          periodic=(True, False, False),
                          max_refinement_level=1)
        g = s.grid
        g.refine_completely(1)
        g.stop_refining()
        cells = g.get_cells()
        centers = g.geometry.get_center(cells)
        rng = np.random.default_rng(0)
        s.set_rhs(np.sin(centers[:, 0]) + 0.1 * rng.random(len(cells)))
        # Dirichlet boundary: first level-0 plane
        solve = cells[centers[:, 1] > 1.5]
        return s, solve

    s1, solve1 = make()
    out1 = s1.solve(rtol=1e-6, max_iterations=60, cells_to_solve=solve1,
                    fused=True)
    s2, solve2 = make()
    out2 = s2.solve(rtol=1e-6, max_iterations=60, cells_to_solve=solve2,
                    fused=False)
    assert out1["iterations"] == out2["iterations"]
    # f32 reduction orders differ between the fused and host programs
    np.testing.assert_allclose(out1["residual"], out2["residual"],
                               rtol=5e-2, atol=1e-10)
    np.testing.assert_allclose(s1.solution(), s2.solution(),
                               rtol=5e-4, atol=5e-6)


def _fft_solve(b):
    """The zero-mean solution of the unit-cell periodic 7-point system
    ``A x = b`` on a [z, y, x] array, exactly, in float64: A's
    eigenvalue at wave numbers k is sum_d (2 cos(2 pi k_d / n) - 2)."""
    lam = sum((2 * np.cos(2 * np.pi * np.fft.fftfreq(n)) - 2).reshape(
        [n if a == d else 1 for a in range(3)]) for d, n in enumerate(b.shape))
    lam.flat[0] = 1.0
    xk = np.fft.fftn(b) / lam
    xk.flat[0] = 0.0
    return np.real(np.fft.ifftn(xk))


@pytest.mark.parametrize("n_dev", [1, 4])
def test_periodic_solve_matches_fft_reference(n_dev):
    """The fused BiCG solve at 16^3, periodic in all three axes, from a
    seeded rhs, against the float64 FFT solution of the same system."""
    n, rtol = 16, 1e-5
    s = PoissonSolver((n, n, n), mesh=mesh1(n_dev))
    rhs = np.random.default_rng(20251015).standard_normal(n**3)
    s.set_rhs(rhs.astype(np.float32))
    info = s.solve(rtol=rtol, max_iterations=1000)
    assert 0 < info["iterations"] < 1000
    b = (rhs - rhs.mean()).reshape(n, n, n)  # cells in id order: x fastest
    want = _fft_solve(b).ravel()
    got = s.solution().astype(np.float64)
    # the solve stops at ||r|| <= rtol ||b||; with the constant mode
    # removed, ||x - x*|| / ||x*|| <= cond(A) ||r|| / ||b||, and cond(A)
    # = 12 / (4 sin^2(pi / n)) = 78.8 at n = 16 (A's largest over its
    # smallest nonzero eigenvalue); a factor 2 for float32 rounding of
    # the residual the loop tracks
    cond = 12 / (4 * np.sin(np.pi / n) ** 2)
    assert discrete_rel_error(got, want) < 2 * cond * rtol, info


def _gather_programs():
    """Slot-wise programs built since the registry's last reset, by the
    kind of their neighbor gather."""
    return {k: telemetry.registry().counter_value(
        "dccrg_slot_gather_programs_total", gather=k)
        for k in ("roll3d", "slab3d", "roll_fixup", "table")}


@pytest.mark.parametrize("n_dev", [1, 4])
def test_amr_matvec_adjoint_identity(n_dev):
    """On a refined grid the two matvecs of the host loop are each
    other's transpose: <q, A p> = <A^T q, p> for seeded p, q in float64.
    The hybrid plan runs the table slot gather plus the hard-row pass
    over the faces between levels."""
    telemetry.registry().reset()
    s = PoissonSolver((6, 6, 6), mesh=mesh1(n_dev), dtype=jnp.float64,
                      max_refinement_level=1)
    g = s.grid
    for c in (1, 44, 130, 216):
        g.refine_completely(c)
    g.stop_refining()
    s.prepare()
    assert g.plan.hoods[POISSON_NEIGHBORHOOD_ID].hard_nbr_rows is not None
    cells = g.get_cells()
    assert len(cells) > 216
    p, q = np.random.default_rng(20261018).standard_normal((2, len(cells)))
    g.set("p0", cells, p)
    g.set("p1", cells, q)
    s._exchange_p(["p0", "p1"])
    s._apply(transpose=False)
    s._apply(transpose=True)
    q_ap = float(q @ g.get("Ap0", cells))
    atq_p = float(g.get("r1", cells) @ p)
    assert abs(q_ap - atq_p) <= 1e-12 * abs(q_ap), (q_ap, atq_p)
    counts = _gather_programs()
    assert counts.pop("table") == 2 and not any(counts.values()), counts


@pytest.mark.parametrize("n_dev, shape, kind", [
    (1, (8, 6, 4), "roll3d"),
    (4, (8, 6, 4), "table"),
    (4, (4, 4, 8), "roll_fixup"),
])
def test_uniform_matvec_matches_seven_point_stencil(n_dev, shape, kind):
    """On a uniform periodic grid of unit cells A p is numpy's 7-point
    stencil of p, through the slot gather the plan picks."""
    telemetry.registry().reset()
    s = PoissonSolver(shape, mesh=mesh1(n_dev), dtype=jnp.float64)
    g = s.grid
    s.prepare()
    cells = g.get_cells()
    p = np.random.default_rng(7).standard_normal(len(cells))
    g.set("p0", cells, p)
    s._exchange_p(["p0"])
    s._apply(transpose=False)
    counts = _gather_programs()
    assert counts.pop(kind) == 1 and not any(counts.values()), counts
    p3 = p.reshape(shape[::-1])  # cells in id order: x fastest
    want = sum(np.roll(p3, 1, a) + np.roll(p3, -1, a) for a in range(3)) - 6 * p3
    np.testing.assert_allclose(g.get("Ap0", cells), want.ravel(),
                               rtol=0, atol=1e-12)
