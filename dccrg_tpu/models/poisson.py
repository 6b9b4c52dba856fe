"""Poisson solver on the distributed grid.

Equivalent of the reference's tests/poisson solver family
(tests/poisson/poisson_solve.hpp): the Numerical-Recipes 2.7.6
biconjugate scheme over grid cells, with per-cell per-direction
geometry factors so the same solver covers uniform, AMR, and stretched
grids, plus boundary (Dirichlet) cells and skipped cells
(poisson_solve.hpp:222-258's cells / cells_to_skip / boundary
classification).

Fidelity notes:

- Geometry factors: per direction, the offset to the face neighbor's
  center is half_own + half_neighbor (missing or skipped neighbors act
  as equal-size cells with no coupling); f_dir = ±2/(offset · total)
  and the diagonal is -Σf (set_scaling_factor,
  poisson_solve.hpp:691-830). A direction with 4 finer face neighbors
  applies f/4 to each (:332-338).
- The matrix is asymmetric under AMR, so the solve iterates both A·p0
  and transpose(A)·p1 — the transpose using the *neighbor's* factor of
  the opposite direction (:422-466).
- The reference iterates `update_copies_of_remote_neighbors` on a
  sub-selection of fields chosen by ``Poisson_Cell::transfer_switch``
  (poisson_solve.hpp:47-141); here that boundary is the ``fields``
  argument of the halo update — each iteration moves only p0/p1,
  factors move once at preparation (the GEOMETRY transfer, :968-970).
- Global dot products (MPI_Allreduce, :341-349) are jnp reductions
  over the sharded fields: XLA inserts the all-reduce.
- Cells neither solved nor skipped are boundary cells: their solution
  feeds the initial residual (Dirichlet data, initialize_solver
  :986-1041) and is never changed.

``DensePoissonSolver`` is the uniform fast path on DenseGrid for
large problems (the serial reference solver's role,
tests/poisson/reference_poisson_solve.hpp, doubles as the parity
check).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..grid import DEFAULT_NEIGHBORHOOD_ID, Grid
from ..dense import DenseGrid
from ..neighbors import face_masks, make_neighborhood

POISSON_NEIGHBORHOOD_ID = 0xB01550

# cell_type values (poisson_solve.hpp:143-149)
SOLVE_CELL, BOUNDARY_CELL, SKIP_CELL = 1, 0, -1

def poisson_fields(dtype=jnp.float32):
    """The solver's field spec at a given float width. The reference
    solver is double-precision throughout (poisson_solve.hpp:47-141);
    ``poisson_fields(jnp.float64)`` is the parity mode (CPU: tests run
    with JAX_ENABLE_X64). TPU runs use float32: expect the residual
    floor near 1e-6 relative instead of 1e-12 — see
    tests/test_poisson.py::test_f64_parity_mode for the measured
    budget."""
    f = jnp.dtype(dtype)
    return {
        "rhs": f, "solution": f,
        "r0": f, "r1": f,
        "p0": f, "p1": f, "Ap0": f,
        "fxp": f, "fxn": f,
        "fyp": f, "fyn": f,
        "fzp": f, "fzn": f,
        "scale": f, "ctype": jnp.int32, "ilen": jnp.int32,
    }


POISSON_FIELDS = poisson_fields(jnp.float32)

_F_NAMES = (("fxp", "fxn"), ("fyp", "fyn"), ("fzp", "fzn"))
_GEOMETRY_FIELDS = [n for pair in _F_NAMES for n in pair] + ["scale", "ctype", "ilen"]


def _matvec_kernel(transpose: bool):
    """A·p (or transpose(A)·p) over face neighbors
    (poisson_solve.hpp:296-338 forward, :422-466 transpose)."""
    src = "p1" if transpose else "p0"

    def kernel(cell, nbr, offs, mask):
        p_c = cell[src]
        p_n = nbr[src]
        faces = face_masks(cell["ilen"][:, None], nbr["ilen"], offs, mask)
        if transpose:
            # transpose reads A[n, c]: the /4 averaging applies when
            # THIS cell is the finer side of n's face (:463-466)
            finer = cell["ilen"][:, None] < nbr["ilen"]
        else:
            # finer face neighbors: 4 per direction, each weighted f/4
            finer = nbr["ilen"] < cell["ilen"][:, None]
        w = jnp.where(finer, 0.25, 1.0) * (nbr["ctype"] != SKIP_CELL)
        acc = cell["scale"] * p_c
        for d, (face_pos, face_neg) in enumerate(faces):
            if transpose:
                # neighbor's factor of the opposite direction (:436-455)
                m_pos = nbr[_F_NAMES[d][1]]
                m_neg = nbr[_F_NAMES[d][0]]
            else:
                m_pos = cell[_F_NAMES[d][0]][:, None]
                m_neg = cell[_F_NAMES[d][1]][:, None]
            acc = acc + jnp.sum(jnp.where(face_pos, m_pos * w * p_n, 0.0), axis=1)
            acc = acc + jnp.sum(jnp.where(face_neg, m_neg * w * p_n, 0.0), axis=1)
        return {"out": acc}

    def wrapped(cell, nbr, offs, mask):
        out = kernel(cell, nbr, offs, mask)
        # only solve cells carry the result; others stay 0
        return {("r1" if transpose else "Ap0"):
                jnp.where(cell["ctype"] == SOLVE_CELL, out["out"], 0.0)}

    return wrapped


class PoissonSolver:
    """Biconjugate Poisson solve on the general (AMR-capable) grid.

    Either wraps an existing grid declared with POISSON_FIELDS (the
    reference solver is grid-agnostic the same way,
    poisson_solve.hpp:252-258) or builds a uniform one from ``length``.
    """

    def __init__(self, length=None, mesh=None, periodic=(True, True, True),
                 dtype=jnp.float32, grid: Grid | None = None,
                 max_refinement_level: int = 0):
        if grid is not None:
            self.grid = grid
        else:
            self.grid = (
                Grid(cell_data=poisson_fields(dtype))
                .set_initial_length(length)
                .set_periodic(*periodic)
                .set_maximum_refinement_level(max_refinement_level)
                .set_neighborhood_length(1)
                .initialize(mesh)
            )
        missing = [n for n in POISSON_FIELDS if n not in self.grid.fields]
        if missing:
            raise ValueError(f"grid lacks Poisson fields {missing}")
        self.dtype = self.grid.fields["solution"][1]
        self._np_dtype = np.dtype(self.dtype)
        if POISSON_NEIGHBORHOOD_ID not in self.grid.neighborhoods:
            self.grid.add_neighborhood(POISSON_NEIGHBORHOOD_ID, make_neighborhood(0))
        self._fwd = _matvec_kernel(transpose=False)
        self._tr = _matvec_kernel(transpose=True)
        self._prepared_epoch = None
        self._solve_mask = None

    def _cache_key(self, cells_to_solve, cells_to_skip):
        return (
            self.grid.plan.epoch,
            None if cells_to_solve is None
            else np.asarray(cells_to_solve, np.uint64).tobytes(),
            None if cells_to_skip is None
            else np.asarray(cells_to_skip, np.uint64).tobytes(),
        )

    # -- field setup ---------------------------------------------------

    def set_rhs(self, values) -> None:
        cells = self.grid.get_cells()
        self.grid.set("rhs", cells, np.asarray(values, dtype=self._np_dtype))

    def set_rhs_from(self, fn) -> None:
        """rhs from a function of cell centers."""
        cells = self.grid.get_cells()
        centers = self.grid.geometry.get_center(cells)
        self.set_rhs(fn(centers[:, 0], centers[:, 1], centers[:, 2]))

    def solution(self) -> np.ndarray:
        return self.grid.get("solution", self.grid.get_cells())

    # -- preparation (cache_system_info, poisson_solve.hpp:838-970) ----

    def prepare(self, cells_to_solve=None, cells_to_skip=None) -> None:
        """Classify cells and compute geometry factors for the current
        structure epoch. Its seconds set the plan-phase gauge
        ``dccrg_plan_phase_seconds{phase="poisson_prepare"}``."""
        mark = telemetry.phase_timer()
        g = self.grid
        cells = g.get_cells()
        n = len(cells)

        def positions(ids, what):
            ids = np.asarray(ids, dtype=np.uint64)
            pos = np.searchsorted(cells, ids)
            bad = (pos >= n) | (cells[np.minimum(pos, n - 1)] != ids)
            if bad.any():
                raise ValueError(f"{what} contains unknown cell id(s): "
                                 f"{ids[bad][:5].tolist()}")
            return pos

        ctype = np.full(n, BOUNDARY_CELL, dtype=np.int32)
        if cells_to_solve is None:
            ctype[:] = SOLVE_CELL
        else:
            ctype[positions(cells_to_solve, "cells_to_solve")] = SOLVE_CELL
        if cells_to_skip is not None:
            pos = positions(cells_to_skip, "cells_to_skip")
            # solve wins over skip (poisson_solve.hpp:230-233)
            ctype[pos[ctype[pos] != SOLVE_CELL]] = SKIP_CELL

        lengths = g.geometry.get_length(cells).astype(np.float64)
        half = lengths / 2.0
        ilen = g.mapping.get_cell_length_in_indices(cells).astype(np.int64)

        # host face classification over the face-hood neighbor lists
        nl = g.plan.hoods[POISSON_NEIGHBORHOOD_ID].lists
        src, nbr_pos = nl.of_source, np.searchsorted(cells, nl.of_neighbor)
        offs = nl.of_offset
        ok = ctype[nbr_pos] != SKIP_CELL
        faces = face_masks(ilen[src], ilen[nbr_pos], offs, ok)
        # per (cell, direction, sign): non-skip face neighbor half size
        has = np.zeros((n, 3, 2), dtype=bool)
        nbr_half = np.zeros((n, 3, 2), dtype=np.float64)
        for d in range(3):
            for s, mm in enumerate(faces[d]):
                has[src[mm], d, s] = True
                nbr_half[src[mm], d, s] = half[nbr_pos[mm], d]

        # offsets to neighbor centers; missing/skipped neighbors act as
        # equal-size cells (poisson_solve.hpp:716-723)
        pos_off = half + np.where(has[:, :, 0], nbr_half[:, :, 0], half)
        neg_off = half + np.where(has[:, :, 1], nbr_half[:, :, 1], half)
        tot = pos_off + neg_off
        f_pos = np.where(has[:, :, 0], 2.0 / (pos_off * tot), 0.0)
        f_neg = np.where(has[:, :, 1], 2.0 / (neg_off * tot), 0.0)
        scale = -(f_pos.sum(axis=1) + f_neg.sum(axis=1))

        for d in range(3):
            g.set(_F_NAMES[d][0], cells, f_pos[:, d].astype(self._np_dtype))
            g.set(_F_NAMES[d][1], cells, f_neg[:, d].astype(self._np_dtype))
        g.set("scale", cells, scale.astype(self._np_dtype))
        g.set("ctype", cells, ctype)
        g.set("ilen", cells, ilen.astype(np.int32))
        # the GEOMETRY transfer: factors valid for the whole epoch
        g.update_copies_of_remote_neighbors(
            neighborhood_id=POISSON_NEIGHBORHOOD_ID, fields=_GEOMETRY_FIELDS
        )

        self._solve_mask = g.local_row_mask().astype(
            jnp.dtype(self._np_dtype)
        ) * (g.data["ctype"] == SOLVE_CELL)
        self._prepared_epoch = self._cache_key(cells_to_solve, cells_to_skip)
        mark("poisson_prepare")

    # -- reductions ----------------------------------------------------

    def _dot(self, a: str, b: str) -> float:
        return float(jnp.sum(self.grid.data[a] * self.grid.data[b] * self._solve_mask))

    def _exchange_p(self, fields) -> None:
        self.grid.update_copies_of_remote_neighbors(
            neighborhood_id=POISSON_NEIGHBORHOOD_ID, fields=fields
        )

    def _apply(self, transpose: bool) -> None:
        fields_in = ["p1" if transpose else "p0", "ilen", "ctype", "scale"] + [
            n for pair in _F_NAMES for n in pair
        ]
        self.grid.apply_stencil(
            self._tr if transpose else self._fwd,
            fields_in,
            ["r1" if transpose else "Ap0"],
            neighborhood_id=POISSON_NEIGHBORHOOD_ID,
        )

    # -- solve (poisson_solve.hpp:252-523) -----------------------------

    def _fused_solve_fn(self):
        """The ENTIRE biconjugate solve as one XLA program: initial
        residual, then a lax.while_loop whose body fuses the p0/p1
        halo exchange, both matvecs, the three global dots (XLA
        all-reduces — the reference pays an MPI_Allreduce per
        iteration, poisson_solve.hpp:341-349) and the vector updates.
        No host round-trips until the result is read.

        Tables, static fields and the solve mask are ARGUMENTS of the
        compiled program (cached in the grid's shape-keyed program
        cache), so bucket-stable structure epochs reuse it instead of
        recompiling. Returns ``(program, bindings)``: the program is
        called as ``program(*state, *bindings)``.

        The program is named ``dccrg_poisson_solve`` (its module is
        ``jit_dccrg_poisson_solve``) and wraps each phase in
        ``jax.named_scope`` (metadata only): ``dccrg.matvec`` (both
        matvecs: gather, kernel, write-back), ``dccrg.dot`` (the global
        reductions), ``dccrg.update`` (alpha/beta, the vector updates and
        the selects on ``go``) and, on several devices, ``dccrg.exchange``
        (the p0/p1 halo update, before both matvecs)."""
        g = self.grid
        fields_in_fwd = ["p0", "ilen", "ctype", "scale"] + [
            n for pair in _F_NAMES for n in pair
        ]
        fields_in_tr = ["p1"] + fields_in_fwd[1:]
        fwd_fn, fwd_tables = g._make_stencil(
            self._fwd, tuple(fields_in_fwd), ("Ap0",),
            POISSON_NEIGHBORHOOD_ID, False)
        tr_fn, tr_tables = g._make_stencil(
            self._tr, tuple(fields_in_tr), ("r1",),
            POISSON_NEIGHBORHOOD_ID, False)
        _s1, _f1, fused1, _nt1 = g._exchange_programs(POISSON_NEIGHBORHOOD_ID, 1)
        sx1, rx1 = g._pair_tables_device(POISSON_NEIGHBORHOOD_ID, ("p0",))
        _s2, _f2, fused2, _nt2 = g._exchange_programs(POISSON_NEIGHBORHOOD_ID, 2)
        sx2, rx2 = g._pair_tables_device(POISSON_NEIGHBORHOOD_ID, ("p0", "p1"))
        statics = tuple(g.data[n] for n in fields_in_fwd[1:])
        mask = self._solve_mask
        single = g.n_dev == 1
        nf, nt = len(fwd_tables), len(tr_tables)
        n1, n2 = len(sx1) + len(rx1), len(sx2) + len(rx2)
        ns = len(statics)
        bindings = (*fwd_tables, *tr_tables, *sx1, *rx1, *sx2, *rx2,
                    mask, *statics)
        key = ("poisson_fused", self._fwd, self._tr, single,
               nf, nt, n1, n2, ns, g.plan.L, g.plan.R)
        prog = g._program_cache.get(key)
        if prog is not None:
            return prog, bindings

        def dccrg_poisson_solve(solution, rhs, scratch, rtol, max_iterations,
                                *rest):
            fwd_t = rest[:nf]
            tr_t = rest[nf:nf + nt]
            ex1 = rest[nf + nt:nf + nt + n1]
            ex2 = rest[nf + nt + n1:nf + nt + n1 + n2]
            mask = rest[nf + nt + n1 + n2]
            statics = rest[nf + nt + n1 + n2 + 1:]

            def fwd(*args):
                with jax.named_scope("dccrg.matvec"):
                    return fwd_fn(*fwd_t, *args)

            def tr(*args):
                with jax.named_scope("dccrg.matvec"):
                    return tr_fn(*tr_t, *args)

            def exchange1(p0):
                with jax.named_scope("dccrg.exchange"):
                    return fused1(*ex1, p0)

            def exchange2(p0, p1):
                with jax.named_scope("dccrg.exchange"):
                    return fused2(*ex2, p0, p1)

            def dot(a, b):
                with jax.named_scope("dccrg.dot"):
                    return jnp.sum(a * b * mask)

            # initial residual (initialize_solver, :986-1041)
            p0 = solution
            if not single:
                (p0,) = exchange1(p0)
            (Ap0,) = fwd(p0, *statics, scratch)
            with jax.named_scope("dccrg.update"):
                r0 = (rhs - Ap0) * mask
            dot_r0 = dot(r0, r0)
            b2 = dot(rhs, rhs)
            with jax.named_scope("dccrg.dot"):
                target = jnp.maximum(
                    rtol * rtol * jnp.maximum(jnp.maximum(b2, dot_r0), 1e-30),
                    1e-30,
                )

            def cond(s):
                return s["go"] & (s["residual"] > target) & (
                    s["it"] < max_iterations
                )

            def body(s):
                p0, p1 = s["p0"], s["p1"]
                if not single:
                    p0, p1 = exchange2(p0, p1)
                (Ap0,) = fwd(p0, *statics, s["Ap0"])
                (Atp1,) = tr(p1, *statics, s["r1"])
                dot_p = dot(p1, Ap0)
                with jax.named_scope("dccrg.update"):
                    go = (dot_p != 0) & (s["dot_r"] != 0)
                    safe_p = jnp.where(dot_p == 0, 1, dot_p)
                    alpha = jnp.where(go, s["dot_r"] / safe_p, 0.0)
                    solution = s["solution"] + alpha * p0 * mask
                    r0 = s["r0"] - alpha * Ap0 * mask
                    r1 = s["r1"] - alpha * Atp1 * mask
                new_dot_r = dot(r0, r1)
                with jax.named_scope("dccrg.update"):
                    safe_r = jnp.where(s["dot_r"] == 0, 1, s["dot_r"])
                    beta = jnp.where(go, new_dot_r / safe_r, 0.0)
                    p0 = (r0 + beta * p0) * mask
                    p1 = (r1 + beta * p1) * mask
                    out = {
                        "solution": jnp.where(go, solution, s["solution"]),
                        "r0": jnp.where(go, r0, s["r0"]),
                        "r1": jnp.where(go, r1, s["r1"]),
                        "p0": jnp.where(go, p0, s["p0"]),
                        "p1": jnp.where(go, p1, s["p1"]),
                        "Ap0": Ap0,
                        "dot_r": jnp.where(go, new_dot_r, s["dot_r"]),
                    }
                residual = dot(r0, r0)
                with jax.named_scope("dccrg.update"):
                    out["residual"] = jnp.where(go, residual, s["residual"])
                return {
                    **out,
                    "it": s["it"] + jnp.where(go, 1, 0),
                    "go": go,
                }

            init = {
                "solution": solution, "r0": r0, "r1": r0, "p0": r0,
                "p1": r0, "Ap0": Ap0, "dot_r": dot_r0, "residual": dot_r0,
                "it": jnp.int32(0), "go": jnp.bool_(True),
            }
            out = jax.lax.while_loop(cond, body, init)
            return out["solution"], out["it"], out["residual"]

        prog = jax.jit(dccrg_poisson_solve)
        g._program_cache[key] = prog
        return prog, bindings

    def solve(self, rtol: float = 1e-5, max_iterations: int = 1000,
              cells_to_solve=None, cells_to_skip=None,
              cache_is_up_to_date: bool = False, fused: bool = True) -> dict:
        g = self.grid
        # re-prepare only when the structure epoch or the cell
        # classification changed (the reference's cache_is_up_to_date
        # flag, poisson_solve.hpp:241-245, made automatic: the key
        # includes plan.epoch, which changes on refine/balance)
        del cache_is_up_to_date
        if self._cache_key(cells_to_solve, cells_to_skip) != self._prepared_epoch:
            self.prepare(cells_to_solve, cells_to_skip)
        mask = self._solve_mask
        # with no Dirichlet classification every boundary closure —
        # periodic wrap or missing-neighbor zero flux alike — is
        # Neumann, so the operator always has the constant nullspace
        singular = cells_to_solve is None and cells_to_skip is None
        if singular:
            self._remove_mean("rhs")

        if fused:
            solve_span = telemetry.span("poisson.solve")
            with solve_span:
                prog, bindings = self._fused_solve_fn()
                args = (self.grid.data["solution"], self.grid.data["rhs"],
                        self.grid.data["Ap0"],
                        jnp.asarray(rtol, dtype=self.dtype),
                        jnp.int32(max_iterations), *bindings)
                sol, it, residual = prog(*args)
                iterations = int(it)
            if solve_span is not telemetry.NULL_SPAN and telemetry.profiling():
                # the op -> phase table of the solve program, once per
                # program, as Grid.run_steps publishes the step program's
                telemetry.publish_scopes(prog, args)
            self.grid.data["solution"] = sol
            if singular:
                self._remove_mean("solution")
            self._count(iterations, max_iterations)
            return {"iterations": iterations,
                    "residual": float(np.sqrt(max(float(residual), 0.0)))}

        # r0 = rhs - A·solution, with boundary cells' solution as data
        # (initialize_solver, poisson_solve.hpp:986-1041)
        g.data["p0"] = g.data["solution"]
        self._exchange_p(["p0"])
        self._apply(transpose=False)
        g.data["r0"] = (g.data["rhs"] - g.data["Ap0"]) * mask
        g.data["r1"] = g.data["r0"]
        g.data["p0"] = g.data["r0"]
        g.data["p1"] = g.data["r0"]

        # r1 == r0 here, so one reduction serves all three initial dots
        dot_r = residual = r2_0 = self._dot("r0", "r0")
        b2 = self._dot("rhs", "rhs")
        # pure-Dirichlet/Laplace problems have zero rhs on solve cells;
        # fall back to the initial residual so rtol still applies
        target = max(rtol * rtol * max(b2, r2_0, 1e-30), 1e-30)
        iterations = 0
        while residual > target and iterations < max_iterations:
            self._exchange_p(["p0", "p1"])
            self._apply(transpose=False)
            dot_p = self._dot("p1", "Ap0")
            if dot_p == 0.0 or dot_r == 0.0:
                break
            alpha = dot_r / dot_p
            g.data["solution"] = g.data["solution"] + alpha * g.data["p0"] * mask
            g.data["r0"] = g.data["r0"] - alpha * g.data["Ap0"] * mask
            # r1 -= alpha · transpose(A)·p1 (:415-470); the kernel
            # writes A^T p1 into r1's slot, so stash r1 first
            r1_old = g.data["r1"]
            self._apply(transpose=True)
            g.data["r1"] = r1_old - alpha * g.data["r1"] * mask
            new_dot_r = self._dot("r0", "r1")
            beta = new_dot_r / dot_r
            g.data["p0"] = (g.data["r0"] + beta * g.data["p0"]) * mask
            g.data["p1"] = (g.data["r1"] + beta * g.data["p1"]) * mask
            dot_r = new_dot_r
            residual = self._dot("r0", "r0")
            iterations += 1
        if singular:
            self._remove_mean("solution")
        self._count(iterations, max_iterations)
        return {"iterations": iterations, "residual": float(np.sqrt(max(residual, 0.0)))}

    @staticmethod
    def _count(iterations: int, max_iterations: int) -> None:
        """The solve counters: BiCG iterations, and one solve labelled
        by whether it stopped before ``max_iterations``."""
        telemetry.inc("dccrg_poisson_iterations_total", iterations)
        telemetry.inc("dccrg_poisson_solves_total",
                      converged="true" if iterations < max_iterations else "false")

    def _remove_mean(self, field: str) -> None:
        """Subtract ``field``'s mean over the solve cells, in one device
        program (``dccrg_poisson_remove_mean``, its ops in scope
        ``dccrg.update``), so that a traced solve runs no op outside a
        published table."""
        g = self.grid
        prog = g._program_cache.get("poisson_remove_mean")
        if prog is None:
            def dccrg_poisson_remove_mean(x, mask):
                with jax.named_scope("dccrg.update"):
                    total = jnp.sum(x * mask)
                    cnt = jnp.maximum(jnp.sum(mask), 1.0)
                    return x - (total / cnt) * mask

            prog = jax.jit(dccrg_poisson_remove_mean)
            g._program_cache["poisson_remove_mean"] = prog
        args = (g.data[field], self._solve_mask)
        g.data[field] = prog(*args)
        if telemetry.profiling():
            telemetry.publish_scopes(prog, args)


class DensePoissonSolver:
    """CG on the dense fast path (uniform grids, big problems)."""

    def __init__(self, length, mesh=None, periodic=(True, True, True), dtype=jnp.float32):
        self.grid = DenseGrid(
            length,
            {"p": dtype, "Ap": dtype},
            mesh=mesh,
            periodic=periodic,
            cell_length=tuple(1.0 / l for l in length),
        )
        self.periodic = tuple(periodic)
        self.dtype = jnp.dtype(dtype)
        rdx2 = (1.0 / np.asarray(self.grid.cell_length) ** 2).astype(self.dtype)
        grid = self.grid

        def lap_kernel(b):
            from jax import lax
            from ..dense import AXES

            p = b["p"]
            core = tuple(slice(1, s - 1) for s in p.shape)
            nloc = tuple(s - 2 for s in p.shape)
            out = jnp.zeros_like(p[core])
            for d in range(3):
                lo = tuple(
                    slice(0 if dd == d else 1, (s - 2 if dd == d else s - 1))
                    for dd, s in enumerate(p.shape)
                )
                hi = tuple(
                    slice(2 if dd == d else 1, (s if dd == d else s - 1))
                    for dd, s in enumerate(p.shape)
                )
                t_lo = p[lo] - p[core]
                t_hi = p[hi] - p[core]
                if not grid.periodic[d]:
                    # homogeneous Neumann: drop missing-neighbor terms,
                    # matching PoissonSolver's masked stencil
                    pos = lax.axis_index(AXES[d])
                    g = pos * nloc[d] + lax.broadcasted_iota(jnp.int32, nloc, d)
                    t_lo = jnp.where(g > 0, t_lo, 0.0)
                    t_hi = jnp.where(g < grid.length[d] - 1, t_hi, 0.0)
                out = out + rdx2[d] * (t_lo + t_hi)
            return {"Ap": out}

        self._matvec = self.grid.make_step(lap_kernel, ("p",), ("Ap",), halo=1)

    def solve(self, rhs, rtol=1e-5, max_iterations=1000):
        def mv(p):
            arrays = {"p": p, "Ap": p}
            return self._matvec(arrays)["Ap"]

        return cg_solve(mv, rhs, singular=all(self.periodic),
                        dtype=self.dtype, rtol=rtol,
                        max_iterations=max_iterations)


def cg_solve(matvec, rhs, singular, dtype, rtol=1e-5, max_iterations=1000):
    """Plain conjugate gradients over an SPD ``matvec`` callable —
    shared by DensePoissonSolver (XLA dense step) and
    PallasPoissonSolver (Pallas kernel matvec). ``singular`` removes
    the constant null space (all-periodic Laplacian): the RHS and the
    solution are projected to zero mean."""
    rhs = jnp.asarray(rhs, dtype=dtype)
    if singular:
        rhs = rhs - jnp.mean(rhs)
    x = jnp.zeros_like(rhs)
    r = rhs
    p = r
    rs = float(jnp.sum(r * r))
    target = max(rtol * rtol * float(jnp.sum(rhs * rhs)), 1e-30)
    it = 0
    while rs > target and it < max_iterations:
        Ap = matvec(p)
        pAp = float(jnp.sum(p * Ap))
        if pAp == 0.0:
            break
        alpha = rs / pAp
        x = x + alpha * p
        r = r - alpha * Ap
        rs_new = float(jnp.sum(r * r))
        p = r + (rs_new / rs) * p
        rs = rs_new
        it += 1
    if singular:
        x = x - jnp.mean(x)
    return x, {"iterations": it, "residual": float(np.sqrt(max(rs, 0.0)))}
