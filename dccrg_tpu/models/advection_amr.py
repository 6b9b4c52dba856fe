"""Adaptive advection: the reference advection test's full loop —
upwind finite-volume fluxes over AMR face neighbors, the relative-
density-difference adaptation criterion, and periodic load balancing
(tests/advection/2d.cpp:321-442, solve.hpp:44-333, adapter.hpp:47-311)
— on the general distributed grid.

TPU-first formulation: the reference's per-cell scatter loop (visit
each face once, update both sides, solve.hpp:166-234) becomes a
*gather* kernel — every cell accumulates its own flux from all of its
face neighbors, so each face is evaluated twice (once per side) with
identical face velocity / area / upwind density, which keeps the scheme
conservative while staying embarrassingly parallel for the MXU/VPU.
Face detection is the reference's offset arithmetic
(solve.hpp:76-120): a neighbor at logical offset ``o`` with index
length ``nl`` is a face neighbor in dimension d when ``o_d`` equals the
cell's index length (+d side) or ``-nl`` (-d side) and the windows
overlap in both other dimensions.

Static per-cell quantities (edge lengths, velocities at the center,
index length) are fields refreshed once per structure epoch and halo-
exchanged once, so the per-step exchange only moves density (the
reference's transfer-count trick, tests/advection/cell.hpp:31-55).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..grid import DEFAULT_NEIGHBORHOOD_ID, Grid
from ..neighbors import face_masks

STATIC_FIELDS = ("vx", "vy", "vz", "lx", "ly", "lz", "ilen")


def velocity(centers: np.ndarray) -> np.ndarray:
    """Solid-body rotation about (0.5, 0.5) (solve.hpp:339-346)."""
    v = np.zeros_like(centers)
    v[:, 0] = 0.5 - centers[:, 1]
    v[:, 1] = centers[:, 0] - 0.5
    return v


def hump(centers: np.ndarray, x0=0.25, y0=0.5, radius=0.15) -> np.ndarray:
    """Cosine hump initial density (tests/advection/initialize.hpp:54-66)."""
    r = np.minimum(
        np.sqrt((centers[:, 0] - x0) ** 2 + (centers[:, 1] - y0) ** 2), radius
    ) / radius
    return (1.0 + np.cos(np.pi * r)) / 4


def make_flux_kernel():
    """The upwind flux gather kernel (solve.hpp:44-266)."""

    def kernel(cell, nbr, offs, mask, dt):
        rho_c = cell["density"][:, None]
        rho_n = nbr["density"]
        ilen_c = cell["ilen"]
        ilen_n = nbr["ilen"]
        lens_c = [cell["lx"][:, None], cell["ly"][:, None], cell["lz"][:, None]]
        lens_n = [nbr["lx"], nbr["ly"], nbr["lz"]]
        vels_c = [cell["vx"][:, None], cell["vy"][:, None], cell["vz"][:, None]]
        vels_n = [nbr["vx"], nbr["vy"], nbr["vz"]]
        vol_c = (cell["lx"] * cell["ly"] * cell["lz"])[:, None]

        faces = face_masks(ilen_c[:, None], ilen_n, offs, mask)
        flux = jnp.zeros_like(rho_n)
        for d, (face_pos, face_neg) in enumerate(faces):
            # velocity interpolated to the shared face (solve.hpp:168-175)
            v = (lens_c[d] * vels_n[d] + lens_n[d] * vels_c[d]) / (
                lens_c[d] + lens_n[d] + 1e-30
            )
            o1, o2 = [e for e in range(3) if e != d]
            area = jnp.minimum(lens_c[o1] * lens_c[o2], lens_n[o1] * lens_n[o2])
            # +d face: positive v carries cell density out (solve.hpp:180-234)
            up_pos = jnp.where(v >= 0, rho_c, rho_n)
            up_neg = jnp.where(v >= 0, rho_n, rho_c)
            m = dt * v * area / vol_c
            flux = flux - jnp.where(face_pos, up_pos * m, 0.0)
            flux = flux + jnp.where(face_neg, up_neg * m, 0.0)
        return {"flux": jnp.sum(flux, axis=1)}

    return kernel


def make_fused_step_kernel():
    """Flux + apply in one kernel for the fused multi-step loop:
    returns the post-step density directly (solve.hpp:272-279 folded
    into the flux gather), so exchange+flux+apply is one XLA program
    per step under Grid.run_steps."""
    base = make_flux_kernel()

    def kernel(cell, nbr, offs, mask, dt):
        r = base(cell, nbr, offs, mask, dt)
        return {"density": cell["density"] + r["flux"]}

    return kernel


def make_diff_kernel(diff_threshold: float):
    """Max relative density difference over face neighbors
    (adapter.hpp:110-131)."""

    def kernel(cell, nbr, offs, mask):
        rho_c = cell["density"][:, None]
        rho_n = nbr["density"]
        faces = face_masks(cell["ilen"][:, None], nbr["ilen"], offs, mask)
        is_face = jnp.zeros(mask.shape, dtype=bool)
        for fp, fn in faces:
            is_face = is_face | fp | fn
        diff = jnp.abs(rho_c - rho_n) / (jnp.minimum(rho_c, rho_n) + diff_threshold)
        return {"max_diff": jnp.max(jnp.where(is_face, diff, 0.0), axis=1)}

    return kernel


class AmrAdvection:
    """The reference test's main program (tests/advection/2d.cpp):
    solve / adapt every ``adapt_n`` / balance every ``balance_n``.
    ``dtype`` is the storage dtype of the density; the static fields
    stay float32."""

    def __init__(self, length=(32, 32, 1), max_refinement_level=1, mesh=None,
                 cfl=0.5, diff_increase=0.02, diff_threshold=0.025,
                 unrefine_sensitivity=0.5, partition=None,
                 dtype=jnp.float32):
        self.cfl = cfl
        self.diff_increase = diff_increase
        self.diff_threshold = diff_threshold
        self.unrefine_sensitivity = unrefine_sensitivity
        cell_len = tuple(1.0 / n for n in length)
        self.grid = (
            Grid(cell_data={
                "density": dtype, "flux": jnp.float32,
                "max_diff": jnp.float32,
                "vx": jnp.float32, "vy": jnp.float32, "vz": jnp.float32,
                "lx": jnp.float32, "ly": jnp.float32, "lz": jnp.float32,
                "ilen": jnp.int32,
            })
            .set_initial_length(length)
            .set_maximum_refinement_level(max_refinement_level)
            .set_neighborhood_length(1)
            .set_geometry("cartesian", start=(0.0, 0.0, 0.0),
                          level_0_cell_length=cell_len)
            .initialize(mesh, partition=partition)
        )
        self._flux_kernel = make_flux_kernel()
        self._fused_kernel = make_fused_step_kernel()
        self._diff_kernel = make_diff_kernel(diff_threshold)
        self._refresh_static()
        cells = self.grid.get_cells()
        self.grid.set("density", cells,
                      hump(self.grid.geometry.get_center(cells)).astype(np.float32))
        self.time = 0.0

    @classmethod
    def from_grid(cls, grid, cfl=0.5, diff_increase=0.02,
                  diff_threshold=0.025, unrefine_sensitivity=0.5,
                  time=0.0):
        """Wrap an existing grid (e.g. one restored with
        ``Grid.from_file``) carrying this app's field schema — the
        restart path of the reference's advection test."""
        app = cls.__new__(cls)
        app.cfl = cfl
        app.diff_increase = diff_increase
        app.diff_threshold = diff_threshold
        app.unrefine_sensitivity = unrefine_sensitivity
        app.grid = grid
        app._flux_kernel = make_flux_kernel()
        app._fused_kernel = make_fused_step_kernel()
        app._diff_kernel = make_diff_kernel(diff_threshold)
        app._refresh_static()
        app.time = time
        return app

    # -- static per-epoch fields ---------------------------------------

    def _refresh_static(self) -> None:
        g = self.grid
        cells = g.get_cells()
        centers = g.geometry.get_center(cells)
        lengths = g.geometry.get_length(cells)
        v = velocity(centers)
        # one batched upload: static fields cover every cell, so the
        # old device arrays are never read back; the exchange below
        # re-fills the ghost rows for the whole epoch
        g.set_many(cells, {
            "vx": v[:, 0].astype(np.float32),
            "vy": v[:, 1].astype(np.float32),
            "vz": v[:, 2].astype(np.float32),
            "lx": lengths[:, 0].astype(np.float32),
            "ly": lengths[:, 1].astype(np.float32),
            "lz": lengths[:, 2].astype(np.float32),
            "ilen": g.mapping.get_cell_length_in_indices(cells).astype(np.int32),
        }, preserve_ghosts=False)
        g.update_copies_of_remote_neighbors(fields=list(STATIC_FIELDS))

    # -- time stepping (2d.cpp:321-343) --------------------------------

    def max_time_step(self) -> float:
        """Global CFL limit (solve.hpp:289-333). Depends only on the
        static per-epoch velocity/length fields, so it is computed once
        per structure epoch (one device reduction, one scalar pull)."""
        g = self.grid
        cached = getattr(self, "_cfl_cache", None)
        if cached is not None and cached[0] == g.plan.epoch:
            return cached[1]
        steps = []
        for lname, vname in (("lx", "vx"), ("ly", "vy"), ("lz", "vz")):
            l = g.data[lname]
            v = jnp.abs(g.data[vname])
            s = jnp.min(jnp.where(v > 0, l / jnp.maximum(v, 1e-30), jnp.inf))
            steps.append(float(s))
        dt = float(min(steps))
        self._cfl_cache = (g.plan.epoch, dt)
        return dt

    def step(self, dt: float | None = None) -> float:
        if dt is None:
            dt = self.cfl * self.max_time_step()
        g = self.grid
        g.update_copies_of_remote_neighbors(fields=["density"])
        g.apply_stencil(
            self._flux_kernel,
            ["density", "vx", "vy", "vz", "lx", "ly", "lz", "ilen"],
            ["flux"],
            extra_args=(jnp.float32(dt),),
        )
        # apply_fluxes (solve.hpp:272-279)
        g.data["density"] = g.data["density"] + g.data["flux"]
        g.data["flux"] = jnp.zeros_like(g.data["flux"])
        self.time += dt
        return dt

    def run_fused(self, n_steps: int, dt: float | None = None) -> float:
        """``n_steps`` advection steps as ONE jitted device program
        (exchange + flux + apply per step inside lax.fori_loop) — the
        hot path between structure events. dt is constant across the
        segment: the CFL limit depends only on the static per-epoch
        velocity/length fields (solve.hpp:289-333)."""
        if dt is None:
            dt = self.cfl * self.max_time_step()
        self.grid.run_steps(
            self._fused_kernel,
            ["density", "vx", "vy", "vz", "lx", "ly", "lz", "ilen"],
            ["density"],
            n_steps,
            extra_args=(jnp.float32(dt),),
        )
        self.time += n_steps * dt
        return dt

    # -- adaptation (adapter.hpp:47-311) -------------------------------

    def _flagged_cells(self) -> tuple:
        """Device-side adaptation criterion (adapter.hpp:47-178 runs it
        rank-locally; here it is one threshold reduction ON device): a
        per-row decision code is computed from max_diff and the level
        (recovered from ilen = 2^(max_lvl - lvl)) in one jitted
        program, and only the compact int8 code array crosses to the
        host — 1 byte/row instead of the f64 max_diff pull plus host
        level recomputation (VERDICT r3 item 5; a device-side
        ``jnp.nonzero(size=...)`` compaction was measured 3.4 s/call
        on the CPU mesh against <0.1 s for the int8 pull, so the
        host does the final nonzero on the byte array). Returns
        (ids, codes) with code 1=refine, 2=dont_unrefine,
        3=unrefine."""
        g = self.grid
        max_lvl = g.mapping.max_refinement_level
        if not hasattr(self, "_code_fn"):
            @jax.jit
            def _codes(diff, ilen, nl, inc, sens):
                rows = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
                local = rows < nl
                lvl = jnp.int32(max_lvl) - jnp.round(
                    jnp.log2(jnp.maximum(ilen, 1).astype(jnp.float32))
                ).astype(jnp.int32)
                refine_t = (lvl + 1).astype(jnp.float32) * inc
                unref_t = sens * refine_t
                code = jnp.where(
                    (diff > refine_t) & (lvl < max_lvl), 1,
                    jnp.where(
                        (diff < unref_t) & (lvl > 0), 3,
                        jnp.where(
                            (diff <= refine_t) & (diff >= unref_t)
                            & (lvl > 0), 2, 0),
                    ),
                )
                return jnp.where(local, code, 0).astype(jnp.int8)

            self._code_fn = _codes
        nl = jnp.asarray(np.asarray(g.plan.n_local)[:, None].astype(np.int32))
        code = np.asarray(self._code_fn(
            g.data["max_diff"], g.data["ilen"], nl,
            jnp.float32(self.diff_increase),
            jnp.float32(self.unrefine_sensitivity),
        ))
        d, row = np.nonzero(code)
        if len(d) == 0:
            return np.empty(0, np.uint64), np.empty(0, np.int8)
        codes = code[d, row]
        ids = np.empty(len(d), dtype=np.uint64)
        for dev in range(g.n_dev):
            m = d == dev
            if m.any():
                ids[m] = g.plan.local_ids[dev][row[m]]
        return ids, codes

    def adapt(self) -> tuple:
        """check_for_adaptation + adapt_grid: returns (created, removed).
        Host span ``amr.adapt`` (the plan rebuild inside it is
        ``grid.recommit``); counters ``dccrg_amr_epochs_total``,
        ``dccrg_amr_cells_created_total``, ``dccrg_amr_cells_removed_total``
        and the gauge ``dccrg_amr_hard_rows``, the hard rows of the new
        plan."""
        with telemetry.span("amr.adapt"):
            if self.grid.mapping.max_refinement_level == 0:
                return (np.empty(0, np.uint64), np.empty(0, np.uint64))
            created, removed = self._adapt()
            telemetry.inc("dccrg_amr_epochs_total")
            telemetry.inc("dccrg_amr_cells_created_total", len(created))
            telemetry.inc("dccrg_amr_cells_removed_total", len(removed))
            telemetry.set_gauge("dccrg_amr_hard_rows", self.hard_rows())
        return created, removed

    def hard_rows(self) -> int:
        """Rows of the plan's hard-row pass (cells near a change of
        refinement level; 0 on a plan without one)."""
        plan = self.grid.plan
        rows = plan.hoods[DEFAULT_NEIGHBORHOOD_ID].hard_rows
        return 0 if rows is None else int(np.sum(np.asarray(rows) < plan.L))

    def compute_max_diff(self) -> None:
        """The criterion stencil (adapter.hpp:47-178): every local
        cell's ``max_diff`` over its face neighbours, on the current
        plan. Writes nothing but ``max_diff``."""
        g = self.grid
        g.update_copies_of_remote_neighbors(fields=["density"])
        g.apply_stencil(
            self._diff_kernel, ["density", "ilen"], ["max_diff"]
        )

    def _adapt(self) -> tuple:
        g = self.grid
        self.compute_max_diff()
        ids, codes = self._flagged_cells()
        to_refine = ids[codes == 1]
        keep = ids[codes == 2]
        to_unrefine = ids[codes == 3]
        # conflict resolution between siblings is the grid's job
        # (refine_completely overrides sibling unrefines, dccrg.hpp:2517)
        for c in to_refine:
            g.refine_completely(c)
        for c in keep:
            g.dont_unrefine(c)
        for c in to_unrefine:
            g.unrefine_completely(c)
        created = g.stop_refining()
        removed = g.get_removed_cells()
        # project data across the structure change (adapter.hpp:229-301)
        g.assign_children_from_parents(fields=["density"])
        g.average_parents_from_children(fields=["density"])
        g.clear_refined_unrefined_data()
        self._refresh_static()
        g.data["flux"] = jnp.zeros_like(g.data["flux"])
        return created, removed

    # -- load balancing (2d.cpp:425-438) -------------------------------

    def balance(self) -> None:
        self.grid.balance_load()
        self._refresh_static()

    # -- diagnostics ---------------------------------------------------

    def total_mass(self) -> float:
        g = self.grid
        cells = g.get_cells()
        rho = g.get("density", cells).astype(np.float64)
        vol = np.prod(g.geometry.get_length(cells), axis=1)
        return float(np.sum(rho * vol))

    def run(self, steps: int, adapt_n: int = 0, balance_n: int = 0,
            fused: bool = True) -> None:
        """The main loop (2d.cpp:321-442). With ``fused`` (default) the
        steps between structure events run as one device program each
        (run_fused); otherwise one dispatch pair per step."""
        i = 0
        while i < steps:
            # next structure event bounds the fused segment
            nexts = [steps - i]
            if adapt_n:
                nexts.append(adapt_n - i % adapt_n)
            if balance_n:
                nexts.append(balance_n - i % balance_n)
            seg = min(nexts)
            if fused:
                self.run_fused(seg)
                i += seg
            else:
                self.step()
                i += 1
            if adapt_n and i % adapt_n == 0:
                self.adapt()
            if balance_n and i % balance_n == 0:
                self.balance()
