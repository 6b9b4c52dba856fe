"""Finite-volume ideal-MHD-style solver — the model zoo's multi-field
workload.

Eight coupled per-cell fields (the Vlasiator/dccrg shape: density,
momentum x3, total energy, magnetic field x3) advanced by two
operator-split passes with **different ghost dependencies**:

- the **hydro flux pass** — first-order Rusanov (local Lax-Friedrichs)
  fluxes of the Euler subsystem over face neighbors — reads ONLY the
  hydro fields' ghosts;
- the **CT/divergence-cleaning pass** — a conservative resistive
  smoothing of B over face neighbors (the diffusive limit of
  constrained-transport cleaning) — reads ONLY the B fields' ghosts.

Each pass exchanges only its own subsystem (``exchange_fields`` of
``Grid.run_steps``): the other subsystem's ghosts are not read by that
pass, so they do not move.

Modeling notes (honest simplifications):

- The Lorentz back-reaction on the momentum/energy equations is
  omitted and the induction stretching term is folded into the
  cleaning diffusivity, so each subsystem is EXACTLY conservative in
  real arithmetic — mass, momentum x3, energy and B x3 under periodic
  BCs — which is precisely the invariant surface the SDC defense
  consumes (``integrity.register_conserved("mhd", ...)``).
- Face fluxes are written so the two sides of a face compute
  bit-identical values (commutative-add flux averages, shared
  ``U_right - U_left`` dissipation term, symmetric ``max`` wave
  speed): the pairwise cancellation is exact, and the conservation
  sums drift only by reduction rounding — inside
  ``integrity.sum_tolerance`` by construction.
- Pressure and density are floored (``P_FLOOR``/``RHO_FLOOR``) inside
  the flux evaluation only: the update stays flux-form, so the floors
  never break conservation, they only keep the wave-speed finite on
  rough states (the fleet's seeded random inits).

The single fused kernel (:func:`make_mhd_kernel` — hydro AND cleaning
every step) is registered as the fleet kernel ``"mhd"``; the
two-pass form (:func:`make_mhd_pass_kernels`) drives
:class:`GridMHD`, the multi-device model class.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..grid import Grid

GAMMA = 5.0 / 3.0
ETA = 0.08          # B cleaning diffusivity (stability: lam*ETA*6 < 1)
P_FLOOR = 1.0e-6
RHO_FLOOR = 1.0e-3

MHD_HYDRO = ("rho", "mx", "my", "mz", "en")
MHD_BFIELD = ("bx", "by", "bz")
MHD_ALL = MHD_HYDRO + MHD_BFIELD

_f32 = jnp.float32


def mhd_cell_data(dtype=jnp.float32) -> dict:
    """The 8-field MHD schema (every field a scalar per cell)."""
    return {n: dtype for n in MHD_ALL}


def _widen(fields, names):
    return {n: fields[n].astype(_f32) for n in names}


def _euler_flux(U, d):
    """Euler flux along axis ``d`` plus the local max wave speed
    ``|v_d| + c``. Shapes follow the inputs ([L] cells or [L, S]
    neighbors)."""
    rho = jnp.maximum(U["rho"], _f32(RHO_FLOOR))
    inv = 1.0 / rho
    vx, vy, vz = U["mx"] * inv, U["my"] * inv, U["mz"] * inv
    ke = 0.5 * (U["mx"] * vx + U["my"] * vy + U["mz"] * vz)
    p = jnp.maximum(_f32(GAMMA - 1.0) * (U["en"] - ke), _f32(P_FLOOR))
    vd = (vx, vy, vz)[d]
    F = {
        "rho": U[("mx", "my", "mz")[d]],
        "mx": vd * U["mx"],
        "my": vd * U["my"],
        "mz": vd * U["mz"],
        "en": vd * (U["en"] + p),
    }
    md = ("mx", "my", "mz")[d]
    F[md] = F[md] + p
    speed = jnp.abs(vd) + jnp.sqrt(_f32(GAMMA) * p * inv)
    return F, speed


def _hydro_update(cell, nbr, offs, mask, lam):
    """One Rusanov step of the hydro subsystem: ``U += lam * sum of
    face fluxes`` with ``lam = dt/dx``. Reads hydro neighbor (ghost)
    values only."""
    U_c = _widen(cell, MHD_HYDRO)
    U_n = _widen(nbr, MHD_HYDRO)
    lam = _f32(lam)
    acc = {n: jnp.zeros_like(U_c[n]) for n in MHD_HYDRO}
    unit = jnp.sum(jnp.abs(offs), axis=-1) == 1
    for d in range(3):
        Fc, sc = _euler_flux(U_c, d)
        Fn, sn = _euler_flux(U_n, d)
        pos = mask & unit & (offs[..., d] == 1)
        neg = mask & unit & (offs[..., d] == -1)
        # the two sides of a face compute bit-identical fluxes: the
        # average is x+y either way, the dissipation term is always
        # (U_right - U_left), and max(a, b) == max(b, a)
        for n in MHD_HYDRO:
            cc = U_c[n][:, None]
            f_hi = (0.5 * (Fc[n][:, None] + Fn[n])
                    - 0.5 * jnp.maximum(sc[:, None], sn)
                    * (U_n[n] - cc))
            f_lo = (0.5 * (Fn[n] + Fc[n][:, None])
                    - 0.5 * jnp.maximum(sn, sc[:, None])
                    * (cc - U_n[n]))
            acc[n] = acc[n] + (jnp.sum(jnp.where(neg, f_lo, 0.0), axis=1)
                               - jnp.sum(jnp.where(pos, f_hi, 0.0),
                                         axis=1))
    return {n: U_c[n] + lam * acc[n] for n in MHD_HYDRO}


def _b_update(cell, nbr, offs, mask, lam):
    """One cleaning step of the B subsystem: conservative face
    smoothing ``B += lam * ETA * sum_faces (B_nbr - B)``. Reads B
    neighbor (ghost) values only."""
    lam = _f32(lam)
    unit = jnp.sum(jnp.abs(offs), axis=-1) == 1
    face = mask & unit
    out = {}
    for n in MHD_BFIELD:
        b_c = cell[n].astype(_f32)
        b_n = nbr[n].astype(_f32)
        s = jnp.sum(jnp.where(face, b_n - b_c[:, None], 0.0), axis=1)
        out[n] = b_c + lam * _f32(ETA) * s
    return out


def make_mhd_kernel():
    """The fused fleet kernel (registry name ``"mhd"``): hydro flux
    AND B cleaning every step, one parameter ``lam = dt/dx``."""

    def kernel(cell, nbr, offs, mask, lam):
        out = _hydro_update(cell, nbr, offs, mask, lam)
        out.update(_b_update(cell, nbr, offs, mask, lam))
        return out

    return kernel


def make_mhd_pass_kernels():
    """The operator-split pair ``(hydro_pass, b_pass)`` driving
    :class:`GridMHD`: each pass updates its subsystem and passes the
    other through IDENTITY, so a ``run_steps`` call exchanges only
    the subsystem that changes — a proper subset of ``fields_out``."""

    def hydro_pass(cell, nbr, offs, mask, lam):
        out = _hydro_update(cell, nbr, offs, mask, lam)
        out.update({n: cell[n] for n in MHD_BFIELD})
        return out

    def b_pass(cell, nbr, offs, mask, lam):
        out = {n: cell[n] for n in MHD_HYDRO}
        out.update(_b_update(cell, nbr, offs, mask, lam))
        return out

    return hydro_pass, b_pass


def mhd_default_init(grid, seed: int) -> None:
    """The fleet's seeded default init for ``"mhd"`` jobs: a smooth
    random state with positive density and pressure (the plain
    uniform-random fill of the generic default would start with
    supersonic noise and negative pressures). Deterministic in
    (cell count, seed); byte-identical fleet vs solo."""
    rng = np.random.default_rng(seed)
    cells = grid.plan.cells
    nc = len(cells)
    rho = (1.0 + 0.5 * rng.random(nc)).astype(np.float32)
    mom = {n: (0.2 * (rng.random(nc) - 0.5)).astype(np.float32)
           for n in ("mx", "my", "mz")}
    p = (0.5 + 0.5 * rng.random(nc)).astype(np.float32)
    ke = 0.5 * (mom["mx"] ** 2 + mom["my"] ** 2 + mom["mz"] ** 2) / rho
    en = (p / np.float32(GAMMA - 1.0) + ke).astype(np.float32)
    grid.set("rho", cells, rho)
    for n, v in mom.items():
        grid.set(n, cells, v)
    grid.set("en", cells, en)
    for n in MHD_BFIELD:
        grid.set(n, cells, (0.3 * (rng.random(nc) - 0.5))
                 .astype(np.float32))


class GridMHD:
    """The multi-device MHD model on the general ``Grid`` runtime:
    a blast-wave setup advanced by the two-pass operator splitting
    (hydro^n then cleaning^n per :meth:`run` call) through the fused
    ``Grid.run_steps`` loop, each pass exchanging only its own
    subsystem's ghosts."""

    def __init__(self, n=16, nz=None, mesh=None, dtype=jnp.float32,
                 partition="block", profile="blast", seed=0):
        nz = nz if nz is not None else n
        self.n, self.nz = n, nz
        dx = 1.0 / n
        self.dx = dx
        self.grid = (
            Grid(cell_data=mhd_cell_data(jnp.float32), dtype=dtype)
            .set_initial_length((n, n, nz))
            .set_periodic(True, True, True)
            .set_maximum_refinement_level(0)
            .set_neighborhood_length(0)
            .set_geometry("cartesian", start=(0.0, 0.0, 0.0),
                          level_0_cell_length=(dx, dx, 1.0 / nz))
            .initialize(mesh, partition=partition)
        )
        cells = self.grid.plan.cells
        if profile == "blast":
            self._init_blast(cells)
        else:
            mhd_default_init(self.grid, seed)
        self.grid.update_copies_of_remote_neighbors()
        self._hydro, self._bpass = make_mhd_pass_kernels()
        self.time = 0.0

    def _init_blast(self, cells):
        """Sedov-style pressure blast in a uniform magnetized medium
        (the reference test-zoo's classic)."""
        g = self.grid
        idx = g.mapping.get_indices(np.asarray(cells, np.uint64))
        x = (idx[:, 0].astype(np.float64) + 0.5) * self.dx
        y = (idx[:, 1].astype(np.float64) + 0.5) * self.dx
        z = (idx[:, 2].astype(np.float64) + 0.5) / self.nz
        r2 = (x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2
        p = np.where(r2 < 0.1 ** 2, 10.0, 0.1).astype(np.float32)
        nc = len(cells)
        g.set("rho", cells, np.ones(nc, np.float32))
        for nme in ("mx", "my", "mz"):
            g.set(nme, cells, np.zeros(nc, np.float32))
        g.set("en", cells, (p / np.float32(GAMMA - 1.0)))
        g.set("bx", cells, np.full(nc, 0.2, np.float32))
        g.set("by", cells, np.zeros(nc, np.float32))
        g.set("bz", cells, np.zeros(nc, np.float32))

    def max_time_step(self) -> float:
        """CFL bound from the current state (host reduction)."""
        g = self.grid
        rho = np.maximum(np.asarray(g.get("rho", g.plan.cells),
                                    np.float64), RHO_FLOOR)
        vmax = 0.0
        ke = np.zeros_like(rho)
        for nme in ("mx", "my", "mz"):
            m = np.asarray(g.get(nme, g.plan.cells), np.float64)
            vmax = max(vmax, float(np.abs(m / rho).max()))
            ke += 0.5 * m * m / rho
        en = np.asarray(g.get("en", g.plan.cells), np.float64)
        p = np.maximum((GAMMA - 1.0) * (en - ke), P_FLOOR)
        c = float(np.sqrt(GAMMA * p / rho).max())
        return self.dx / max(vmax + c, ETA * 6.0, 1e-12)

    def run(self, n_steps: int, dt: float | None = None,
            cfl: float = 0.4) -> float:
        """``n_steps`` hydro steps then ``n_steps`` cleaning steps
        (coarse operator splitting — each pass is one fused device
        loop exchanging only its own subsystem)."""
        if dt is None:
            dt = cfl * self.max_time_step()
        lam = jnp.float32(dt / self.dx)
        self.grid.run_steps(self._hydro, MHD_ALL, MHD_ALL, n_steps,
                            exchange_fields=MHD_HYDRO,
                            extra_args=(lam,))
        self.grid.run_steps(self._bpass, MHD_ALL, MHD_ALL, n_steps,
                            exchange_fields=MHD_BFIELD,
                            extra_args=(lam,))
        self.time += n_steps * dt
        return dt

    def conserved_sums(self) -> dict:
        """Host-f64 global sums of every conserved field — the
        conservation diagnostic the tests pin."""
        g = self.grid
        return {n: float(np.sum(np.asarray(g.get(n, g.plan.cells),
                                           np.float64)))
                for n in MHD_ALL}


def register() -> None:
    """Register the zoo entries: the ``"mhd"`` fleet kernel (with its
    schema defaults and seeded init) and the conservation invariants
    the SDC defense checks. Idempotent."""
    from .. import fleet, integrity

    fleet.register_kernel("mhd", make_mhd_kernel())
    fleet.register_kernel_spec(
        "mhd", cell_data=mhd_cell_data(jnp.float32),
        fields_in=MHD_ALL, fields_out=MHD_ALL, params=(0.05,),
        init=mhd_default_init)
    integrity.register_conserved("mhd", MHD_ALL, periodic_axes=(0, 1, 2))


ZOO_INFO = {
    "kernel": "mhd",
    "fields": MHD_ALL,
    "conserved": MHD_ALL,
    "model": "GridMHD",
    "description": ("finite-volume ideal-MHD-style: Rusanov hydro "
                    "fluxes (hydro ghosts) + conservative B cleaning "
                    "(B ghosts)"),
}
