"""Vlasov-style workload: wide per-cell velocity-space payloads where
only reduced moments exchange as ghosts.

The reference dccrg's home domain is Vlasiator-style hybrid-Vlasov
simulation (Palmroth et al. 2018): each spatial cell carries a WIDE
velocity-space distribution (the ragged ``Cell_Data`` shape — here a
fixed ``[Nv]`` vector field ``f``), while the MPI ghost traffic moves
only small reduced quantities. This model reproduces exactly that
transfer shape on the batched runtime:

- ``f`` (``[n_cells, Nv]`` float32) is advected in **velocity space**
  by a self-consistent-field sketch (acceleration from the neighbor
  density gradient — an electrostatic-force proxy) with zero-flux
  velocity boundaries, then relaxed BGK-style toward a discrete
  Maxwellian built from the **neighbor-averaged** moments — the
  configuration-space coupling;
- only the reduced moments ``rho`` and ``ux`` (recomputed from ``f``
  every step) are read from neighbors, so ``run_steps`` exchanges
  ``("rho", "ux")`` — a proper subset of ``fields_out`` — and the
  wide payload NEVER moves over the interconnect
  (:class:`GridVlasov` pins the stale-ghost bytes).

Conservation: the velocity advection is flux-form with zero boundary
fluxes (per-cell mass exact in real arithmetic), and the BGK target
is normalized so its moment equals the neighbor-averaged density —
doubly stochastic over the face relation under full periodicity —
so total mass (``sum rho``) is conserved;
``integrity.register_conserved("vlasov", ("rho",))`` wires it into
the SDC defense.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..grid import Grid

NV_DEFAULT = 16
VMAX = 1.0      # velocity-grid half-extent
VT = 0.4        # thermal width of the BGK target
NU = 0.5        # BGK relaxation rate
KFIELD = 0.05   # density-gradient force coefficient
RHO_FLOOR = 1.0e-6

VLASOV_FIELDS = ("f", "rho", "ux")
VLASOV_EXCHANGE = ("rho", "ux")

_f32 = jnp.float32


def vlasov_cell_data(nv: int = NV_DEFAULT, dtype=jnp.float32) -> dict:
    """The schema: the wide ``[Nv]`` payload plus its two moments."""
    return {"f": ((int(nv),), dtype), "rho": dtype, "ux": dtype}


def _v_grid(nv: int):
    v = jnp.linspace(-VMAX, VMAX, nv, dtype=_f32)
    dv = _f32(2.0 * VMAX / (nv - 1))
    return v, dv


def _moments(f, v, dv):
    rho = jnp.sum(f, axis=-1) * dv
    ux = jnp.sum(f * v, axis=-1) * dv / jnp.maximum(rho, _f32(RHO_FLOOR))
    return rho, ux


def make_vlasov_kernel():
    """The fleet kernel (registry name ``"vlasov"``), one parameter
    ``dt``. ``Nv`` is read off the field shape, so one kernel serves
    every payload width. Its only neighbor reads are the two moments —
    the wide ``f`` is never read from neighbors."""

    def kernel(cell, nbr, offs, mask, dt):
        f = cell["f"].astype(_f32)               # [L, Nv]
        nv = f.shape[-1]
        v, dv = _v_grid(nv)
        dt = _f32(dt)
        face = mask & (jnp.sum(jnp.abs(offs), axis=-1) == 1)
        rho_n = nbr["rho"].astype(_f32)          # [L, S]
        ux_n = nbr["ux"].astype(_f32)
        deg = jnp.maximum(jnp.sum(face, axis=1), 1).astype(_f32)
        rho_bar = jnp.sum(jnp.where(face, rho_n, 0.0), axis=1) / deg
        ux_bar = jnp.sum(jnp.where(face, ux_n, 0.0), axis=1) / deg
        # electrostatic-force proxy: the x-gradient of the neighbor
        # density (the only other ghost read)
        gx = jnp.sum(jnp.where(face & (offs[..., 0] != 0),
                               offs[..., 0].astype(_f32) * rho_n, 0.0),
                     axis=1)
        a = -_f32(KFIELD) * gx                   # [L]
        # velocity-space upwind advection, flux form, zero-flux ends:
        # interior edge fluxes [L, Nv-1], per-cell mass telescopes
        ap = jnp.maximum(a, 0.0)[:, None]
        am = jnp.minimum(a, 0.0)[:, None]
        flux = ap * f[:, :-1] + am * f[:, 1:]
        z1 = jnp.zeros(f.shape[:-1] + (1,), _f32)
        f1 = f - (dt / dv) * (jnp.concatenate([flux, z1], axis=-1)
                              - jnp.concatenate([z1, flux], axis=-1))
        # BGK relaxation toward the neighbor-moment Maxwellian,
        # normalized so its density moment is exactly rho_bar
        w = jnp.exp(-((v[None, :] - ux_bar[:, None]) / _f32(VT)) ** 2)
        g = (rho_bar[:, None] * w
             / (jnp.sum(w, axis=-1, keepdims=True) * dv))
        f2 = f1 + dt * _f32(NU) * (g - f1)
        rho2, ux2 = _moments(f2, v, dv)
        return {"f": f2, "rho": rho2, "ux": ux2}

    return kernel


def vlasov_default_init(grid, seed: int) -> None:
    """Seeded default init for ``"vlasov"`` jobs: a positive random
    distribution with SELF-CONSISTENT moments (rho/ux recomputed from
    f exactly as the kernel does). Byte-identical fleet vs solo."""
    rng = np.random.default_rng(seed)
    cells = grid.plan.cells
    nv = int(grid.fields["f"][0][0])
    f = (0.1 + rng.random((len(cells), nv))).astype(np.float32)
    _set_with_moments(grid, cells, f)


def _set_with_moments(grid, cells, f) -> None:
    v = np.linspace(-VMAX, VMAX, f.shape[-1], dtype=np.float32)
    dv = np.float32(2.0 * VMAX / (f.shape[-1] - 1))
    rho = (f.sum(axis=-1, dtype=np.float32) * dv).astype(np.float32)
    ux = ((f * v).sum(axis=-1, dtype=np.float32) * dv
          / np.maximum(rho, np.float32(RHO_FLOOR))).astype(np.float32)
    grid.set("f", cells, f)
    grid.set("rho", cells, rho)
    grid.set("ux", cells, ux)


class GridVlasov:
    """The multi-device Vlasov model: a drifting density bump whose
    wide velocity payload stays device-local — every ``run_steps``
    call exchanges only the two moments."""

    def __init__(self, n=8, nz=None, nv=NV_DEFAULT, mesh=None,
                 partition="block", seed=0):
        nz = nz if nz is not None else n
        self.n, self.nz, self.nv = n, nz, int(nv)
        self.grid = (
            Grid(cell_data=vlasov_cell_data(nv))
            .set_initial_length((n, n, nz))
            .set_periodic(True, True, True)
            .set_maximum_refinement_level(0)
            .set_neighborhood_length(0)
            .initialize(mesh, partition=partition)
        )
        cells = self.grid.plan.cells
        idx = self.grid.mapping.get_indices(np.asarray(cells, np.uint64))
        x = (idx[:, 0].astype(np.float64) + 0.5) / n
        bump = (1.0 + 0.5 * np.cos(2.0 * np.pi * x)).astype(np.float32)
        v = np.linspace(-VMAX, VMAX, self.nv, dtype=np.float32)
        f = (bump[:, None]
             * np.exp(-((v[None, :] - 0.2) / VT) ** 2)).astype(np.float32)
        rng = np.random.default_rng(seed)
        f = f + (0.01 * rng.random(f.shape)).astype(np.float32)
        _set_with_moments(self.grid, cells, f)
        self.grid.update_copies_of_remote_neighbors()
        self._kernel = make_vlasov_kernel()
        self.time = 0.0

    def run(self, n_steps: int, dt: float = 0.05) -> float:
        self.grid.run_steps(
            self._kernel, VLASOV_FIELDS, VLASOV_FIELDS, n_steps,
            exchange_fields=VLASOV_EXCHANGE,
            extra_args=(jnp.float32(dt),))
        self.time += n_steps * dt
        return dt

    def total_mass(self) -> float:
        g = self.grid
        return float(np.sum(np.asarray(g.get("rho", g.plan.cells),
                                       np.float64)))


def register() -> None:
    """Register the zoo entries: the ``"vlasov"`` fleet kernel (with
    the wide-payload schema defaults and seeded init) and the mass
    invariant for the SDC defense. Idempotent."""
    from .. import fleet, integrity

    fleet.register_kernel("vlasov", make_vlasov_kernel())
    fleet.register_kernel_spec(
        "vlasov", cell_data=vlasov_cell_data(NV_DEFAULT),
        fields_in=VLASOV_FIELDS, fields_out=VLASOV_FIELDS,
        params=(0.05,), init=vlasov_default_init)
    integrity.register_conserved("vlasov", ("rho",),
                                 periodic_axes=(0, 1, 2))


ZOO_INFO = {
    "kernel": "vlasov",
    "fields": VLASOV_FIELDS,
    "conserved": ("rho",),
    "model": "GridVlasov",
    "description": ("hybrid-Vlasov-style: wide [Nv] per-cell velocity "
                    "payload advected locally; only the (rho, ux) "
                    "moments exchange as ghosts"),
}
