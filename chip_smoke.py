#!/usr/bin/env python
"""Smoke run of the general Grid runtime's main path on the TPU.

Runs the north-star workload, 512^3 first-order upwind solid-body
rotation advection (BASELINE.json; reference tests/advection), through
``GridAdvection`` and ``Grid.run_steps`` for 20 steps in float32, in
this one process, and checks the result against a plain jax.numpy
re-statement of the same update written below without dccrg_tpu.

    python chip_smoke.py             # one chip: Grid vs the plain reference
    python chip_smoke.py --chips 4   # the same run on a 4-device mesh vs
                                     # on one device, bitwise, and
                                     # nothing else

The timings printed are a smoke reading, not a benchmark. The last line
of standard output is ``{"ok": true, "device": {...}}``; it is printed
only when every check passed. There is no CPU path: the script fails
where JAX finds no TPU.
"""

import argparse
import json
import math
import sys
import time

import jax
import jax.numpy as jnp

from dccrg_tpu.compat import use_compile_cache

N = 512
STEPS = 20
# Both sides round in float32 (unit roundoff u = 2^-24). Per cell and
# step each side makes at most 12 roundings (2 per flux term, 4 terms,
# 4 accumulations and the final add) of values below 1 in magnitude
# (|rho| <= 0.5 and |v dt / dx| <= 0.5 at CFL 0.5), so one step adds at
# most 2 * 12 * u to their difference. At this CFL the update is a
# convex combination of the cell and its upwind neighbours
# (|m_x| + |m_y| <= 1), which cannot grow a difference already there,
# so the differences only add up over the steps. The initial hump may
# differ by a few ulp where sqrt/cos fuse differently: 8 u.
TOL = (STEPS * 2 * 12 + 8) * 2.0**-24  # ~2.9e-5


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-device vs 1-device comparison")
    return ap.parse_args()


def hump(n):
    """[z, y, x] initial density: the cosine hump of radius 0.15 at
    (0.25, 0.5), constant along z."""
    f32 = jnp.float32
    c = (jnp.arange(n, dtype=jnp.int32).astype(f32) + 0.5) * f32(1.0 / n)
    x, y = c[None, None, :], c[None, :, None]
    r = jnp.minimum(jnp.sqrt((x - 0.25) ** 2 + (y - 0.5) ** 2), 0.15) / 0.15
    return jnp.broadcast_to(0.25 * (1.0 + jnp.cos(jnp.pi * r)), (n, n, n))


@jax.jit
def reference_steps(rho, dt):
    """STEPS steps of the update of make_uniform_flux_kernel, restated
    on a dense [z, y, x] array: first-order upwind fluxes through the x
    and y faces with face velocity 0.5 * (v_cell + v_neighbour),
    vx = 0.5 - y, vy = x - 0.5, periodic in x and y, no flux through z
    (vz = 0)."""
    n = rho.shape[0]
    c = (jnp.arange(n, dtype=jnp.int32).astype(jnp.float32) + 0.5) \
        * jnp.float32(1.0 / n)
    vx, vy = 0.5 - c[None, :, None], c[None, None, :] - 0.5
    m = dt * float(n)  # dt / dx

    def flux(rho, v, axis):
        """Net upwind inflow along one periodic axis."""
        v_hi = 0.5 * (v + jnp.roll(v, -1, axis))
        v_lo = 0.5 * (jnp.roll(v, 1, axis) + v)
        out_hi = jnp.where(v_hi >= 0, rho, jnp.roll(rho, -1, axis))
        in_lo = jnp.where(v_lo >= 0, jnp.roll(rho, 1, axis), rho)
        return in_lo * (v_lo * m) - out_hi * (v_hi * m)

    def step(_, rho):
        return rho + (flux(rho, vx, 2) + flux(rho, vy, 1))

    return jax.lax.fori_loop(0, STEPS, step, rho)


def cell_order(grid):
    """The grid's density as a flat [n^3] array in cell-id order (x
    fastest), placed by the grid's own row ids: a device's local rows
    are not in id order once it has outer cells to exchange."""
    n_local = [int(v) for v in grid.plan.n_local]
    rho, ids = grid.data["density"], grid.device_row_ids()
    flat = jnp.concatenate([rho[d, :k] for d, k in enumerate(n_local)])
    order = jnp.concatenate([ids[d, :k] for d, k in enumerate(n_local)])
    return jnp.zeros_like(flat).at[order].set(flat, unique_indices=True)


def grid_run(mesh, label):
    """Build GridAdvection at N^3 on ``mesh`` and run STEPS steps
    through Grid.run_steps; print what ran and its smoke timings."""
    from dccrg_tpu import native
    from dccrg_tpu.models.advection import GridAdvection

    t0 = time.perf_counter()
    solver = GridAdvection(n=N, nz=N, mesh=mesh)
    t_build = time.perf_counter() - t0
    solver.checksum()
    t_init = time.perf_counter() - t0
    dt = 0.5 * solver.max_time_step()
    t0 = time.perf_counter()
    # n_steps is an argument of the one step program: zero steps
    # compile it and leave the state as it is
    solver.run(0, dt)
    solver.checksum()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    solver.run(STEPS, dt)
    mass = solver.checksum()
    t_steady = time.perf_counter() - t0
    grid = solver.grid
    hood = next(iter(grid.plan.hoods.values()))
    programs = sorted({k[0] for k in grid._program_cache
                       if k[0] in ("steploop", "bulksteploop")})
    gather = ("roll, closed-form plan" if hood.closed_form is not None
              else "roll" if grid._use_roll_gather() else "tables")
    print(f"[{label}] devices {grid.n_dev}: init {t_init:.3f} s (constructor "
          f"{t_build:.3f} s, then first sync), compile "
          f"{t_compile:.3f} s, {STEPS} steps {t_steady:.3f} s "
          f"(smoke timing, not a benchmark)")
    print(f"[{label}] program {programs}, gather {gather}, native host "
          f"library "
          f"{'loaded' if native.lib is not None else 'not loaded'}")
    if not math.isfinite(mass):
        raise AssertionError(f"[{label}] density sum is {mass}")
    return solver, dt


def peak_bytes(devices):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def one_chip():
    solver, dt = grid_run(None, "grid")
    l2 = solver.l2_error()
    print(f"[grid] l2 error vs analytic_density after {STEPS} steps: {l2:.6e}")
    got = cell_order(solver.grid).reshape(N, N, N)
    del solver
    t0 = time.perf_counter()
    rho0 = hump(N)
    want = reference_steps(rho0, jnp.float32(dt))
    diff = float(jnp.max(jnp.abs(got - want)))
    moved = float(jnp.max(jnp.abs(want - rho0)))
    print(f"[reference] plain jnp run {time.perf_counter() - t0:.3f} s; "
          f"max |grid - reference| {diff:.3e} (tolerance {TOL:.3e}); "
          f"max change over the run {moved:.3e}")
    if not diff <= TOL:
        raise AssertionError(f"grid differs from the reference by {diff}")
    if not moved > 100 * TOL:
        raise AssertionError("the run barely moved: the check proves nothing")


def four_chips():
    from dccrg_tpu.grid import default_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    four, _ = grid_run(default_mesh(devs[:4]), "4 devices")
    grid = four.grid
    shards = grid.data["density"].addressable_shards
    held = sorted((s.device.id, s.data.shape) for s in shards)
    rows = [int(v) for v in grid.plan.n_local]
    print(f"[4 devices] density shards (device id, shape): {held}; "
          f"local cells per device {rows}")
    if (len({s.device for s in shards}) != 4 or any(r != N**3 // 4 for r in rows)
            or any(s.data.shape[0] != 1 for s in shards)):
        raise AssertionError("the state is not split in quarters over 4 devices")
    got4 = jax.device_put(cell_order(grid), devs[0])
    del four, grid
    one, _ = grid_run(default_mesh(devs[:1]), "1 device")
    got1 = cell_order(one.grid)
    # the same arithmetic per cell, only the neighbour values travel
    # differently (ppermute halo vs rolls): bitwise on the chip
    diff = float(jnp.max(jnp.abs(got4 - got1)))
    bitwise = bool(jnp.all(got4 == got1))
    print(f"[compare] 4 devices vs 1 device: bitwise {bitwise}, "
          f"max |diff| {diff:.3e}")
    if not bitwise:
        raise AssertionError(f"4-device run differs from 1-device by {diff}")


def main():
    args = parse_args()
    cache = use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device_kind {dev.device_kind!r}, platform {dev.platform}, "
          f"count {len(devs)}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    (four_chips if args.chips == 4 else one_chip)()
    print(f"peak_bytes_in_use per device: "
          f"{peak_bytes(devs[:args.chips])}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
