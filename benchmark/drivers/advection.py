"""Driver: uniform-grid upwind advection through ``GridAdvection`` and
``Grid.run_steps``.

The model is built by its public constructor on the cell's devices.
The seed draws the initial density (cosine humps at seeded centres,
modulated along z), which is made on the device from
``grid.device_row_ids()``; the velocities stay the reference's
solid-body rotation. The window calls ``run`` with the traffic's
``steps_per_call`` until ``--seconds`` have passed, keeping up to
``DEPTH`` calls queued on the device, and ends on a device sync.

The plain reference below restates the update on a dense ``[z, y, x]``
array with ``jnp.roll`` (copied from ``chip_smoke.py``), imports
nothing of the program and takes nothing it made: it rebuilds the
initial density from the seed and steps it as many times as the window
did. The time step comes from the configuration in closed form
(``time_step``) and is handed to both. The numbers compared are the
largest absolute difference between the grid's final density, in cell
order, and the reference's, and across devices the same for the ghost
rows against the reference one step earlier.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

HUMPS = 3
RADIUS = 0.15
DEPTH = 3  # calls in flight in the window


def hump_params(seed: int) -> np.ndarray:
    """[HUMPS, 2] centres (in [0.25, 0.75]^2) and the phase of the z
    modulation, drawn from the seed. Every seed gives the same work:
    the update does not depend on the values."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(0.25, 0.75, size=(HUMPS, 2))
    return np.concatenate([centres.ravel(), rng.uniform(0.0, 1.0, 1)]).astype(np.float32)


def time_step(config) -> float:
    """CFL x dx / max |v|: over cell centres (x, y in [dx/2, 1 - dx/2])
    the solid-body rotation's largest speed is 0.5 - dx/2, and a face
    velocity, the mean of two centres', is no larger."""
    dx = 1.0 / config["n"]
    return config["cfl"] * dx / (0.5 - 0.5 * dx)


def density(xi, yi, zi, n: int, nz: int, params):
    """Initial density at integer cell indices, in float32: the mean of
    HUMPS cosine humps of the reference's radius (initialize.hpp:54-66)
    scaled by 0.75 + 0.25 cos(2 pi (z + phase)). Values lie in [0, 0.5]."""
    f32 = jnp.float32
    x = (xi.astype(f32) + 0.5) * f32(1.0 / n)
    y = (yi.astype(f32) + 0.5) * f32(1.0 / n)
    z = (zi.astype(f32) + 0.5) * f32(1.0 / nz)
    rho = jnp.zeros_like(x)
    for h in range(HUMPS):
        r = jnp.minimum(jnp.sqrt((x - params[2 * h]) ** 2
                                 + (y - params[2 * h + 1]) ** 2), RADIUS) / RADIUS
        rho = rho + 0.25 * (1.0 + jnp.cos(jnp.pi * r))
    mod = 0.75 + 0.25 * jnp.cos(2.0 * jnp.pi * (z + params[2 * HUMPS]))
    return rho * f32(1.0 / HUMPS) * mod


@jax.jit
def reference_steps(rho, dt, steps):
    """``steps`` steps of the update of make_uniform_flux_kernel on a
    dense [z, y, x] array: first-order upwind fluxes through the x and
    y faces with face velocity 0.5 * (v_cell + v_neighbour),
    vx = 0.5 - y, vy = x - 0.5, periodic in x and y, no flux through z
    (vz = 0)."""
    n = rho.shape[2]
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

    return jax.lax.fori_loop(0, steps, step, rho)


def reference_initial(n: int, nz: int, params, device):
    """The seeded initial density on a dense [z, y, x] array. The cell
    indices are made inside the program, not captured as constants."""
    @jax.jit
    def init(p):
        zi, yi, xi = (jax.lax.broadcasted_iota(jnp.int32, (nz, n, n), d)
                      for d in range(3))
        return density(xi, yi, zi, n, nz, p)

    return init(jax.device_put(jnp.asarray(params), device))


def host_cells(grid, field):
    """``field`` on the host: (float32 values in cell-id order, x
    fastest; float32 values of the ghost rows; their cell indices).
    Rows are placed by the grid's own row ids: a device's rows are not
    in id order once it has outer cells to exchange, and a ghost row is
    a copy of a neighbour device's cell kept by the halo exchange."""
    n_local = [int(v) for v in grid.plan.n_local]
    vals = np.asarray(grid.data[field]).astype(np.float32)
    ids = np.asarray(grid.device_row_ids())
    cells = np.empty(sum(n_local), np.float32)
    ghost_vals, ghost_ids = [], []
    for d, k in enumerate(n_local):
        cells[ids[d, :k]] = vals[d, :k]
        g = ids[d, k:] >= 0
        ghost_vals.append(vals[d, k:][g])
        ghost_ids.append(ids[d, k:][g])
    return cells, np.concatenate(ghost_vals), np.concatenate(ghost_ids)


def build(config, traffic, devices):
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection import GridAdvection

    if int(traffic["mesh_devices"]) != len(devices):
        raise ValueError(f"the traffic spans {traffic['mesh_devices']} "
                         f"devices, the cell gives {len(devices)}")
    solver = GridAdvection(n=config["n"], nz=config["nz"],
                           mesh=default_mesh(devices), cfl=config["cfl"],
                           dtype=jnp.dtype(config["dtype"]))
    solver.grid.data["density"].block_until_ready()
    return {"solver": solver, "config": config, "traffic": traffic,
            "devices": devices, "dt": time_step(config)}


def load(model, seed):
    """The seeded initial density, made on the device from the row ids
    (pad rows hold 0)."""
    solver, cfg = model["solver"], model["config"]
    grid = solver.grid
    n, nz = cfg["n"], cfg["nz"]
    params = hump_params(seed)
    dtype = grid.data["density"].dtype
    ridx = grid.device_row_ids()

    @jax.jit
    def init(ridx, p):
        valid = ridx >= 0
        i = jnp.where(valid, ridx, 0)
        rho = density(i % n, (i // n) % n, i // (n * n), n, nz, p)
        return jnp.where(valid, rho, 0.0).astype(dtype)

    grid.data["density"] = init(ridx, jnp.asarray(params))
    grid.data["density"].block_until_ready()
    model["params"] = params


def warm(model):
    """Compile the one step program: n_steps is its argument, so zero
    steps compile it and leave the state as it is."""
    model["solver"].run(0, model["dt"])
    model["solver"].grid.data["density"].block_until_ready()


def window(model, seconds, spans):
    """Calls back to back with up to DEPTH of them queued on the device,
    so that a host stall shorter than DEPTH - 1 steps leaves it busy."""
    solver, spc = model["solver"], int(model["traffic"]["steps_per_call"])
    grid = solver.grid
    calls = 0
    queued = []
    t0 = time.perf_counter()
    while True:
        with spans("call"):
            solver.run(spc, model["dt"])
        calls += 1
        queued.append(grid.data["density"])
        if len(queued) >= DEPTH:
            with spans("wait"):
                queued.pop(0).block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with spans("sync"):
        queued[-1].block_until_ready()
    elapsed = time.perf_counter() - t0
    steps = calls * spc
    cells = int(np.sum(grid.plan.n_local))
    itemsize = jnp.dtype(grid.data["density"].dtype).itemsize
    model["steps"] = steps
    return {
        "window_s": elapsed, "calls": calls, "steps": steps,
        "cell_updates": cells * steps, "attempted": steps, "failed": 0,
        # the least bytes any implementation of one run_steps call must
        # move on a device: its stepped field read once and written once
        "least_bytes_per_call": [2 * itemsize * int(k) for k in grid.plan.n_local],
    }


def check(model, rec):
    """Max |grid - reference| over all cells after the window's steps
    and, across devices, over every ghost row: the exchange at the start
    of the last step copied the neighbours' cells as they were one step
    earlier. The comparison runs on the host, and the grid is freed
    before the reference runs."""
    t = time.perf_counter()
    cfg, dev = model["config"], model["devices"][0]
    solver = model.pop("solver")
    got, ghost_vals, ghost_ids = host_cells(solver.grid, "density")
    del solver
    n, nz = cfg["n"], cfg["nz"]
    rho0 = reference_initial(n, nz, model["params"], dev)
    dt = jnp.float32(model["dt"])
    before = reference_steps(rho0, dt, jnp.int32(model["steps"] - 1))
    want = np.asarray(reference_steps(before, dt, jnp.int32(1))).reshape(-1)
    before = np.asarray(before).reshape(-1)
    out = {}
    err = float(np.max(np.abs(got - want)))
    limit = cfg["limit"]["max_abs_err"]
    out["max_abs_err"] = {"value": err, "limit": limit, "ok": bool(err <= limit)}
    if ghost_ids.size:
        gerr = float(np.max(np.abs(ghost_vals - before[ghost_ids])))
        limit = cfg["limit"]["ghost_max_abs_err"]
        out["ghost_max_abs_err"] = {"value": gerr, "limit": limit,
                                    "ok": bool(gerr <= limit)}
    print(f"check took {time.perf_counter() - t:.1f} s", file=sys.stderr)
    return out
