"""Driver: the Poisson solve through ``PoissonSolver.solve``'s fused
biconjugate (BiCG) program.

The solver is built by its public constructor on the cell's devices and
prepared (the host pass over the face-neighbour lists that computes the
geometry factors), so that the build holds the whole plan. The seed
draws the right-hand side, ``BLOBS`` Gaussian charge blobs of
alternating sign at seeded centres and widths, which is made on the
device from ``grid.device_row_ids()``. The window runs whole solves
back to back from a zero guess, each from the same right-hand side,
until ``--seconds`` have passed, and always at least one. One unit of
work is one cell through one BiCG iteration.

The plain reference below imports nothing of the program and takes
nothing it made: it takes the cells' edge lengths from the
configuration, rebuilds the right-hand side from the seed in float64,
removes its mean (the all-periodic operator is singular, and the
solver removes the mean too), and solves the same periodic 7-point
system exactly by FFT in float64. The numbers compared are the largest
difference between the grid's solution and the reference's, relative
to the reference's largest value, the relative residual of the grid's
solution under the same operator in float64, and the iterations of the
window's longest solve, which must stay below ``max_iterations``.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

BLOBS = 8
CENTRES = (0.1, 0.9)  # of the box, per axis
WIDTHS = (0.04, 0.10)  # of the box
# bytes one BiCG iteration must move per cell, in fields of the storage
# dtype: reads p0, p1, the six face factors, scale, solution, r0 and r1;
# writes solution, r0, r1, p0 and p1. In float32 that is 68 B per cell,
# 142.6 MB per 128^3 iteration, 0.174 ms at 819 GB/s; its ~45 flops per
# cell take ~0.5 us, so bytes bound the iteration (stencil_roofline)
FIELDS_PER_ITERATION = 12 + 5


def blob_params(seed: int) -> np.ndarray:
    """[BLOBS, 5]: centre (x, y, z) in the box, width and sign of each
    blob, drawn from the seed; the signs alternate from a seeded first
    sign."""
    rng = np.random.default_rng(seed)
    centres = rng.uniform(*CENTRES, size=(BLOBS, 3))
    widths = rng.uniform(*WIDTHS, size=(BLOBS, 1))
    first = rng.choice((-1.0, 1.0))
    signs = first * (-1.0) ** np.arange(BLOBS)[:, None]
    return np.concatenate([centres, widths, signs], axis=1)


def charge(xp, xi, yi, zi, n: int, params):
    """The right-hand side at integer cell indices in the array module
    ``xp`` (jnp on the device, numpy for the reference), in the dtype of
    ``params``: the sum of the blobs, each at its minimum-image distance
    in the periodic unit box."""
    dt = params.dtype
    pos = [(c.astype(dt) + 0.5) / n for c in (xi, yi, zi)]
    out = xp.zeros_like(pos[0])
    for b in range(BLOBS):
        r2 = 0.0
        for d in range(3):
            dd = pos[d] - params[b, d]
            dd = dd - xp.round(dd)
            r2 = r2 + dd * dd
        out = out + params[b, 4] * xp.exp(-0.5 * r2 / (params[b, 3] ** 2))
    return out


def reference_rhs(n: int, params: np.ndarray) -> np.ndarray:
    """The seeded right-hand side on a dense float64 [z, y, x] array,
    mean removed."""
    zi, yi, xi = np.meshgrid(*(np.arange(n),) * 3, indexing="ij")
    b = charge(np, xi, yi, zi, n, params.astype(np.float64))
    return b - b.mean()


def laplacian(x: np.ndarray, h) -> np.ndarray:
    """The periodic 7-point operator on [z, y, x] in float64; ``h`` is
    the cell length per axis (x, y, z)."""
    out = np.zeros_like(x)
    for axis, hd in zip((2, 1, 0), h):
        out += (np.roll(x, 1, axis) + np.roll(x, -1, axis) - 2.0 * x) / (hd * hd)
    return out


def reference_solve(b: np.ndarray, h) -> np.ndarray:
    """The exact solution of ``laplacian(x) = b`` with zero mean, by FFT
    in float64: the eigenvalue of the periodic 7-point operator at wave
    numbers k is sum_d (2 cos(2 pi k_d / n_d) - 2) / h_d^2, and the zero
    mode is set to 0."""
    lam = np.zeros(b.shape)
    for axis, hd in zip((2, 1, 0), h):
        n = b.shape[axis]
        shape = [1, 1, 1]
        shape[axis] = n
        lam = lam + ((2.0 * np.cos(2.0 * np.pi * np.arange(n) / n) - 2.0)
                     / (hd * hd)).reshape(shape)
    bk = np.fft.fftn(b)
    lam.flat[0] = 1.0
    xk = bk / lam
    xk.flat[0] = 0.0
    return np.real(np.fft.ifftn(xk))


def host_solution(grid) -> np.ndarray:
    """The grid's solution on the host in cell-id order (x fastest), as
    float64; rows are placed by the grid's own row ids."""
    n_local = [int(v) for v in grid.plan.n_local]
    vals = np.asarray(grid.data["solution"]).astype(np.float64)
    ids = np.asarray(grid.device_row_ids())
    cells = np.empty(sum(n_local), np.float64)
    for d, k in enumerate(n_local):
        cells[ids[d, :k]] = vals[d, :k]
    return cells


def build(config, traffic, devices):
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.poisson import PoissonSolver

    if int(traffic["mesh_devices"]) != len(devices):
        raise ValueError(f"the traffic spans {traffic['mesh_devices']} "
                         f"devices, the cell gives {len(devices)}")
    n = int(config["n"])
    solver = PoissonSolver(length=(n, n, n), mesh=default_mesh(devices),
                           periodic=tuple(config["periodic"]),
                           dtype=jnp.dtype(config["dtype"]),
                           max_refinement_level=config["max_refinement_level"])
    solver.prepare()
    solver.grid.data["scale"].block_until_ready()
    return {"solver": solver, "config": config, "traffic": traffic,
            "devices": devices}


def load(model, seed):
    """The seeded right-hand side, made on the device from the row ids
    (pad rows hold 0), and the zero initial guess."""
    grid, n = model["solver"].grid, int(model["config"]["n"])
    params = blob_params(seed)
    dtype = grid.data["rhs"].dtype
    ridx = grid.device_row_ids()

    @jax.jit
    def init(ridx, p):
        valid = ridx >= 0
        i = jnp.where(valid, ridx, 0)
        b = charge(jnp, i % n, (i // n) % n, i // (n * n), n, p)
        return jnp.where(valid, b, 0.0).astype(dtype)

    model["rhs"] = init(ridx, jnp.asarray(params, jnp.float32))
    model["zero"] = jnp.zeros_like(grid.data["solution"])
    model["rhs"].block_until_ready()
    model["params"] = params


def solve(model, max_iterations=None) -> dict:
    """One solve from a zero guess and the loaded right-hand side (the
    solver replaces its rhs by the rhs less its mean)."""
    solver, cfg = model["solver"], model["config"]
    solver.grid.data["rhs"] = model["rhs"]
    solver.grid.data["solution"] = model["zero"]
    return solver.solve(
        rtol=cfg["rtol"],
        max_iterations=(cfg["max_iterations"] if max_iterations is None
                        else max_iterations))


def warm(model):
    """Compile the one solve program: max_iterations is its argument,
    so a solve of zero iterations compiles it."""
    solve(model, max_iterations=0)
    model["solver"].grid.data["solution"].block_until_ready()


def window(model, seconds, spans):
    """Whole solves back to back until ``seconds`` have passed (each
    solve reads its iteration count back, so none is queued)."""
    grid, cfg = model["solver"].grid, model["config"]
    iterations = []
    t0 = time.perf_counter()
    while True:
        with spans("call"):
            info = solve(model)
        iterations.append(int(info["iterations"]))
        if time.perf_counter() - t0 >= seconds:
            break
    with spans("sync"):
        grid.data["solution"].block_until_ready()
    elapsed = time.perf_counter() - t0
    steps = sum(iterations)
    cells = int(np.sum(grid.plan.n_local))
    itemsize = jnp.dtype(grid.data["solution"].dtype).itemsize
    model["iterations"] = iterations
    return {
        "window_s": elapsed, "calls": len(iterations), "steps": steps,
        "cell_updates": cells * steps, "attempted": len(iterations),
        "failed": sum(i >= cfg["max_iterations"] for i in iterations),
        # the least bytes one solve must move on a device: one BiCG
        # iteration's bytes times the window's iterations per solve
        "least_bytes_per_call": [FIELDS_PER_ITERATION * itemsize * int(k)
                                 * steps / len(iterations)
                                 for k in grid.plan.n_local],
    }


def check(model, rec):
    """The grid's solution after the window's last solve against the
    float64 FFT solution of the same system, its float64 residual, and
    the window's longest solve. The comparison runs on the host, and
    the grid is freed before the reference runs."""
    t = time.perf_counter()
    cfg = model["config"]
    n = int(cfg["n"])
    solver = model.pop("solver")
    x = host_solution(solver.grid).reshape(n, n, n)
    del solver
    h = [float(v) for v in cfg["cell_length"]]
    b = reference_rhs(n, model["params"])
    want = reference_solve(b, h)
    values = {
        "max_rel_err": float(np.max(np.abs(x - want)) / np.max(np.abs(want))),
        "rel_residual": float(np.linalg.norm(b - laplacian(x, h))
                              / np.linalg.norm(b)),
        "iterations": max(model["iterations"]),
    }
    limits = dict(cfg["limit"], iterations=int(cfg["max_iterations"]) - 1)
    out = {k: {"value": v, "limit": limits[k],
               "ok": bool(np.isfinite(v) and v <= limits[k])}
           for k, v in values.items()}
    print(f"check took {time.perf_counter() - t:.1f} s; iterations per "
          f"solve {model['iterations']}", file=sys.stderr)
    return out
