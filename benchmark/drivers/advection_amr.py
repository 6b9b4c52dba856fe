"""Driver: adaptive-mesh upwind advection through ``AmrAdvection``:
``run_fused`` (``Grid.run_steps``) between adapt epochs (``adapt``).

The model is built by its public constructor on the cell's device, at
``max_refinement_level`` 1 with the model's own closed boundaries and
criterion constants from the configuration. A cycle is
``run_fused(steps_per_cycle)`` followed by one ``adapt()``, the
reference's main loop (tests/advection/2d.cpp:321-442) with its adapt
cadence.

Seeded data, the same work for every seed: one fixed initial density
on the fine lattice (``initial_density``), of which the seed picks one
of 8 images (``image``, ``seed % 8``): a rotation by a multiple of 90
degrees about the axis through (0.5, 0.5), times an optional mirror
z -> 1 - z. These maps leave the rotation velocity field and the closed
box unchanged, so every image refines, steps and coarsens the same
number of cells at every epoch. The grid takes the initial data as the
reference's initial refinement does (``initial_state``): every cell
coarse with its slots' mean, one adapt epoch, then the fine cells take
the field's values and the coarse ones its means. That is set-up.

Warm-up is ``warm_cycles`` whole cycles, then a zero-step ``run_fused``
and the criterion stencil (``compute_max_diff``), which compile the
programs the window's first cycle runs on the new plan; the plan's
hard-row capacities grow in the first epoch and are steady after. The
window is ``window_cycles`` whole cycles and ignores ``--seconds``; it
ends on a device sync after the last cycle's adapt. One unit of work is
one live cell through one step; adapt epochs count in the time. The
window counts the XLA compiles that start in it through a
``jax.monitoring`` listener (``compiles_in_window``).

The plain reference below imports nothing of the program and takes
nothing it made. It keeps the state on the dense fine lattice, ``N =
2 n`` cells per axis indexed ``[z, y, x]``, beside a per-root level
mask ``[n, n, n]`` (0 coarse, 1 refined); a coarse cell stores its
value in each of its 8 fine slots. The rules are those of
``tests/advection`` (``solve.hpp``, ``adapter.hpp``) as ``AmrAdvection``
restates them:

- Upwind face fluxes. Each cell sums, over its face neighbours, the
  face term ``upwind density * dt * v * area / volume``. ``v`` is the
  face velocity interpolated by cell lengths,
  ``(l_c v_n + l_n v_c) / (l_c + l_n)``, from the cells' centre
  velocities of the solid-body rotation ``(0.5 - y, x - 0.5, 0)``.
  ``area`` is the smaller of the two faces. Faces run between adjacent
  fine slots of different cells; a face between two coarse cells spans
  four slot pairs and is counted once. Faces in z carry no flux
  (``v_z = 0``), so they are skipped. The domain is closed on every
  side.
- The adaptation criterion. A cell's ``max_diff`` is the largest
  ``|rho_c - rho_n| / (min(rho_c, rho_n) + diff_threshold)`` over its
  face neighbours, z faces included. With ``refine = (level + 1) *
  diff_increase`` and ``unrefine = unrefine_sensitivity * refine``, a
  coarse cell above ``refine`` is refined, and a refined root is
  unrefined where one of its 8 cells is below ``unrefine`` and none lies
  between the two thresholds.
- Projection. New children keep their parent's value; an unrefined
  parent takes the mean of its 8 children.

It runs the cycles of the warm-up and the window from the same image.
The numbers compared are ``level_mismatch``, the roots whose refinement
differs at the end, and ``max_abs_err``, the largest absolute
difference over cells between the grid's density and the reference's.
The time step is ``time_step``, the CFL limit of a fine cell anywhere in
the box, handed to both.
"""

from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

F32 = np.float32
HUMP_RADIUS = 0.15
# the reference's hump (initialize.hpp:54-66), then two at fixed dyadic
# centres; each cosine hump has the reference's radius
HUMP_CENTRES = ((0.25, 0.5), (0.625, 0.75), (0.6875, 0.3125))
Z_PHASE = 0.125
IMAGES = 8
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# -- the plain reference ----------------------------------------------

def initial_density(n: int) -> np.ndarray:
    """The fixed initial density on the fine lattice of an ``n``-cube
    base, float32 ``[2n, 2n, 2n]``: the mean of the three humps, scaled
    by ``0.75 + 0.25 cos(2 pi (z + Z_PHASE))``. Values lie in [0, 0.5]."""
    N = 2 * n
    c = (np.arange(N) + 0.5) / N
    z, y, x = np.meshgrid(c, c, c, indexing="ij")
    rho = np.zeros((N, N, N))
    for x0, y0 in HUMP_CENTRES:
        r = np.minimum(np.hypot(x - x0, y - y0), HUMP_RADIUS) / HUMP_RADIUS
        rho += 0.25 * (1.0 + np.cos(np.pi * r))
    mod = 0.75 + 0.25 * np.cos(2.0 * np.pi * (z + Z_PHASE))
    return (rho / len(HUMP_CENTRES) * mod).astype(F32)


def image(field: np.ndarray, g: int) -> np.ndarray:
    """Image ``g`` (0..7) of a ``[z, y, x]`` lattice array: a rotation by
    ``(g % 4) * 90`` degrees about the axis through (0.5, 0.5), then,
    for ``g >= 4``, the mirror ``z -> 1 - z``. Both leave the rotation
    velocity field and the closed box unchanged."""
    out = np.rot90(field, g % 4, axes=(2, 1))
    if g >= 4:
        out = out[::-1]
    return np.ascontiguousarray(out)


def time_step(n: int, cfl: float) -> float:
    """``cfl`` times the CFL limit of a fine cell anywhere in the box:
    edge ``1 / 2n`` over the largest centre speed ``0.5 - 1 / 4n``. It
    bounds every refinement, so one step size serves the whole run."""
    N = 2 * n
    return cfl * (1.0 / N) / (0.5 - 0.5 / N)


def blocks(a: np.ndarray) -> np.ndarray:
    """``[N, N, N]`` -> ``[n, n, n, 8]``: each root's 8 fine slots, x
    fastest, then y, then z (the order of a cell's children)."""
    n = a.shape[0] // 2
    return a.reshape(n, 2, n, 2, n, 2).transpose(0, 2, 4, 1, 3, 5).reshape(
        n, n, n, 8)


def fine(a: np.ndarray) -> np.ndarray:
    """Per-root ``[n, n, n]`` -> the fine lattice ``[N, N, N]``."""
    return np.repeat(np.repeat(np.repeat(a, 2, 0), 2, 1), 2, 2)


def coarsen(rho: np.ndarray, level: np.ndarray) -> np.ndarray:
    """Coarse roots take the mean of their 8 slots (float32, in
    children order); refined roots keep theirs."""
    mean = blocks(rho).mean(axis=3, dtype=F32)
    return np.where(fine(level) == 1, rho, fine(mean)).astype(F32)


def geometry(level: np.ndarray):
    """Per fine slot: the edge length of its cell and the centre
    velocities ``vx = 0.5 - y``, ``vy = x - 0.5`` of its cell, float32."""
    n = level.shape[0]
    N = 2 * n
    ref = fine(level) == 1
    i = np.arange(N)
    cf = (i + 0.5) / N  # a fine cell's centre
    cc = (i // 2 + 0.5) / n  # its root's centre
    x = np.where(ref, cf[None, None, :], cc[None, None, :])
    y = np.where(ref, cf[None, :, None], cc[None, :, None])
    length = np.where(ref, 1.0 / N, 1.0 / n).astype(F32)
    return length, (0.5 - y).astype(F32), (x - 0.5).astype(F32)


def _pairs(a: np.ndarray, axis: int):
    """(lower, upper) views of ``a`` over the adjacent slot pairs along
    ``axis``."""
    lo = [slice(None)] * 3
    hi = [slice(None)] * 3
    lo[axis] = slice(0, -1)
    hi[axis] = slice(1, None)
    return a[tuple(lo)], a[tuple(hi)]


def _face(level: np.ndarray, axis: int) -> np.ndarray:
    """Per slot pair along ``axis``: True where the pair carries a face
    term. A pair inside one coarse root has none; of the four pairs of
    a face between two coarse roots only the one at even fine indices
    in the other two axes carries it."""
    N = 2 * level.shape[0]
    ref = fine(level) == 1
    ra, rb = _pairs(ref, axis)
    k = np.arange(N - 1)
    shape = [1, 1, 1]
    shape[axis] = N - 1
    same_root = (k % 2 == 0).reshape(shape)
    i = np.arange(N) % 2 == 0
    even = [i.reshape(-1, 1, 1), i.reshape(1, -1, 1), i.reshape(1, 1, -1)]
    others = [a for a in range(3) if a != axis]
    rep = even[others[0]] & even[others[1]]
    coarse_pair = ~ra & ~rb
    return np.where(same_root, ra, ~coarse_pair | _pairs(
        np.broadcast_to(rep, ref.shape), axis)[0])


def step(rho: np.ndarray, level: np.ndarray, dt: float) -> np.ndarray:
    """One upwind step of every cell (float32)."""
    length, vx, vy = geometry(level)
    dt = F32(dt)
    vol = length * length * length
    total = np.zeros_like(rho)
    for axis, vel in ((2, vx), (1, vy)):
        la, lb = _pairs(length, axis)
        va, vb = _pairs(vel, axis)
        ra, rb = _pairs(rho, axis)
        vola, volb = _pairs(vol, axis)
        face = _face(level, axis)
        v = (la * vb + lb * va) / (la + lb + F32(1e-30))
        area = np.minimum(la * la, lb * lb)
        up = np.where(v >= 0, ra, rb)
        flow = dt * v * area
        out_a = np.where(face, up * (flow / vola), F32(0))
        in_b = np.where(face, up * (flow / volb), F32(0))
        ta, tb = _pairs(total, axis)
        ta -= out_a
        tb += in_b
    cell_total = np.where(fine(level) == 1, total,
                          fine(blocks(total).sum(axis=3, dtype=F32)))
    return (rho + cell_total).astype(F32)


def max_diff(rho: np.ndarray, level: np.ndarray,
             diff_threshold: float) -> np.ndarray:
    """Per slot, its cell's ``max_diff`` over face neighbours in all
    three axes. Pairs inside one coarse root hold equal values and read
    0, so every adjacent pair may count."""
    thr = F32(diff_threshold)
    out = np.zeros_like(rho)
    for axis in range(3):
        ra, rb = _pairs(rho, axis)
        d = np.abs(ra - rb) / (np.minimum(ra, rb) + thr)
        oa, ob = _pairs(out, axis)
        np.maximum(oa, d, out=oa)
        np.maximum(ob, d, out=ob)
    cell = blocks(out).max(axis=3)
    return np.where(fine(level) == 1, out, fine(cell))


def adapt(rho: np.ndarray, level: np.ndarray, diff_threshold: float,
          diff_increase: float, unrefine_sensitivity: float):
    """One adapt epoch: ``(rho, level, created, removed)``, counts of
    cells made and removed."""
    diff = max_diff(rho, level, diff_threshold)
    lvl = fine(level).astype(F32)
    refine_t = (lvl + F32(1)) * F32(diff_increase)
    unref_t = F32(unrefine_sensitivity) * refine_t
    refined = lvl > 0
    code = np.where((diff > refine_t) & ~refined, 1,
                    np.where((diff < unref_t) & refined, 3,
                             np.where((diff <= refine_t) & (diff >= unref_t)
                                      & refined, 2, 0)))
    code = blocks(code)
    refine = (level == 0) & (code[..., 0] == 1)
    unrefine = ((level == 1) & (code == 3).any(axis=3)
                & ~(code == 2).any(axis=3))
    new_level = level.copy()
    new_level[refine] = 1
    new_level[unrefine] = 0
    # children keep the parent's value in its slots; unrefined roots
    # take the mean of their children
    mean = fine(blocks(rho).mean(axis=3, dtype=F32))
    rho = np.where(fine(unrefine), mean, rho).astype(F32)
    return rho, new_level, 8 * int(refine.sum()), 8 * int(unrefine.sum())


def initial_state(field: np.ndarray, diff_threshold: float,
                  diff_increase: float, unrefine_sensitivity: float):
    """The reference's initial refinement (2d.cpp's loop over levels):
    every root coarse with its slots' mean, one adapt epoch, then the
    fine cells take the field's values and the coarse ones its means."""
    n = field.shape[0] // 2
    level = np.zeros((n, n, n), np.int8)
    rho = coarsen(field, level)
    _, level, _, _ = adapt(rho, level, diff_threshold, diff_increase,
                           unrefine_sensitivity)
    return coarsen(field, level), level


def cell_count(level: np.ndarray) -> int:
    """Cells of the grid a per-root level mask describes."""
    return int(level.size + 7 * level.sum())


# -- the grid's cells on the fine lattice ------------------------------

def criterion(config) -> dict:
    return {k: float(config[k]) for k in
            ("diff_threshold", "diff_increase", "unrefine_sensitivity")}


def _cells(grid):
    """(ids, fine-lattice indices x, y, z of each cell's first slot,
    refined mask), in the grid's cell order."""
    cells = grid.get_cells()
    idx = grid.mapping.get_indices(cells).astype(np.int64)
    refined = grid.mapping.get_refinement_level(cells) == 1
    return cells, idx[:, 0], idx[:, 1], idx[:, 2], refined


def put_density(grid, rho: np.ndarray) -> None:
    """Set every cell from the fine lattice ``rho``: a refined cell its
    slot, a coarse cell the float32 mean of its 8 slots."""
    cells, x, y, z, refined = _cells(grid)
    means = blocks(rho).mean(axis=3, dtype=F32)
    vals = np.where(refined, rho[z, y, x], means[z // 2, y // 2, x // 2])
    grid.set("density", cells, vals.astype(F32))


def take_density(grid, n: int):
    """The grid's density on the fine lattice (a coarse cell in each of
    its 8 slots, as float32) and its per-root level mask."""
    cells, x, y, z, refined = _cells(grid)
    vals = np.asarray(grid.get("density", cells)).astype(F32)
    level = np.zeros((n, n, n), np.int8)
    level[z[refined] // 2, y[refined] // 2, x[refined] // 2] = 1
    root = np.zeros((n, n, n), F32)
    c = ~refined
    root[z[c] // 2, y[c] // 2, x[c] // 2] = vals[c]
    rho = fine(root)
    rho[z[refined], y[refined], x[refined]] = vals[refined]
    return rho, level


# -- the harness's hooks -----------------------------------------------

def build(config, traffic, devices):
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection_amr import AmrAdvection

    if int(traffic["mesh_devices"]) != len(devices):
        raise ValueError(f"the traffic spans {traffic['mesh_devices']} "
                         f"devices, the cell gives {len(devices)}")
    n = int(config["n"])
    amr = AmrAdvection(length=(n, n, n),
                       max_refinement_level=int(config["max_refinement_level"]),
                       mesh=default_mesh(devices), cfl=float(config["cfl"]),
                       dtype=jnp.dtype(config["dtype"]), **criterion(config))
    amr.grid.data["density"].block_until_ready()
    return {"amr": amr, "config": config, "traffic": traffic,
            "devices": devices, "n": n,
            "dt": time_step(n, float(config["cfl"]))}


def load(model, seed):
    """Image ``seed % 8`` of the fixed initial density, refined as the
    reference's initial refinement refines it."""
    amr, n = model["amr"], model["n"]
    model["image"] = int(seed) % IMAGES
    field = image(initial_density(n), model["image"])
    put_density(amr.grid, coarsen(field, np.zeros((n, n, n), np.int8)))
    amr.adapt()
    put_density(amr.grid, field)
    amr.grid.data["density"].block_until_ready()
    model["cycles"] = 0


def cycle(model, spans) -> int:
    """``run_fused(steps_per_cycle)`` then ``adapt()``; returns the cell
    updates of its steps (the live cells times the steps). The wait for
    the steps sits between the two, so that the adapt epoch's span holds
    its own work only (its first read of the device would wait for them
    anyway)."""
    amr, spc = model["amr"], int(model["traffic"]["steps_per_cycle"])
    cells = int(np.sum(amr.grid.plan.n_local))
    with spans("call"):
        amr.run_fused(spc, model["dt"])
    with spans("wait"):
        amr.grid.data["density"].block_until_ready()
    with spans("adapt"):
        amr.adapt()
    model["cycles"] += 1
    return cells * spc


def warm(model):
    """``warm_cycles`` whole cycles, then a zero-step call and the
    criterion stencil, which compile the programs that the window's
    first cycle runs on the new plan; neither changes the state."""
    for _ in range(int(model["traffic"]["warm_cycles"])):
        cycle(model, lambda name: contextlib.nullcontext())
    model["amr"].run_fused(0, model["dt"])
    model["amr"].compute_max_diff()
    model["amr"].grid.data["density"].block_until_ready()


def window(model, seconds, spans):
    """``window_cycles`` whole cycles (``seconds`` is not read), then a
    device sync; XLA compiles that start in the window are counted."""
    amr, traffic = model["amr"], model["traffic"]
    cycles = int(traffic["window_cycles"])
    spc = int(traffic["steps_per_cycle"])
    compiles = []

    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE:
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    updates = []
    try:
        t0 = time.perf_counter()
        for _ in range(cycles):
            updates.append(cycle(model, spans))
        with spans("sync"):
            amr.grid.data["density"].block_until_ready()
        elapsed = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    if compiles:
        print(f"compiled in the window: {compiles}", file=sys.stderr)
    itemsize = jnp.dtype(amr.grid.data["density"].dtype).itemsize
    return {
        "window_s": elapsed, "calls": cycles, "steps": cycles * spc,
        "cell_updates": sum(updates), "attempted": cycles * spc, "failed": 0,
        "compiles_in_window": len(compiles),
        # the least bytes any implementation of one run_fused call must
        # move: its stepped field read once and written once per step
        # (the mean over the window's calls)
        "least_bytes_per_call": [2 * itemsize * sum(updates) / cycles],
    }


def check(model, rec):
    """The grid's levels and density after every cycle of the warm-up
    and the window against the plain reference's, run from the same
    image on the host. The grid is freed before the reference runs."""
    t = time.perf_counter()
    cfg, n = model["config"], model["n"]
    amr = model.pop("amr")
    got, got_level = take_density(amr.grid, n)
    del amr
    crit = criterion(cfg)
    rho, level = initial_state(image(initial_density(n), model["image"]),
                               **crit)
    spc = int(model["traffic"]["steps_per_cycle"])
    for _ in range(model["cycles"]):
        for _ in range(spc):
            rho = step(rho, level, model["dt"])
        rho, level, _, _ = adapt(rho, level, **crit)
    values = {"level_mismatch": int(np.sum(level != got_level)),
              "max_abs_err": float(np.max(np.abs(got - rho)))}
    out = {k: {"value": v, "limit": cfg["limit"][k],
               "ok": bool(np.isfinite(v) and v <= cfg["limit"][k])}
           for k, v in values.items()}
    print(f"check took {time.perf_counter() - t:.1f} s; image "
          f"{model['image']}, {model['cycles']} cycles, "
          f"{cell_count(level)} cells", file=sys.stderr)
    return out
