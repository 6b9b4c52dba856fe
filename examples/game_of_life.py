#!/usr/bin/env python
"""Game of life with split-phase halo updates (reference
examples/game_of_life.cpp): start the remote-copy update, do the work
that doesn't need fresh ghosts, finish receives before reading
neighbors, finish sends before overwriting local state — the
reference's solve-inner-while-messages-fly structure, expressed
through the same four-call API. (The fused ``Grid.run_steps`` path
exchanges first and then steps every cell inside one XLA program;
this example demonstrates the HOST-side split-phase parity API.)

The board is verified against a pure-numpy life simulation every turn,
and per-turn speed statistics are printed like the reference's.

Run (defaults to a virtual 8-device CPU mesh):
    python examples/game_of_life.py
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_plat = os.environ.get("DCCRG_EXAMPLE_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _plat
_flags = os.environ.get("XLA_FLAGS", "")
if _plat == "cpu" and "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", _plat)

import numpy as np
import jax.numpy as jnp

from dccrg_tpu.grid import Grid

N = 60
TURNS = 20


def numpy_life_step(board):
    """Zero-boundary (non-periodic) life step, the oracle."""
    nbrs = np.zeros_like(board, dtype=np.int64)
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            if dx == dy == 0:
                continue
            sh = np.zeros_like(board, dtype=np.int64)
            xs = slice(max(dx, 0), board.shape[0] + min(dx, 0))
            xd = slice(max(-dx, 0), board.shape[0] + min(-dx, 0))
            ys = slice(max(dy, 0), board.shape[1] + min(dy, 0))
            yd = slice(max(-dy, 0), board.shape[1] + min(-dy, 0))
            sh[xd, yd] = board[xs, ys]
            nbrs += sh
    return (nbrs == 3) | (board.astype(bool) & (nbrs == 2))


def count_kernel(cell, nbr, offs, mask):
    return {"nbrs": jnp.sum(jnp.where(mask, nbr["alive"], 0), axis=1)}


def rules_kernel(cell, nbr, offs, mask):
    nb = cell["nbrs"]
    alive = (nb == 3) | ((cell["alive"] > 0) & (nb == 2))
    return {"alive": alive.astype(jnp.int32)}


def main() -> None:
    grid = (
        Grid(cell_data={"alive": jnp.int32, "nbrs": jnp.int32})
        .set_initial_length((N, N, 1))
        .set_neighborhood_length(1)
        .set_maximum_refinement_level(0)
        .initialize(partition="block")
    )
    grid.balance_load()

    rng = np.random.default_rng(42)
    board = (rng.random((N, N)) < 0.3).astype(np.int32)
    cells = grid.plan.cells  # ids 1..N*N in x-fastest order
    grid.set("alive", cells, board.reshape(-1, order="F").astype(np.int32))

    n_inner = len(grid.inner_cells())
    n_outer = len(grid.outer_cells())
    t0 = time.perf_counter()
    for turn in range(TURNS):
        # start updating cell data from other devices; the work that
        # only needs local rows could proceed here (the reference
        # computes inner cells' neighbor counts now)
        grid.start_remote_neighbor_copy_updates(fields=["alive"])

        # fresh ghosts are needed to count neighbors: finish receives
        grid.wait_remote_neighbor_copy_update_receives()
        grid.apply_stencil(count_kernel, ["alive"], ["nbrs"])

        # local state may only change once sends are done
        grid.wait_remote_neighbor_copy_update_sends()
        grid.apply_stencil(rules_kernel, ["alive", "nbrs"], ["alive"])

        board = numpy_life_step(board).astype(np.int32)
        got = np.asarray(grid.get("alive", cells)).reshape((N, N), order="F")
        assert np.array_equal(got, board), f"turn {turn}: board diverged"
    elapsed = time.perf_counter() - t0

    total = TURNS * (n_inner + n_outer)
    print(f"inner cells {n_inner}, outer cells {n_outer}")
    print(f"{TURNS} turns verified against the numpy oracle")
    print(f"speed: {total / elapsed:.3g} cells/s ({elapsed:.2f}s)")
    print("PASSED")


if __name__ == "__main__":
    main()
