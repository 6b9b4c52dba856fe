#!/usr/bin/env python
"""Minimal stencil application: Conway's game of life on a 10x10 grid
(reference examples/simple_game_of_life.cpp) — a blinker oscillating
for 10 turns, verified every step.

Run (defaults to a virtual 8-device CPU mesh):
    python examples/simple_game_of_life.py
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# examples default to the virtual 8-device CPU mesh; set
# DCCRG_EXAMPLE_PLATFORM to run on another backend
_plat = os.environ.get("DCCRG_EXAMPLE_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _plat
_flags = os.environ.get("XLA_FLAGS", "")
if _plat == "cpu" and "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_platforms", _plat)

import numpy as np

from dccrg_tpu.models.game_of_life import GameOfLife


def main() -> None:
    gol = GameOfLife(length=(10, 10, 1))

    def cid(x, y):
        return 1 + x + y * 10

    vertical = [cid(4, 3), cid(4, 4), cid(4, 5)]
    horizontal = [cid(3, 4), cid(4, 4), cid(5, 4)]
    gol.set_alive(vertical)

    for turn in range(10):
        gol.step()
        expect = horizontal if turn % 2 == 0 else vertical
        got = np.sort(gol.alive_cells())
        assert np.array_equal(got, np.sort(expect)), (turn, got)
        print(f"turn {turn + 1}: alive = {got.tolist()}")
    print("PASSED")


if __name__ == "__main__":
    main()
