#!/usr/bin/env python
"""Game of life with checkpoint + VTK output (reference
examples/game_of_life_with_output.cpp + dc2vtk.cpp): saves the game
state to a .dc file each turn, then converts the checkpoints to VTK
with the standalone converter.

Run (defaults to a virtual 8-device CPU mesh):
    python examples/game_of_life_with_output.py [outdir]
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
from dccrg_tpu.utils import dc_to_vtk


def main(outdir: str = ".") -> None:
    gol = GameOfLife(length=(10, 10, 1))
    gol.set_alive([1 + 4 + y * 10 for y in (3, 4, 5)])

    fields = {"live": ((), np.int32), "total": ((), np.int32)}
    for turn in range(5):
        dc = f"{outdir}/gol_{turn:05d}.dc"
        gol.grid.save_grid_data(dc)
        dc_to_vtk(dc, dc.replace(".dc", ".vtk"), fields=fields)
        print(f"turn {turn}: wrote {dc} (+ .vtk), "
              f"{len(gol.alive_cells())} cells alive")
        gol.step()
    print("PASSED")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else ".")
