#!/usr/bin/env python
"""Adaptive advection: the reference advection test's full loop
(tests/advection/2d.cpp) — upwind solve, adapt every 4 steps, balance
every 10 — with VTK snapshots of the refined grid.

Run (defaults to a virtual 8-device CPU mesh):
    python examples/amr_advection.py [steps] [outdir]
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

from dccrg_tpu.models.advection_amr import AmrAdvection


def main(steps: int = 20, outdir: str = ".") -> None:
    amr = AmrAdvection((16, 16, 1), max_refinement_level=2)
    m0 = amr.total_mass()
    for i in range(1, steps + 1):
        amr.step()
        if i % 4 == 0:
            created, removed = amr.adapt()
            print(f"step {i}: t={amr.time:.3f} cells={len(amr.grid.get_cells())} "
                  f"(+{len(created)}/-{len(removed)})")
        if i % 10 == 0:
            amr.balance()
            amr.grid.write_vtk_file(f"{outdir}/advection_{i:05d}.vtk",
                                    fields=["density"])
    m1 = amr.total_mass()
    print(f"mass drift: {abs(m1 - m0) / m0:.2e}")
    assert abs(m1 - m0) / m0 < 1e-4
    print("PASSED")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 20,
         sys.argv[2] if len(sys.argv) > 2 else ".")
