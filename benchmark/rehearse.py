#!/usr/bin/env python3
"""Chipless compile rehearsal: each cell's timed program, at the cell's
own size, compiled for a described TPU v5e (on-chip-measurement guide
§2.3). Nothing runs on a chip; the grid is built on CPU devices and
lends the program its shapes.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 benchmark/rehearse.py <cell> [<cell> ...]

Prints one JSON line per cell with ``memory_analysis()`` of the
compiled program (bytes per device) and the collectives in it. The
benchmark's runs do not use this file.
"""

import importlib.util
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def shapes_on(mesh, axis, arrays):
    """ShapeDtypeStructs of ``arrays`` placed as the grid places them."""
    out = []
    for a in arrays:
        a = jnp.asarray(a)
        split = getattr(a.sharding, "spec", P()) == P(axis)
        out.append(jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, P(axis) if split else P())))
    return out


def advection(config, chips, topo):
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection import GridAdvection

    solver = GridAdvection(n=config["n"], nz=config["nz"], cfl=config["cfl"],
                           mesh=default_mesh(jax.devices()[:chips]),
                           dtype=jnp.dtype(config["dtype"]))
    grid = solver.grid
    args = (solver._kernel, ["density", "vx", "vy"], ["density"])
    # the chip overlaps the halo exchange by default: build its tables too
    os.environ["DCCRG_OVERLAP"] = "1"
    grid.compile_step_loop(*args, n_extra=1)
    grid.mesh = Mesh(np.array(topo.devices[:chips]), ("dev",))
    grid._program_cache.clear()
    fn, tables, static_in = grid.compile_step_loop(*args, n_extra=1)
    scalar = NamedSharding(grid.mesh, P())
    shapes = ([jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar)]
              + shapes_on(grid.mesh, grid.axis,
                          [*tables, *(grid.data[f] for f in (*static_in, "density"))])
              + [jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar)])
    return fn.lower(*shapes).compile()


def main(cells):
    from jax.experimental import topologies

    spec = importlib.util.spec_from_file_location("bench_run", HERE / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    bench = run.read_json(HERE.parent / "BENCHMARK.json")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    for name in cells:
        cell = run.resolve_cell(bench, name, HERE.parent)
        chips = int(cell["cell"]["chips"])
        compiled = advection(cell["config"], chips, topo)
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "cell": name, "topology": "v5e:2x2", "devices": chips,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "collective_permutes": text.count("collective-permute-start"),
            "all_reduces": text.count("all-reduce-start") + text.count(" all-reduce("),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
