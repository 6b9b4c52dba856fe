"""Chipless compiles of the main-path programs for a described TPU v5e.

Nothing here runs: each test hands the TPU compiler the shapes of one
program and checks that it compiles (on-chip-measurement guide §2.3).
That catches what interpret mode cannot — Mosaic lowering refusals,
VMEM overruns, programs that do not fit HBM — at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the tests run
under several xdist workers. Keep every chip-compile test in this file
so they share that fixture's worker.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

V5E_HBM = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile written to the persistent cache cannot be read back
    # without the chip (it warns and recompiles): keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def fits_hbm(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return used < V5E_HBM


def compile_grid_step(grid, kernel, devices):
    """Lower and compile the advection ``run_steps`` program of ``grid``
    (one ``Grid.compile_step_loop``) for ``devices``. The grid was built
    on a CPU mesh of as many devices; building the program there first
    uploads and caches its plan tables. Then the test hands the grid a
    mesh of ``devices`` and rebuilds the program over it: the cached
    tables and the fields only lend their shapes."""
    args = (kernel, ["density", "vx", "vy"], ["density"])
    grid.compile_step_loop(*args, n_extra=1)
    grid.mesh = Mesh(np.array(devices), ("dev",))
    grid._program_cache.clear()
    fn, tables, static_in = grid.compile_step_loop(*args, n_extra=1)

    def spec(a):
        split = getattr(a.sharding, "spec", P()) == P(grid.axis)
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(grid.mesh, P(grid.axis) if split else P()))

    scalar = NamedSharding(grid.mesh, P())
    shapes = ([jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar)]
              + [spec(t) for t in tables]
              + [spec(grid.data[f]) for f in (*static_in, "density")]
              + [jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar)])
    with jax.enable_x64(False):  # as the program runs (see below)
        return fn.lower(*shapes).compile()


def advection_step_128(devices):
    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.advection import GridAdvection

    cpu = default_mesh(jax.devices()[:len(devices)])
    solver = GridAdvection(n=128, nz=128, mesh=cpu)
    return compile_grid_step(solver.grid, solver._kernel, devices)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rotation_kernel_compiles_512(one_chip, dtype):
    """The specialized Pallas advection kernel at the north-star size,
    one sub-step per pass: each sub-step is unrolled, and what Mosaic
    refuses it refuses at any depth (7, the default, compiles in 20-50 s
    where 1 takes 2-4 s)."""
    from dccrg_tpu.ops.advection_kernel import make_rotation_step

    n = 512
    step = make_rotation_step((n, n, n), dtype=jnp.dtype(dtype),
                              tile=(32, 128), steps_per_pass=1)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    # x64 off, as the program runs: the test session turns it on for
    # host index math, and Mosaic's lowering then recurses without end
    with jax.enable_x64(False):
        compiled = step.lower(
            sds((n, n, n), dtype), sds((1, n), jnp.float32),
            sds((n + 16, 1), jnp.float32), sds((), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert fits_hbm(compiled)


def test_bulk_executor_compiles_128(topo, monkeypatch):
    """The roll-plan Pallas bulk executor (DCCRG_BULK=pallas) replacing
    the grid step; interpret mode is off because the mesh is a TPU."""
    monkeypatch.setenv("DCCRG_BULK", "pallas")
    compiled = advection_step_128(topo.devices[:1])
    assert "tpu_custom_call" in compiled.as_text()
    assert fits_hbm(compiled)


def test_xla_step_program_compiles_128(topo):
    """The default XLA roll-gather step program of Grid.run_steps (a
    shard_map over the grid's mesh) on one chip."""
    compiled = advection_step_128(topo.devices[:1])
    assert "tpu_custom_call" not in compiled.as_text()
    assert fits_hbm(compiled)


def test_xla_step_program_compiles_128_mesh4(topo):
    """The step program on a 2x2 v5e: four z slabs of whole planes take
    the slab gather, so the bulk pass holds no scatter (the flat roll's
    fix-ups were one per slot and field). The halo exchange runs before
    the bulk pass, and no outer rows are recomputed after it."""
    compiled = advection_step_128(topo.devices[:4])
    assert fits_hbm(compiled)
    lines = compiled.as_text().splitlines()
    bulk_scatters = [ln for ln in lines
                     if " scatter(" in ln and "dccrg.bulk" in ln]
    assert not bulk_scatters
    assert any("dccrg.exchange" in ln for ln in lines)
    assert not any("dccrg.repass" in ln for ln in lines)
