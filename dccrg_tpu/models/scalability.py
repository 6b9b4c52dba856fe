"""Synthetic weak/strong-scaling harness.

Equivalent of the reference's scalability suite
(tests/scalability/scalability.cpp:39-160): a configurable cost model —
bytes transferred per cell and artificial compute per cell — measuring
solve time vs halo-exchange time per step, plus a sweep driver over
parallelism (tests/scalability/run_tests.py:28-39 sweeps MPI process
counts; here the sweep varies device-mesh size).

The per-cell payload is ``floats_per_cell`` f32 lanes (the reference's
``bytes_per_cell`` knob); the solve does ``work_iters`` dependent
fused multiply-adds per lane inside ``lax.fori_loop`` (the reference's
busy-wait ``solution_time`` knob, :61-75 — a compute knob XLA cannot
constant-fold because each iteration depends on the previous).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..grid import Grid
from ..utils import PhaseTimer
from ..utils.profiling import halo_bytes_per_update


class ScalabilityModel:
    def __init__(self, length=(16, 16, 16), floats_per_cell: int = 8,
                 work_iters: int = 64, mesh=None, partition=None,
                 neighborhood_length: int = 1):
        self.floats_per_cell = int(floats_per_cell)
        self.work_iters = int(work_iters)
        self.grid = (
            Grid(cell_data={"payload": ((self.floats_per_cell,), jnp.float32)})
            .set_initial_length(length)
            .set_periodic(True, True, True)
            .set_neighborhood_length(neighborhood_length)
            .initialize(mesh, partition=partition)
        )
        cells = self.grid.get_cells()
        rng = np.random.default_rng(0)
        self.grid.set(
            "payload", cells,
            rng.standard_normal((len(cells), self.floats_per_cell)).astype(np.float32),
        )
        self.timer = PhaseTimer()
        iters = self.work_iters

        def kernel(cell, nbr, offs, mask):
            # average of neighbors (consumes the halo) ...
            cnt = jnp.maximum(jnp.sum(mask, axis=1), 1)
            avg = jnp.sum(jnp.where(mask[..., None], nbr["payload"], 0.0), axis=1)
            avg = avg / cnt[:, None].astype(jnp.float32)
            # ... then a dependent FMA chain per lane: the tunable
            # compute cost (scalability.cpp:61-75's busy loop)
            def body(_, v):
                return v * jnp.float32(1.0000001) + jnp.float32(1e-7)
            out = lax.fori_loop(0, iters, body, 0.5 * (cell["payload"] + avg))
            return {"payload": out}

        self._kernel = kernel

    def step(self) -> None:
        """One timed step: halo exchange then synthetic solve (the
        reference times these phases separately, scalability.cpp:124-160)."""
        g = self.grid
        with self.timer.phase("halo"):
            g.update_copies_of_remote_neighbors(fields=["payload"])
            jax.block_until_ready(g.data["payload"])
        with self.timer.phase("solve"):
            g.apply_stencil(self._kernel, ["payload"], ["payload"])
            jax.block_until_ready(g.data["payload"])

    def run(self, steps: int = 10, warmup: int = 2) -> dict:
        """Report per-step timings + transfer volume, the reference's
        printed metrics (scalability.cpp:124-160)."""
        for _ in range(warmup):
            self.step()
        self.timer.reset()
        t0 = time.perf_counter()
        for _ in range(steps):
            self.step()
        total = time.perf_counter() - t0
        rep = self.timer.report()
        n_cells = len(self.grid.get_cells())
        return {
            "n_devices": self.grid.n_dev,
            "n_cells": n_cells,
            "steps": steps,
            "solve_s_per_step": rep["solve"]["total"] / steps,
            "halo_s_per_step": rep["halo"]["total"] / steps,
            "total_s_per_step": total / steps,
            "cell_updates_per_sec": n_cells * steps / total,
            "halo_bytes_per_step": halo_bytes_per_update(self.grid),
        }


def run_sweep(device_counts=None, length=(16, 16, 16), floats_per_cell: int = 8,
              work_iters: int = 64, steps: int = 10, weak: bool = False) -> list:
    """Strong (fixed size) or weak (size grows with devices in x)
    scaling sweep over device counts — the run_tests.py driver."""
    from jax.sharding import Mesh

    devices = jax.devices()
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8) if n <= len(devices)]
    dropped = [n for n in device_counts if n > len(devices)]
    if dropped:
        import sys
        print(f"skipping device counts {dropped}: only {len(devices)} "
              f"device(s) available", file=sys.stderr)
        device_counts = [n for n in device_counts if n <= len(devices)]
    results = []
    for n in device_counts:
        dims = (length[0] * n, length[1], length[2]) if weak else length
        mesh = Mesh(np.array(devices[:n]), ("dev",))
        model = ScalabilityModel(
            dims, floats_per_cell=floats_per_cell, work_iters=work_iters, mesh=mesh
        )
        results.append(model.run(steps=steps))
    return results


if __name__ == "__main__":
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--length", type=int, nargs=3, default=[16, 16, 16])
    p.add_argument("--floats-per-cell", type=int, default=8)
    p.add_argument("--work-iters", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--weak", action="store_true")
    p.add_argument("--devices", type=int, nargs="*", default=None)
    a = p.parse_args()
    for row in run_sweep(a.devices, tuple(a.length), a.floats_per_cell,
                         a.work_iters, a.steps, a.weak):
        print(json.dumps(row))
