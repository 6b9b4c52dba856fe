"""Grid construction speed (the reference's tests/init suite).

Times Grid.initialize at growing sizes on the host (structure building
is host work in this design; the reference's equivalent is
create_level_0_cells + initialize_neighbors, dccrg.hpp:8089-8420).

Run: python bench/init_bench.py [--max 256]
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# --devices N (parsed pre-jax): virtual CPU device count, so the
# multi-device closed-form rows below measure a real n-device mesh
_n_dev = 1
for _i, _a in enumerate(sys.argv):
    if _a == "--devices":
        _n_dev = int(sys.argv[_i + 1])
    elif _a.startswith("--devices="):
        _n_dev = int(_a.split("=", 1)[1])
if _n_dev > 1:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={_n_dev}"
        )

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import dccrg_tpu as dt  # noqa: E402


def time_init(n, partition):
    t0 = time.time()
    g = (
        dt.Grid(cell_data={"density": jnp.float32})
        .set_initial_length((n, n, n))
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .initialize(partition=partition)
    )
    dt_s = time.time() - t0
    n_cells = len(g.plan.cells)
    del g
    return dt_s, n_cells


def time_amr_commit(n):
    """One AMR commit on an n^3 grid: refine a z-slab of 1/64 of the
    level-0 cells (the hybrid builder's hard set is the slab surface),
    then a second commit on the already-refined grid."""
    g = (
        dt.Grid(cell_data={"density": jnp.float32})
        .set_initial_length((n, n, n))
        .set_maximum_refinement_level(1)
        .set_neighborhood_length(1)
        .initialize()
    )
    cells = g.plan.cells
    nref = len(cells) // 64
    for c in cells[:nref]:
        g.refine_completely(c)
    t0 = time.time()
    g.stop_refining()
    first = time.time() - t0
    cells = g.plan.cells
    lvl0 = cells[cells <= np.uint64(n) ** 3]
    for c in lvl0[-nref:]:
        g.refine_completely(c)
    t0 = time.time()
    g.stop_refining()
    second = time.time() - t0
    n_cells = len(g.plan.cells)
    del g
    return first, second, n_cells


def time_field_init(n):
    """GridAdvection construction: structure + ON-device field init
    (density/vx/vy synthesized from the sharded row-id array — no host
    center arrays; the reference's initialize.hpp:36-80 one-pass
    equivalent). Reported both as the constructor wall time (dispatch)
    and with the field computation synced, which on the CPU backend
    executes the trig on host cores; on TPU it runs on chip."""
    from dccrg_tpu.models.advection import GridAdvection

    t0 = time.time()
    a = GridAdvection(n=n)
    construct = time.time() - t0
    for f in a.grid.data.values():
        f.block_until_ready()
    synced = time.time() - t0
    n_cells = len(a.grid.plan.cells)
    del a
    return construct, synced, n_cells


def time_multi_device_init(n, n_dev):
    """n-device uniform init + first roll plan: block partitions take
    the closed-form multi-device plan (no dense tables); morton takes
    the dense path — the two rows bound the closed-form win."""
    from jax.sharding import Mesh

    from dccrg_tpu.grid import DEFAULT_NEIGHBORHOOD_ID

    # a CPU-only host bench: probe the CPU backend in a killable
    # subprocess before any jax work
    from dccrg_tpu.resilience import safe_devices

    devices = safe_devices(timeout=120, retries=1, platform="cpu")
    if len(devices) < n_dev:
        raise RuntimeError(
            f"--devices {n_dev} requested but only {len(devices)} "
            "devices exist (inherited XLA_FLAGS already pins "
            "xla_force_host_platform_device_count?)"
        )
    out = []
    mesh = Mesh(np.array(devices[:n_dev]), ("dev",))
    for part in ("block", "morton"):
        t0 = time.time()
        g = (
            dt.Grid(cell_data={"density": jnp.float32})
            .set_initial_length((n, n, n))
            .set_maximum_refinement_level(0)
            .set_neighborhood_length(0)
            .initialize(mesh, partition=part)
        )
        hood = g.plan.hoods[DEFAULT_NEIGHBORHOOD_ID]
        hood.roll_plan(g.plan.L)
        secs = time.time() - t0
        closed = hood.closed_form is not None
        out.append({
            "size": f"{n}^3 x {n_dev} devices", "partition": part,
            "seconds": round(secs, 2), "closed_form": closed,
        })
        del g
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max", type=int, default=256)
    ap.add_argument("--amr-max", type=int, default=128)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()
    sizes = [s for s in (64, 128, 256, 512) if s <= args.max]
    results = []
    for n in sizes:
        for part in ("block", "morton"):
            # best of 2: the first touch of a fresh heap region pays
            # page faults that later builds (and long-running apps)
            # amortize away
            secs, n_cells = min(time_init(n, part) for _ in range(2))
            results.append({
                "size": f"{n}^3", "partition": part, "seconds": round(secs, 2),
                "cells_per_s": round(n_cells / secs),
            })
            print(json.dumps(results[-1]))
    construct, synced, n_cells = time_field_init(min(args.max, 256))
    results.append({
        "size": f"GridAdvection {min(args.max, 256)}^3 field init",
        "construct_s": round(construct, 2), "synced_s": round(synced, 2),
        "cells": n_cells,
    })
    print(json.dumps(results[-1]))
    if args.devices > 1:
        for row in time_multi_device_init(min(args.max, 256), args.devices):
            results.append(row)
            print(json.dumps(row))
    for n in (s for s in (64, 128, 256) if s <= args.amr_max):
        first, second, n_cells = time_amr_commit(n)
        results.append({
            "size": f"{n}^3 + 1/64 refined", "amr_commit_s": round(first, 2),
            "amr_recommit_s": round(second, 2), "cells": n_cells,
        })
        print(json.dumps(results[-1]))
    return results


if __name__ == "__main__":
    main()
