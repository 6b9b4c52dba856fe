#!/usr/bin/env python
"""A/B the overlapped fused step (DCCRG_OVERLAP) against the
sequential exchange -> kernel path on the GridAdvection workload.

The overlap launches the halo ppermutes before the bulk kernel and
redoes only the outer rows after the scatter (grid.py
compile_step_loop), mirroring the reference's
solve-inner-while-messages-fly split (dccrg.hpp:5046-5413,
tests/advection/2d.cpp:327-343). On accelerators the collective can
fly under the stencil; on the CPU backend collectives are memcpys so
the extra outer pass is pure overhead — this script measures both so
the default (_use_overlap: accelerators only) stays justified by data.

Usage: python bench/overlap_bench.py [--n 128] [--steps 10] [--cpu]
Prints one JSON line with both step rates.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def run_leg(overlap, n, steps):
    os.environ["DCCRG_OVERLAP"] = "1" if overlap else "0"
    from dccrg_tpu.models.advection import GridAdvection

    solver = GridAdvection(n=n, nz=n)
    dt = 0.5 * solver.max_time_step()
    solver.run(1, dt)  # warmup/compile
    solver.checksum()
    t0 = time.perf_counter()
    solver.run(steps, dt)
    solver.checksum()
    elapsed = time.perf_counter() - t0
    return n * n * n * steps / elapsed, solver.l2_error()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (virtual device mesh "
                    "via XLA_FLAGS still applies)")
    args = ap.parse_args()

    # in process: on a chip, a probing child would hold the device
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()

    ups = {}
    l2 = {}
    for mode in ("sequential", "overlap"):
        ups[mode], l2[mode] = run_leg(mode == "overlap", args.n, args.steps)
        print(f"{mode}: {ups[mode]:.4g} updates/s (l2 {l2[mode]:.3e})",
              file=sys.stderr)
    print(json.dumps({
        "metric": f"overlap A/B grid advection {args.n}^3",
        "platform": devices[0].platform,
        "n_devices": len(devices),
        "sequential_updates_per_sec": ups["sequential"],
        "overlap_updates_per_sec": ups["overlap"],
        "overlap_speedup": ups["overlap"] / ups["sequential"],
    }))


if __name__ == "__main__":
    main()
