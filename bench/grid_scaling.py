"""Strong/weak scaling of the general-Grid fused step loop over the
virtual CPU mesh — the reference's scalability suite role
(tests/scalability, tests/game_of_life/scalability*.cpp) for the
framework path. The absolute numbers are CPU-host numbers; the point
is the scaling shape of exchange+stencil+apply as devices grow.

Run: python bench/grid_scaling.py [--n 64] [--steps 10]
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from dccrg_tpu.models.advection import GridAdvection  # noqa: E402


_devices = None


def _safe_device_list():
    # a CPU-only host bench: the CPU backend is probed once in a
    # killable subprocess, then cached
    global _devices
    if _devices is None:
        from dccrg_tpu.resilience import safe_devices

        _devices = safe_devices(timeout=120, retries=1, platform="cpu")
    return _devices


def run_once(n, nz, n_dev, steps):
    mesh = Mesh(np.array(_safe_device_list()[:n_dev]), ("dev",))
    s = GridAdvection(n=n, nz=nz, mesh=mesh)
    dt = 0.5 * s.max_time_step()
    s.run(1, dt)
    s.checksum()
    t0 = time.perf_counter()
    s.run(steps, dt)
    s.checksum()
    el = time.perf_counter() - t0
    return n * n * nz * steps / el


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    results = []
    base = None
    for n_dev in (1, 2, 4, 8):
        # strong scaling: fixed problem
        strong = run_once(args.n, args.n, n_dev, args.steps)
        # weak scaling: nz grows with devices
        weak = run_once(args.n, max(4, args.n // 8) * n_dev, n_dev, args.steps)
        if base is None:
            base = strong
        results.append({
            "devices": n_dev,
            "strong_updates_per_s": round(strong),
            "strong_speedup": round(strong / base, 2),
            "weak_updates_per_s": round(weak),
        })
        print(json.dumps(results[-1]))
    return results


if __name__ == "__main__":
    main()
