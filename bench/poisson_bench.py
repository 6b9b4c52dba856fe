"""Poisson solver benchmark: CG iterations/sec through the Pallas
matvec vs the XLA dense path (the BASELINE.json poisson leg).

Run on the chip: ``python bench/poisson_bench.py [--n 256]``.
On CPU hosts: ``JAX_PLATFORMS=cpu`` (interpret-mode kernel; numbers
only validate the flow, not performance).
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    # in process: on a chip, a probing child would hold the device
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"

    import numpy as np
    import jax.numpy as jnp

    from dccrg_tpu.models.poisson import DensePoissonSolver
    from dccrg_tpu.ops.poisson_kernel import make_laplacian_matvec

    n = args.n
    shape = (n, n, n)
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.random(shape).astype(np.float32))

    mv_pallas = make_laplacian_matvec(shape, interpret=not on_tpu)
    dense = DensePoissonSolver(shape)

    def dense_mv(x):
        arrays = {"p": x, "Ap": x}
        return dense._matvec(arrays)["Ap"]

    results = {"size": f"{n}^3", "platform": devices[0].platform}
    float(jnp.sum(p))  # pre-compile the sync reduction OUTSIDE timing
    for name, mv in (("pallas", mv_pallas), ("xla_dense", dense_mv)):
        out = mv(p)
        out.block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = mv(out)
        float(jnp.sum(out))  # forced scalar readback sync
        dt = time.perf_counter() - t0
        results[f"{name}_matvecs_per_sec"] = args.iters / dt
        results[f"{name}_cell_updates_per_sec"] = n**3 * args.iters / dt
    results["pallas_vs_dense"] = (
        results["pallas_matvecs_per_sec"] / results["xla_dense_matvecs_per_sec"]
    )
    print(json.dumps(results))


if __name__ == "__main__":
    main()
