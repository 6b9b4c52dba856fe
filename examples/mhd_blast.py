"""Model-zoo walkthrough: an MHD blast wave through the whole stack.

1. A magnetized Sedov-style pressure blast (GridMHD) advances via the
   two operator-split passes — the hydro Rusanov flux pass exchanges
   ONLY the hydro fields' ghosts, the CT/divergence-cleaning pass
   ONLY the B fields' — and conservation of mass/momentum/energy/B
   is checked against the integrity layer's drift tolerance.
2. The same physics serves as a FLEET kernel: a mixed mini-fleet
   (advect_x + mhd + vlasov — three buckets under one scheduler)
   runs to completion with every job's digest bitwise equal to its
   solo ``Grid.run_steps`` run.

Run: python examples/mhd_blast.py
"""

import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main():
    from dccrg_tpu import integrity
    from dccrg_tpu.fleet import FleetJob, run_solo
    from dccrg_tpu.models import GridMHD
    from dccrg_tpu.models.mhd import MHD_ALL
    from dccrg_tpu.scheduler import FleetScheduler

    # -- 1. the blast, conservation pinned ----------------------------
    m = GridMHD(n=12)
    before = m.conserved_sums()
    dt = m.run(20)
    after = m.conserved_sums()
    print(f"blast: 20+20 split steps at dt={dt:.4f}")
    for name in MHD_ALL:
        drift = abs(after[name] - before[name])
        tol = integrity.sum_tolerance(before[name], 12 ** 3, steps=20)
        status = "ok" if drift <= tol else "DRIFTED"
        print(f"  sum({name}): {before[name]:+.6f} -> "
              f"{after[name]:+.6f}  (|drift| {drift:.2e} "
              f"<= tol {tol:.2e}: {status})")
        assert drift <= tol, name

    # -- 2. the mixed mini-fleet --------------------------------------
    jobs = [FleetJob(f"{k}0", kernel=k, length=(6, 6, 6), n_steps=8,
                     seed=7, checkpoint_every=4)
            for k in ("advect_x", "mhd", "vlasov")]
    solo = {j.name: run_solo(FleetJob(
        j.name, kernel=j.kernel, length=j.length, n_steps=j.n_steps,
        seed=j.seed)) for j in jobs}
    with tempfile.TemporaryDirectory(prefix="dccrg_zoo_") as wd:
        report = FleetScheduler(wd, jobs, quantum=4).run()
    for name, row in sorted(report.items()):
        match = "bitwise == solo" if row["digest"] == solo[name] \
            else "MISMATCH"
        print(f"  fleet {name}: {row['status']} at step "
              f"{row['steps']} ({match})")
        assert row["digest"] == solo[name], name
    print("mixed-kernel fleet OK: 3 kernels, 3 buckets, one "
          "scheduler, all digests solo-bitwise")


if __name__ == "__main__":
    main()
