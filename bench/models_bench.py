"""Model-zoo throughput + the ghost-split outer-re-pass reduction.

Two leg families, JSON rows to stdout like the other bench emitters:

- **model legs** — cell-updates/s per zoo model (advection / MHD /
  Vlasov) through the fused ``Grid.run_steps`` loop on one device:
  trend keys ``advect<n>_updates_per_sec`` /
  ``mhd<n>_updates_per_sec`` / ``vlasov<n>_updates_per_sec``
  (``bench/trend.py`` tracks ``*updates_per_sec`` higher-is-better
  unchanged). The MHD number counts cell-updates across BOTH
  operator-split passes; the Vlasov row also reports
  ``phase_updates_per_sec`` (cells x Nv — the wide payload's true
  element throughput).

- **ghost-split leg** (``--split``, needs the multi-device mesh this
  file self-configures) — the per-field ghost-split overlap
  (``DCCRG_GHOST_SPLIT``) vs the full outer re-pass on the
  multi-device MHD model: emits ``outer_repass_rows_full`` /
  ``outer_repass_rows_split`` (outer row-slots recomputed per
  super-step, the reduction the split buys) plus the directional
  trend key ``ghost_split_rows_vs_baseline`` (full/split ratio,
  higher is better), and ASSERTS the two programs' final states are
  BITWISE identical per leg — the bench doubles as the parity check.

Every leg follows the null-on-failure discipline: a failed leg emits
``null`` metrics and the bench exits 0 (never a fabricated number).
Devices come from ``jax.devices()`` in process.

Run:  timeout -k 10 900 python bench/models_bench.py [--n 16]
      [--steps 40] [--no-split]

(``timeout -k`` so a wedged backend can never hang CI; 900 s covers
the CPU host with margin.)
"""

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# the ghost-split leg needs a multi-device mesh: force the virtual
# CPU mesh BEFORE jax loads (the conftest discipline)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def emit(row):
    print(json.dumps(row), flush=True)


def probe():
    import jax

    return jax.devices()


def _bench_loop(run_fn, steps, reps=3):
    """Best-of-reps wall for ``run_fn(steps)`` (first call compiles
    outside the window)."""
    run_fn(1)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run_fn(steps)
        best = min(best, time.perf_counter() - t0)
    return best


def model_leg(name, n, steps):
    from dccrg_tpu.models import GridAdvection, GridMHD, GridVlasov

    row = {"leg": name, "n": n, "steps": steps}
    try:
        if name == "advect":
            m = GridAdvection(n=n, nz=n)
            dt = 0.4 * m.max_time_step()
            wall = _bench_loop(lambda s: m.run(s, dt=dt), steps)
            per_pass = 1
        elif name == "mhd":
            m = GridMHD(n=n)
            dt = 0.3 * m.max_time_step()
            wall = _bench_loop(lambda s: m.run(s, dt=dt), steps)
            per_pass = 2  # hydro + cleaning passes per super-step
        else:
            m = GridVlasov(n=n, nv=16)
            wall = _bench_loop(lambda s: m.run(s, dt=0.03), steps)
            per_pass = 1
            row["nv"] = 16
            row["phase_updates_per_sec"] = round(
                n ** 3 * 16 * steps / wall, 1)
        ups = n ** 3 * steps * per_pass / wall
        row["wall_s"] = round(wall, 4)
        row[f"{name}{n}_updates_per_sec"] = round(ups, 1)
    except Exception as e:  # noqa: BLE001 - null-on-failure discipline
        traceback.print_exc()
        row["error"] = f"{type(e).__name__}: {e}"
        row[f"{name}{n}_updates_per_sec"] = None
    return row


def ghost_split_leg(n, nz, steps):
    """Split vs full outer re-pass on the multi-device MHD model:
    bitwise parity asserted, row counts + wall per leg."""
    from dccrg_tpu import checkpoint
    from dccrg_tpu.models import GridMHD

    row = {"leg": "ghost_split", "n": n, "nz": nz, "steps": steps,
           "n_dev": len(jax.devices())}
    try:
        os.environ["DCCRG_OVERLAP"] = "1"
        out = {}
        for split in (False, True):
            os.environ["DCCRG_GHOST_SPLIT"] = "1" if split else "0"
            m = GridMHD(n=n, nz=nz)
            dt = 0.3 * m.max_time_step()
            wall = _bench_loop(lambda s: m.run(s, dt=dt), steps)
            # per-super-step recompute slots = hydro + cleaning pass:
            # one more instrumented super-step reads both passes'
            # counts (last_overlap reflects the latest compile)
            from dccrg_tpu.models.mhd import (MHD_ALL, MHD_BFIELD,
                                              MHD_HYDRO,
                                              make_mhd_pass_kernels)
            import jax.numpy as jnp

            hk, bk = make_mhd_pass_kernels()
            lam = jnp.float32(dt * n)
            counts = []
            for kern, exch in ((hk, MHD_HYDRO), (bk, MHD_BFIELD)):
                m.grid.run_steps(kern, MHD_ALL, MHD_ALL, 1,
                                 exchange_fields=exch,
                                 extra_args=(lam,))
                counts.append(dict(m.grid.last_overlap))
            rows_per_super = sum(c["rows_split"] for c in counts)
            rows_full = sum(c["rows_full"] for c in counts)
            out[split] = {
                "digest": checkpoint.state_digest(m.grid),
                "wall_s": wall,
                "rows": rows_per_super,
                "rows_full": rows_full,
                "mode": [c["mode"] for c in counts],
            }
        # the parity assertion: one extra super-step ran on each leg
        # with identical inputs, so the digests must still agree
        assert out[False]["digest"] == out[True]["digest"], (
            "ghost-split vs full outer re-pass digests diverged")
        row["outer_repass_rows_full"] = out[False]["rows"]
        row["outer_repass_rows_split"] = out[True]["rows"]
        row["ghost_split_rows_vs_baseline"] = round(
            out[False]["rows"] / max(1, out[True]["rows"]), 3)
        row["wall_full_s"] = round(out[False]["wall_s"], 4)
        row["wall_split_s"] = round(out[True]["wall_s"], 4)
        row["modes"] = {"full": out[False]["mode"],
                        "split": out[True]["mode"]}
        row["bitwise_parity"] = True
    except Exception as e:  # noqa: BLE001 - null-on-failure discipline
        traceback.print_exc()
        row["error"] = f"{type(e).__name__}: {e}"
        row["outer_repass_rows_full"] = None
        row["outer_repass_rows_split"] = None
        row["ghost_split_rows_vs_baseline"] = None
        row["bitwise_parity"] = None
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=16,
                    help="cube edge for the model legs (default 16)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--split-n", type=int, default=8,
                    help="ghost-split leg edge (x --split-nz slabs)")
    ap.add_argument("--split-nz", type=int, default=80)
    ap.add_argument("--no-split", action="store_true",
                    help="skip the multi-device ghost-split leg")
    args = ap.parse_args(argv)

    devs = probe()
    if not devs:
        emit({"error": "no devices (probe failed)", "legs": None})
        return 0
    summary = {}
    for name in ("advect", "mhd", "vlasov"):
        row = model_leg(name, args.n, args.steps)
        emit(row)
        for k, v in row.items():
            if k.endswith("updates_per_sec"):
                summary[k] = v
    if not args.no_split:
        row = ghost_split_leg(args.split_n, args.split_nz,
                              max(4, args.steps // 8))
        emit(row)
        for k in ("outer_repass_rows_full", "outer_repass_rows_split",
                  "ghost_split_rows_vs_baseline"):
            summary[k] = row.get(k)
    emit({"summary": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
