"""Model-zoo throughput.

Model legs, JSON rows to stdout like the other bench emitters:
cell-updates/s per zoo model (advection / MHD / Vlasov) through the
fused ``Grid.run_steps`` loop on one device: trend keys
``advect<n>_updates_per_sec`` / ``mhd<n>_updates_per_sec`` /
``vlasov<n>_updates_per_sec`` (``bench/trend.py`` tracks
``*updates_per_sec`` higher-is-better unchanged). The MHD number counts
cell-updates across BOTH operator-split passes; the Vlasov row also
reports ``phase_updates_per_sec`` (cells x Nv — the wide payload's true
element throughput).

Every leg follows the null-on-failure discipline: a failed leg emits
``null`` metrics and the bench exits 0 (never a fabricated number).
Devices come from ``jax.devices()`` in process.

Run:  timeout -k 10 900 python bench/models_bench.py [--n 16]
      [--steps 40]

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

# the CPU host, set BEFORE jax loads (the conftest discipline)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=16,
                    help="cube edge for the model legs (default 16)")
    ap.add_argument("--steps", type=int, default=40)
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
    emit({"summary": summary})
    return 0


if __name__ == "__main__":
    sys.exit(main())
