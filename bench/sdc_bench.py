"""Silent-data-corruption defense cost: what each SDC layer charges.

Three measurements over the same fleet workload (``--jobs`` jobs of
``--n``^3 cells, ``--steps`` steps, checkpoint cadence off so the
numbers are pure stepping):

- ``invariants`` — the in-program integrity invariants
  (``DCCRG_INTEGRITY=1``: fused entry/exit fingerprints +
  conservation sums + the per-quantum host compare) vs the same run
  with ``DCCRG_INTEGRITY=0`` (bitwise the pre-SDC program). The
  overhead target is <2% per step when on, 0 when off.
- ``audit`` — shadow-execution audits at ``--audit-every 1`` (the
  worst case: every tick re-executes one slot's quantum) vs audits
  off; reported per audit window so production cadences
  (``DCCRG_AUDIT_EVERY=50``-ish) can be extrapolated.
- ``dmr`` — ``FleetJob(redundancy=2)`` vs unreplicated: the
  throughput factor of running every step twice plus the per-quantum
  digest comparison (the expected factor is ~0.5x minus the compare;
  DMR is the always-on belt for jobs that cannot tolerate a sampled
  detector).

Every leg asserts bitwise digest parity with the solo baseline — a
defense layer that perturbs the answer would be worse than the
disease.

Run:  timeout -k 10 900 python bench/sdc_bench.py [--n 16]
      [--steps 32] [--jobs 16]

JSON rows to stdout like the other bench emitters; the summary row
carries the percentages PERF.md quotes.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def make_jobs(count, n, steps, redundancy=1):
    from dccrg_tpu.fleet import FleetJob

    return [FleetJob(f"b{i:04d}", length=(n, n, n), n_steps=steps,
                     params=(0.02 + 0.003 * (i % 7),), seed=i,
                     checkpoint_every=0, redundancy=redundancy)
            for i in range(count)]


def run_fleet_once(count, n, steps, *, integrity_on, audit_every=0,
                   redundancy=1, quantum=None):
    """One fleet pass under one SDC configuration; returns
    ``(wall_s, digests, audits)``."""
    from dccrg_tpu.fleet import GridBatch
    from dccrg_tpu.scheduler import FleetScheduler

    os.environ["DCCRG_INTEGRITY"] = "1" if integrity_on else "0"
    try:
        jobs = make_jobs(count, n, steps, redundancy)
        workdir = tempfile.mkdtemp(prefix="dccrg_sdc_bench_")
        try:
            sched = FleetScheduler(workdir, jobs, quantum=quantum,
                                   audit_every=audit_every)
            # warm every compile outside the window (program cache is
            # keyed by (bucket, capacity, integrity flag); the
            # fingerprint program is part of the integrity variant)
            sched._admit_pending()
            for bs in sched.buckets.values():
                for b in bs:
                    dummy = GridBatch(jobs[0], b.capacity)
                    dummy.step(np.ones(b.capacity, dtype=np.int32))
                    dummy.finite_slots()
                    if integrity_on:
                        dummy.fingerprint_slots()
            t0 = time.perf_counter()
            report = sched.run()
            wall = time.perf_counter() - t0
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        assert all(r["status"] == "done" for r in report.values())
        assert all(r["trips"] == 0 for r in report.values()), \
            "false SDC alarm during the bench"
        return (wall, {m: r["digest"] for m, r in report.items()},
                sched.audits)
    finally:
        os.environ.pop("DCCRG_INTEGRITY", None)


def run_fleet(count, n, steps, legs, *, quantum=None, repeats=3):
    """INTERLEAVED best-of-``repeats``: every repeat runs every leg
    back to back, so host noise (this is a 1-core container) hits all
    configurations alike instead of whichever leg ran during a busy
    window. Returns ``{leg_name: (best_wall, digests, audits)}``."""
    best = {}
    for _ in range(repeats):
        for name, kw in legs.items():
            wall, digests, audits = run_fleet_once(
                count, n, steps, quantum=quantum, **kw)
            if name not in best or wall < best[name][0]:
                best[name] = (wall, digests, audits)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--steps", type=int, default=96)
    ap.add_argument("--jobs", type=int, default=16)
    ap.add_argument("--quantum", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    # a CPU-only host bench: probe the CPU backend in a killable
    # subprocess before any jax work
    from dccrg_tpu.resilience import safe_devices

    safe_devices(timeout=120, retries=1, platform="cpu")

    from dccrg_tpu.fleet import FleetJob, run_solo

    solo = {j.name: run_solo(FleetJob(
        j.name, length=j.length, n_steps=j.n_steps, params=j.params,
        seed=j.seed)) for j in make_jobs(args.jobs, args.n, args.steps)}

    legs = {
        "off": dict(integrity_on=False),
        "invariants": dict(integrity_on=True),
        "audit": dict(integrity_on=True, audit_every=1),
        "dmr": dict(integrity_on=True, redundancy=2),
    }
    out = run_fleet(args.jobs, args.n, args.steps, legs,
                    quantum=args.quantum, repeats=args.repeats)
    off, on, aud, dmr = (out[k][0] for k in
                         ("off", "invariants", "audit", "dmr"))
    n_aud = out["audit"][2]
    for name, (_w, d, _a) in out.items():
        assert d == solo, f"{name} leg lost bitwise parity with solo"

    steps_total = args.jobs * args.steps
    inv_pct = 100.0 * (on - off) / off
    rows = [
        {"leg": "baseline_integrity_off", "wall_s": round(off, 4),
         "ms_per_step": round(1e3 * off / steps_total, 4)},
        {"leg": "invariants_on", "wall_s": round(on, 4),
         "ms_per_step": round(1e3 * on / steps_total, 4),
         "overhead_pct": round(inv_pct, 2)},
        {"leg": "audit_every_tick", "wall_s": round(aud, 4),
         "audits": n_aud,
         "ms_per_audit_window": round(
             1e3 * (aud - on) / max(1, n_aud), 3)},
        {"leg": "dmr_redundancy_2", "wall_s": round(dmr, 4),
         "throughput_factor": round(on / dmr, 3)},
    ]
    for row in rows:
        print(json.dumps(row), flush=True)
    summary = {
        "jobs": args.jobs, "n": args.n, "steps": args.steps,
        "invariant_overhead_pct": round(inv_pct, 2),
        "audit_cost_ms_per_window": rows[2]["ms_per_audit_window"],
        "dmr_throughput_factor": rows[3]["throughput_factor"],
        "bitwise_parity": True,
    }
    print(json.dumps({"summary": summary}), flush=True)
    return summary


if __name__ == "__main__":
    main()
