"""Fleet serving throughput: batched many-grid multiplexing vs the
sequential one-grid-at-a-time loop (the ROADMAP "Fleet serving"
item's measuring stick).

For each concurrency level (default 1/8/32/100 jobs of ``--n``^3
cells, ``--steps`` steps each) the same job set runs twice:

- ``sequential`` — the pre-fleet baseline: one grid at a time through
  ``Grid.run_steps`` (one shared compile; each job re-inits the
  template grid), and
- ``fleet`` — one :class:`~dccrg_tpu.scheduler.FleetScheduler` batch:
  all jobs stacked along the batch axis into one jitted program.

Both passes produce per-job final-state digests; the bench ASSERTS
they match bitwise (it doubles as the end-to-end parity check), then
reports runs/s, cell-updates/s and mean per-job latency. Checkpoint
cadence is disabled in both passes so the number is pure stepping
throughput; ``--ckpt-every K`` turns the fleet data plane back on.

Run:  timeout -k 10 900 python bench/fleet_bench.py [--n 32]
      [--steps 20] [--jobs 1 8 32 100]

``--hosts N`` instead runs the ELASTIC multi-host leg: N in-process
rank-aware schedulers (shared InMemoryKV + checkpoint dir, real
clock, tight heartbeat/lease bounds) serve one job set; host 1 is
killed mid-serve (its tick driver stops — the in-process analogue of
the mp harness's real ``kill -9``) and the leg measures the recovery
wall: ``fleet_reclaim_seconds`` (kill -> the survivor's CAS takeover
of the first orphan) and ``fleet_kill_downtime_seconds`` (kill ->
the first reclaimed job's dispatch completes) — the two trend keys
``bench/trend.py`` tracks for the elastic control plane, with
bitwise solo-digest parity asserted for every job, victims included.

JSON rows go to stdout like the other bench emitters; the summary row
carries the runs/s table PERF.md quotes.
"""

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402, F401
import numpy as np  # noqa: E402


def make_jobs(count, n, steps, ckpt_every):
    from dccrg_tpu.fleet import FleetJob

    return [FleetJob(f"b{i:04d}", length=(n, n, n), n_steps=steps,
                     params=(0.02 + 0.003 * (i % 7),), seed=i,
                     checkpoint_every=ckpt_every)
            for i in range(count)]


def run_sequential(count, n, steps, ckpt_every):
    """One grid at a time: a single template grid + compiled step
    loop, re-initialized per job (the strongest sequential baseline —
    a fresh Grid per job would also pay N plan builds + compiles)."""
    from dccrg_tpu import checkpoint as checkpoint_mod
    from dccrg_tpu.fleet import template_grid

    jobs = make_jobs(count, n, steps, ckpt_every)
    g = template_grid(jobs[0])
    # warm the compile outside the measured window (both passes get
    # this; compile amortizes to zero in steady serving)
    jobs[0].apply_init(g)
    g.run_steps(jobs[0].resolved_kernel(), jobs[0].fields_in,
                jobs[0].fields_out, 1,
                extra_args=(jnp.float32(jobs[0].params[0]),))
    digests = {}
    lat = []
    # symmetric accounting with run_fleet: its window starts AFTER
    # admission (init + scatter + step-0 keyframes), so the sequential
    # window likewise excludes each job's apply_init and measures
    # stepping + final digest only
    for j in jobs:
        j.apply_init(g)
        jax.block_until_ready(list(g.data.values()))
        t1 = time.perf_counter()
        g.run_steps(j.resolved_kernel(), j.fields_in, j.fields_out,
                    j.n_steps, extra_args=(jnp.float32(j.params[0]),))
        jax.block_until_ready(list(g.data.values()))
        digests[j.name] = checkpoint_mod.state_digest(g)
        lat.append(time.perf_counter() - t1)
    wall = sum(lat)
    return wall, digests, lat


def run_fleet(count, n, steps, ckpt_every, quantum):
    from dccrg_tpu.scheduler import FleetScheduler

    jobs = make_jobs(count, n, steps, ckpt_every)
    workdir = tempfile.mkdtemp(prefix="dccrg_fleet_bench_")
    try:
        sched = FleetScheduler(workdir, jobs, quantum=quantum)
        # warm the batched compile outside the measured window: a
        # throwaway batch with the same bucket key and capacity shares
        # the compiled program (the fleet program cache is keyed on
        # exactly that), so one dummy dispatch compiles it
        sched._admit_pending()
        from dccrg_tpu.fleet import GridBatch

        for bs in sched.buckets.values():
            for b in bs:
                dummy = GridBatch(jobs[0], b.capacity)
                dummy.step(np.ones(b.capacity, dtype=np.int32))
                dummy.finite_slots()
        t0 = time.perf_counter()
        report = sched.run()
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    assert all(r["status"] == "done" for r in report.values())
    return wall, {name: r["digest"] for name, r in report.items()}


def run_hosts(n_hosts, n, steps, quantum, heartbeat_s=0.1,
              lease_s=0.4):
    """The elastic multi-host leg: ``n_hosts`` in-process rank-aware
    schedulers over one shared KV + checkpoint dir; host 1 dies
    mid-serve and the survivors' lease-expiry reclaim is timed."""
    from dccrg_tpu import coord, telemetry
    from dccrg_tpu.fleet import run_solo
    from dccrg_tpu.scheduler import FleetScheduler

    count = max(2, 2 * n_hosts)
    kv = coord.InMemoryKV()
    workdir = tempfile.mkdtemp(prefix="dccrg_fleet_hosts_")
    refs = {j.name: run_solo(j)
            for j in make_jobs(count, n, steps, 4)}
    try:
        scheds = []
        for rank in range(n_hosts):
            m = coord.Membership(rank, n_hosts, kv=kv,
                                 heartbeat_s=heartbeat_s,
                                 lease_s=lease_s, clock=time.monotonic)
            scheds.append(FleetScheduler(
                workdir, make_jobs(count, n, steps, 4),
                quantum=quantum or 4, membership=m))
        names = [f"b{i:04d}" for i in range(count)]
        reg = telemetry.registry()
        base_reclaims = reg.counter_total("dccrg_fleet_reclaims_total")

        def tick(s):
            s.run(max_ticks=s.ticks + 1)

        def _disp_total(name):
            h = reg.histogram("dccrg_fleet_quantum_seconds", job=name)
            return 0 if h is None else h.total

        victim = scheds[1] if n_hosts > 1 else None
        live = list(scheds)
        orphans, disp_base = [], {}
        t_kill = t_reclaim = t_first_dispatch = None
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            for s in live:
                tick(s)
            done = sum(1 for nm in names if nm in scheds[0].report)
            if victim is not None and t_kill is None \
                    and victim.leases.owned \
                    and any(j.steps_done > 0
                            for _b, _s2, j in victim.active_jobs()):
                # the victim is mid-serve with real progress: kill it
                # (ticks and heartbeats both cease — the in-process
                # analogue of the mp harness's real kill -9)
                t_kill = time.monotonic()
                victim.membership.stop_auto()
                orphans = sorted(victim.leases.owned)
                disp_base = {nm: _disp_total(nm) for nm in orphans}
                live = [s for s in scheds if s is not victim]
            if t_kill is not None and t_reclaim is None \
                    and reg.counter_total("dccrg_fleet_reclaims_total") \
                    > base_reclaims:
                t_reclaim = time.monotonic()
            if t_reclaim is not None and t_first_dispatch is None \
                    and any(_disp_total(nm) > disp_base[nm]
                            for nm in orphans):
                # a survivor finished a dispatch that ADVANCED a
                # reclaimed job: serving resumed
                t_first_dispatch = time.monotonic()
            if done == count and (victim is None
                                  or t_first_dispatch is not None):
                break
        report = {}
        for s in live:
            for nm, row in s.report.items():
                if not row.get("remote"):
                    report[nm] = row
        assert sorted(report) == names, sorted(report)
        for nm, row in report.items():
            assert row["status"] == "done" and row["digest"] == refs[nm], nm
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    row = {
        "hosts": n_hosts, "jobs": count, "cells_per_job": n ** 3,
        "steps": steps,
        "heartbeat_s": heartbeat_s, "lease_s": lease_s,
        "fleet_reclaim_seconds": (
            None if t_kill is None or t_reclaim is None
            else round(t_reclaim - t_kill, 4)),
        "fleet_kill_downtime_seconds": (
            None if t_kill is None or t_first_dispatch is None
            else round(t_first_dispatch - t_kill, 4)),
        "orphans_reclaimed": len(orphans) if t_kill is not None else 0,
        "bitwise_parity": True,
    }
    print(json.dumps(row), flush=True)
    print(json.dumps({"summary": row}), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32,
                    help="grid edge length per job (n^3 cells)")
    ap.add_argument("--steps", type=int, default=20,
                    help="steps per job")
    ap.add_argument("--jobs", type=int, nargs="+",
                    default=(1, 8, 32, 100),
                    help="concurrency levels to measure")
    ap.add_argument("--quantum", type=int, default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="fleet checkpoint cadence (0 = pure stepping)")
    ap.add_argument("--hosts", type=int, default=None, metavar="N",
                    help="elastic multi-host leg: N in-process "
                         "rank-aware schedulers, host 1 killed "
                         "mid-serve, reclaim latency measured")
    args = ap.parse_args()

    # a CPU-only host bench: probe the CPU backend in a killable
    # subprocess before any jax work
    from dccrg_tpu.resilience import safe_devices

    safe_devices(timeout=120, retries=1, platform="cpu")

    if args.hosts is not None:
        return run_hosts(args.hosts, min(args.n, 12), args.steps,
                         args.quantum)

    cells = args.n ** 3
    rows = []
    for count in args.jobs:
        seq_wall, seq_digests, seq_lat = run_sequential(
            count, args.n, args.steps, args.ckpt_every)
        flt_wall, flt_digests = run_fleet(
            count, args.n, args.steps, args.ckpt_every,
            args.quantum)
        assert flt_digests == seq_digests, \
            "fleet digests differ from the sequential baseline"
        updates = count * cells * args.steps
        row = {
            "jobs": count, "cells_per_job": cells, "steps": args.steps,
            "seq_wall_s": round(seq_wall, 4),
            "fleet_wall_s": round(flt_wall, 4),
            "seq_runs_per_s": round(count / seq_wall, 3),
            "fleet_runs_per_s": round(count / flt_wall, 3),
            "seq_updates_per_s": round(updates / seq_wall),
            "fleet_updates_per_s": round(updates / flt_wall),
            "seq_job_latency_s": round(sum(seq_lat) / len(seq_lat), 4),
            "fleet_job_latency_s": round(flt_wall / count, 4),
            "speedup": round(seq_wall / flt_wall, 2),
            "bitwise_parity": True,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    best = max(rows, key=lambda r: r["speedup"])
    summary = {
        "n": args.n, "steps": args.steps,
        "max_jobs": max(r["jobs"] for r in rows),
        "best_speedup": best["speedup"],
        "best_speedup_jobs": best["jobs"],
        "fleet_runs_per_s_at_max": rows[-1]["fleet_runs_per_s"],
        "seq_runs_per_s_at_max": rows[-1]["seq_runs_per_s"],
    }
    print(json.dumps({"summary": summary}), flush=True)
    return summary


if __name__ == "__main__":
    main()
