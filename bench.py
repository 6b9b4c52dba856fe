#!/usr/bin/env python
"""Benchmark driver: advection 3-D cell-updates/sec on TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ...}

``value`` is the FRAMEWORK (grid-path) throughput. When that leg fails
the bench exits non-zero and prints no JSON line. The specialized
Pallas kernel bound is published separately under ``pallas_metric`` /
``pallas_updates_per_sec`` (null when that side leg fails) and is never
substituted into the headline. The bench runs on whatever JAX finds
(``JAX_PLATFORMS=cpu`` and small ``BENCH_*`` sizes validate it without
a chip; such numbers are not chip numbers).

Workload: the reference's north-star configuration (BASELINE.json) —
tests/advection 3-D 512^3 uniform grid (max_refinement_level 0),
first-order upwind solid-body rotation — on the real TPU chip via the
dense fast path (dccrg_tpu/models/advection.py).

Baseline: the reference repo publishes no advection numbers and cannot
be built here (no MPI/Zoltan/boost toolchain), so the baseline is
measured on this host: the identical math as a -O3 C++ loop
(bench/baseline_advection.cpp), single core, scaled by a nominal
32-core HPC node with perfect MPI scaling — a deliberately generous
stand-in for "single-node MPI cell-updates/sec". Cached in
bench/baseline_measured.json.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
NODE_CORES = 32  # nominal single-node core count for the MPI baseline
N = int(os.environ.get("BENCH_N", "512"))
NZ = int(os.environ.get("BENCH_NZ", str(N)))
STEPS = int(os.environ.get("BENCH_STEPS", "20"))


def measure_baseline() -> float:
    """Single-node reference throughput: the C++ upwind loop
    (bench/baseline_advection.cpp, the reference's solve.hpp math) at
    the bench's own per-core problem size, fork-parallel across the
    host's cores. When the host has fewer cores than the nominal
    32-core node, the concurrent measurement is extrapolated to
    NODE_CORES at perfect MPI scaling — deliberately generous to the
    reference (tests/advection/2d.cpp:453-503 reports per-rank sums) —
    so a 1-core build host still yields a full-node bar. The cache
    records both the measured aggregate and the node figure; the bench
    compares against the node figure."""
    cache = ROOT / "bench" / "baseline_measured.json"
    if cache.exists():
        got = json.loads(cache.read_text())
        if "node_cell_updates_per_sec" in got:  # current-format cache only
            return got["node_cell_updates_per_sec"]
    exe = ROOT / "bench" / "baseline_advection"
    src = ROOT / "bench" / "baseline_advection.cpp"
    subprocess.run(
        ["g++", "-O3", "-march=native", "-o", str(exe), str(src)],
        check=True, capture_output=True,
    )
    cores = max(1, min(os.cpu_count() or 1, NODE_CORES))
    # the bench size split across cores (as an MPI run would be), at
    # least a few z-planes per rank
    nzp = max(8, NZ // cores)
    steps = 3

    def trial():
        t0 = time.perf_counter()
        procs = [
            subprocess.Popen([str(exe), str(N), str(nzp), str(steps)],
                             stdout=subprocess.PIPE, text=True)
            for _ in range(cores)
        ]
        for p in procs:
            p.wait()
        wall = time.perf_counter() - t0
        for p in procs:
            if p.returncode != 0:
                raise RuntimeError("baseline_advection failed")
        return [float(p.stdout.read().strip()) for p in procs], wall

    # best of 3: the baseline must not be deflated by transient load on
    # a shared host (that would flatter vs_baseline)
    trials = [trial() for _ in range(3)]
    per_core_internal, wall = max(trials, key=lambda t: sum(t[0]))
    # each process times its own stepping loop while all run
    # concurrently: the sum is the host throughput under real memory
    # contention, without charging process startup to the reference
    measured_rate = sum(per_core_internal)
    # extrapolate to the nominal node width at perfect scaling when the
    # host is narrower than a node (generous to the reference: real MPI
    # scaling is sublinear under shared-memory-bandwidth contention)
    node_rate = measured_rate * (NODE_CORES / cores)
    result = {
        "single_core_cell_updates_per_sec": max(per_core_internal),
        "measured_aggregate_cell_updates_per_sec": measured_rate,
        "node_cell_updates_per_sec": node_rate,
        "node_cores_used": cores,
        "node_cores_nominal": NODE_CORES,
        "node_extrapolated": cores < NODE_CORES,
        "per_core_size": [N, nzp, steps],
        "wall_seconds": wall,
    }
    cache.write_text(json.dumps(result, indent=1))
    return node_rate


GRID_N = int(os.environ.get("BENCH_GRID_N", "512"))  # north-star size
GRID_STEPS = int(os.environ.get("BENCH_GRID_STEPS", "20"))
AB_N = int(os.environ.get("BENCH_AB_N", "128"))
AB_STEPS = int(os.environ.get("BENCH_AB_STEPS", "10"))


def bench_pallas(baseline):
    """The Pallas temporal-blocked fast path at the north-star size.
    BENCH_PALLAS_DTYPE=bfloat16 runs the narrow-storage variant (the
    kernel's flux arithmetic is weakly typed, so state stays bf16 in
    VMEM and HBM — roughly half the traffic of f32 on chip)."""
    import jax
    import jax.numpy as jnp
    from dccrg_tpu.models.advection import PallasRotationAdvection, analytic_density
    import numpy as np

    pdt = jnp.dtype(os.environ.get("BENCH_PALLAS_DTYPE", "float32"))
    solver = PallasRotationAdvection(n=N, nz=NZ, dtype=pdt)
    dt = 0.5 * solver.max_time_step()

    # warmup / compile, synced by a forced scalar readback
    solver.step(dt)
    float(jnp.sum(solver.rho))

    t0 = time.perf_counter()
    for _ in range(STEPS):
        solver.step(dt)
    checksum = float(jnp.sum(solver.rho))
    elapsed = time.perf_counter() - t0
    assert np.isfinite(checksum)

    n_cells = N * N * NZ
    updates_per_sec = n_cells * STEPS * solver.steps_per_pass / elapsed
    pallas_dtype = str(pdt)
    x = (np.arange(N) + 0.5) / N
    exact = np.asarray(
        analytic_density(x[:, None, None], x[None, :, None], solver.time)
    ) * np.ones((1, 1, NZ))
    diff = np.asarray(solver.rho, dtype=np.float64) - exact
    l2 = float(np.sqrt(np.sum(diff**2) * (1.0 / N) ** 2 * (1.0 / NZ)))
    print(
        f"pallas: elapsed {elapsed:.3f}s for {STEPS} passes x "
        f"{solver.steps_per_pass} steps; l2 {l2:.2e}",
        file=sys.stderr,
    )
    return updates_per_sec, l2, pallas_dtype


def bench_grid_path(n=None, steps=None, label="grid path", dtype=None):
    """The general Grid runtime (closed-form plan / gather tables +
    fused run_steps) on the same physics — the framework path an AMR
    user exercises, at max_refinement_level 0
    (tests/advection/2d.cpp:327-343). Cell-updates/sec accounting
    mirrors the reference's own benchmark (2d.cpp:316-350)."""
    from dccrg_tpu.models.advection import GridAdvection
    import numpy as np

    n = n if n is not None else GRID_N
    steps = steps if steps is not None else GRID_STEPS
    if dtype is None and os.environ.get("BENCH_GRID_DTYPE"):
        # BENCH_GRID_DTYPE=bfloat16: grid-wide narrow storage for the
        # main leg
        import jax.numpy as jnp

        dtype = jnp.dtype(os.environ["BENCH_GRID_DTYPE"])
    kw = {} if dtype is None else {"dtype": dtype}
    solver = GridAdvection(n=n, nz=n, **kw)
    dt = 0.5 * solver.max_time_step()

    solver.run(1, dt)  # warmup / compile
    solver.checksum()  # forced scalar readback

    t0 = time.perf_counter()
    solver.run(steps, dt)
    checksum = solver.checksum()
    elapsed = time.perf_counter() - t0
    assert np.isfinite(checksum)
    # record only the engagement BIT for the pallas-bulk leg —
    # keeping the whole Grid alive here would pin gigabytes of HBM
    # (fields + plan tables at 512^3) across the remaining legs
    global _BULK_ENGAGED
    _BULK_ENGAGED = any(k[0] == "bulksteploop"
                        for k in solver.grid._program_cache)

    n_cells = n * n * n
    updates_per_sec = n_cells * steps / elapsed
    l2 = solver.l2_error()
    print(
        f"{label}: elapsed {elapsed:.3f}s for {steps} fused steps at "
        f"{n}^3; l2 {l2:.2e}",
        file=sys.stderr,
    )
    return updates_per_sec, l2


_GATHER_VARS = ("DCCRG_FORCE_TABLES", "DCCRG_ROLL_STENCIL")


_BULK_ENGAGED = False  # did the most recent grid leg compile the bulk program


def bench_grid_path_pallas(xla_ups, xla_l2):
    """The roll-plan Pallas bulk executor (DCCRG_BULK=pallas,
    ops/roll_executor.py) on the SAME grid-path workload: the
    framework step loop compiled as tiled, double-buffered Pallas bulk
    passes with fused fixup epilogues. Reported under its own JSON key
    (null on failure — the pallas_metric discipline); the leg is
    VOIDED unless the executor provably engaged (the bulk program in
    the grid's cache — forced table mode from the A/B would otherwise
    silently rebrand the XLA table path) and L2 parity against the
    XLA roll path holds. Skipped when the user exported DCCRG_BULK
    themselves (the headline leg already ran their mode)."""
    if os.environ.get("BENCH_SKIP_BULK") == "1":
        return None, None, None
    if os.environ.get("DCCRG_BULK", "").lower() == "pallas":
        return None, None, "user-ran-headline-as-pallas"
    saved = {v: os.environ.get(v) for v in _GATHER_VARS}
    # the executor needs the closed-form plan: forced dense tables
    # (a tables-winning A/B) would disable it at plan build
    _set_gather_mode("roll")
    os.environ["DCCRG_BULK"] = "pallas"
    try:
        ups, l2 = bench_grid_path(label="grid path pallas-bulk")
    except Exception as e:
        print(f"pallas-bulk grid leg failed ({e!r})", file=sys.stderr)
        return None, None, f"failed: {e!r}"
    finally:
        os.environ.pop("DCCRG_BULK", None)
        for v, val in saved.items():
            if val is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = val
    if not _BULK_ENGAGED:
        print("pallas-bulk leg: executor did NOT engage (ineligible "
              "plan?); leg voided", file=sys.stderr)
        return None, l2, "executor-did-not-engage"
    if xla_l2 is not None and abs(l2 - xla_l2) > 1e-3 + 0.05 * abs(xla_l2):
        print(f"pallas-bulk L2 {l2:.3e} vs xla {xla_l2:.3e}: parity "
              "FAILED; leg voided", file=sys.stderr)
        return None, l2, "l2-parity-failed"
    return ups, l2, None


def _set_gather_mode(mode):
    """Force one gather mode: 'roll' (closed-form plan, rolls forced
    even where the platform default is tables — e.g. the CPU backend)
    or 'tables' (dense gather tables, random gathers)."""
    if mode == "tables":
        os.environ["DCCRG_FORCE_TABLES"] = "1"
        os.environ["DCCRG_ROLL_STENCIL"] = "0"
    else:
        os.environ.pop("DCCRG_FORCE_TABLES", None)
        os.environ["DCCRG_ROLL_STENCIL"] = "1"


def ab_roll_vs_tables():
    """On-chip A/B at a quick size: closed-form roll-decomposed
    gathers vs dense gather tables + random gathers. Returns the
    winning mode name plus both rates — the round-3 verdict's open
    question (the roll default was chosen on theory; this measures it
    wherever the bench runs). User-exported gather overrides are
    respected: the A/B is skipped so the main leg runs the caller's
    explicit settings."""
    if os.environ.get("BENCH_SKIP_AB") == "1" or any(
            v in os.environ for v in _GATHER_VARS):
        return None, None, None, None
    try:
        _set_gather_mode("roll")
        roll_ups, _ = bench_grid_path(AB_N, AB_STEPS, label="A/B roll")
        _set_gather_mode("tables")
        table_ups, _ = bench_grid_path(AB_N, AB_STEPS, label="A/B tables")
    except Exception as e:
        print(f"A/B leg failed ({e!r}); keeping roll default",
              file=sys.stderr)
        _set_gather_mode("roll")
        return None, None, None, None
    winner = "roll" if roll_ups >= table_ups else "tables"
    if winner == "tables":
        # dense tables at the main size cost ~5 bytes x cells x slots
        # plus same-size build temporaries; a host OOM kill would skip
        # the JSON line entirely, so cap the mode at a memory budget
        # (default 16 GiB — a TPU-VM host comfortably holds the 512^3
        # build; the override is recorded in the JSON when it fires)
        est = GRID_N ** 3 * 6 * 5 * 2
        cap = int(os.environ.get("BENCH_TABLES_MEM_CAP", str(16 << 30)))
        if est > cap:
            print(
                f"A/B picked tables but {GRID_N}^3 table build (~{est>>30}"
                f" GiB) exceeds BENCH_TABLES_MEM_CAP; keeping roll",
                file=sys.stderr,
            )
            return "roll", roll_ups, table_ups, "tables-won-but-mem-capped"
    print(
        f"A/B at {AB_N}^3: roll {roll_ups:.3g}/s vs tables "
        f"{table_ups:.3g}/s -> {winner}",
        file=sys.stderr,
    )
    return winner, roll_ups, table_ups, None


def main() -> None:
    from dccrg_tpu.compat import use_compile_cache

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    baseline = measure_baseline()

    user_env = {v: os.environ[v] for v in _GATHER_VARS if v in os.environ}
    winner, ab_roll, ab_tables, ab_note = ab_roll_vs_tables()
    if winner is not None:
        mode_used, mode_source = winner, ("ab" if ab_note is None
                                          else "ab-mem-capped")
        _set_gather_mode(winner)
    else:
        # user-exported overrides (A/B skipped): tables when dense
        # tables or table gathers were explicitly requested
        mode_used = ("tables"
                     if (user_env.get("DCCRG_FORCE_TABLES") == "1"
                         or user_env.get("DCCRG_ROLL_STENCIL") == "0")
                     else "roll")
        mode_source = "user-env" if user_env else "default"
    grid_ups, grid_l2 = bench_grid_path()  # the headline: a failure ends the bench
    # snapshot the HEADLINE leg's bulk engagement before later legs
    # overwrite the flag: a DCCRG_BULK=pallas run whose executor
    # silently fell back (ineligible plan, multi-device mesh) must not
    # report its XLA numbers as the Pallas executor's
    headline_bulk_engaged = _BULK_ENGAGED
    # bfloat16 storage leg (float32 compute): halves the stencil's HBM
    # traffic — reported separately, the headline stays float32 (the
    # reference computes in double; f32 is already the recorded
    # departure, bf16 is the optional narrow-storage mode)
    bf16_ups = bf16_l2 = None
    if os.environ.get("BENCH_SKIP_BF16") != "1":
        try:
            import jax.numpy as jnp
            bf16_ups, bf16_l2 = bench_grid_path(
                label="grid path bf16", dtype=jnp.bfloat16)
        except Exception as e:
            print(f"bf16 leg failed ({e!r})", file=sys.stderr)
    # the bulk-executor leg rides the same gather mode as the headline
    # (the executor replaces the whole step program, but its XLA
    # fallback paths should match the measured configuration)
    bulk_ups, bulk_l2, bulk_note = bench_grid_path_pallas(grid_ups, grid_l2)
    # restore the caller's gather settings for the Pallas leg
    for v in _GATHER_VARS:
        os.environ.pop(v, None)
    os.environ.update(user_env)
    try:
        pallas_ups, pallas_l2, pallas_dt = bench_pallas(baseline)
    except Exception as e:  # the specialized kernel is secondary
        print(f"pallas bench failed ({e!r})", file=sys.stderr)
        pallas_ups, pallas_l2, pallas_dt = None, None, "not-run"

    # headline value = the FRAMEWORK (general Grid runtime) throughput
    # at the north-star size; the Pallas figure is the specialized
    # single-kernel bound, published under its OWN metric name, never
    # the headline (round-5 advisor item: a 7.6e10 'grid-path' value measured on the
    # specialized kernel misleads downstream consumers)
    print(
        json.dumps(
            {
                "metric": (f"grid-path advection 3D {GRID_N}^3 "
                           "cell-updates/sec/chip"),
                "value": grid_ups,
                "unit": "cell-updates/s",
                "vs_baseline": grid_ups / baseline,
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices())},
                "grid_path_updates_per_sec": grid_ups,
                "grid_path_size": f"{GRID_N}^3",
                "grid_path_vs_baseline": grid_ups / baseline,
                "l2_error": grid_l2,
                "gather_mode": mode_used,
                "gather_mode_source": mode_source,
                "ab_roll_updates_per_sec": ab_roll,
                "ab_tables_updates_per_sec": ab_tables,
                "bf16_updates_per_sec": bf16_ups,
                "bf16_l2_error": bf16_l2,
                "grid_path_pallas_updates_per_sec": bulk_ups,
                "grid_path_pallas_l2_error": bulk_l2,
                "grid_path_pallas_vs_xla": (bulk_ups / grid_ups
                                            if bulk_ups is not None
                                            else None),
                "grid_path_pallas_note": bulk_note,
                # the headline leg's ACTUAL mode: "pallas" only when
                # the bulk program provably compiled; a requested-but-
                # fallen-back run is labeled so the chip session's
                # bulk A/B can never rebrand XLA numbers
                "dccrg_bulk_mode": (
                    ("pallas" if headline_bulk_engaged
                     else "pallas-requested-not-engaged")
                    if os.environ.get("DCCRG_BULK", "").lower() == "pallas"
                    else "xla"),
                "pallas_metric": (f"pallas-kernel advection 3D {N}^2x{NZ} "
                                  "cell-updates/sec/chip"),
                "pallas_updates_per_sec": pallas_ups,
                "pallas_vs_baseline": (pallas_ups / baseline
                                       if pallas_ups is not None else None),
                "pallas_l2_error": pallas_l2,
                "pallas_note": ("specialized temporal-blocked kernel bound, "
                                f"{N}^2x{NZ} {pallas_dt}"
                                "; not the framework path"),
                "baseline_node_updates_per_sec": baseline,
                "baseline_note": (f"measured C++ upwind loop, extrapolated "
                                  f"to a {NODE_CORES}-core node at perfect "
                                  "MPI scaling (bench/baseline_measured"
                                  ".json has the raw measurement)"),
            }
        )
    )
    # diagnostics on stderr only
    print(
        f"baseline {baseline:.3g}/s ({NODE_CORES}-core node equivalent); "
        f"DCCRG_BULK={os.environ.get('DCCRG_BULK') or 'xla (default)'}; "
        f"devices {jax.devices()}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
