#!/bin/sh
# DCCRG_DEBUG CI leg: a short tier-1 marker subset (the mutation-heavy
# fuzz + faultinject suites) with continuous invariant verification
# enabled, so an invariant regression surfaces immediately even though
# the main tier-1 run keeps DEBUG off for speed. Mirrors the
# reference's -DDEBUG CI builds.
#
# Also exercises one native-recommit parity test under DEBUG so the
# post-commit verify_all runs against plans built by the native
# in-place table writers + PlanArena (the numpy-only fallback is
# covered by the same test when the native build is unavailable),
# and one incremental-checkpoint chain-integrity test (corrupt/
# truncate/delete each keyframe+delta link position; typed
# DeltaChainError fallback asserted) so the delta data plane runs
# with continuous invariant verification on.
#
# Also runs a small fleet smoke leg: >= 8 concurrent jobs multiplexed
# through one batch with ONE injected NaN trip — the victim must roll
# back alone and every job must finish bitwise equal to its solo run
# (the fleet-isolation fuzz scenario plus the CLI round trip).
#
# Also runs an SDC smoke leg: an 8-job fleet with one silent_flip
# victim (finite corruption, invisible to the finiteness watchdog)
# convicted by the in-program integrity invariants within one
# quantum, plus the quarantine-after-2 path — a repeat-offender
# device lane is taken out of service with its survivors migrated
# bit-exactly (all digests still equal the solo runs).
#
# Also runs a telemetry smoke leg: the strictly-best-effort exporter
# contract (EVERY telemetry write failing must leave the supervised
# run with zero trips/rollbacks), the deterministic latency-SLO
# admission reorder vs the priority-only baseline, and the
# trace-coverage acceptance (a traced fleet run's depth-0 spans
# account for >= 95% of the serving wall-clock).
#
# Also runs a bulk-executor smoke leg: the roll-plan Pallas executor
# (DCCRG_BULK=pallas, interpret mode) against the XLA roll path —
# fixup-row parity on periodic and non-periodic grids plus one fleet
# bucket stepping through the registered bulk kernel — and the
# negative pin that DCCRG_BULK unset compiles the pre-executor
# program.
#
# Also runs a background-recommit leg under DCCRG_DEBUG=1: the
# refine/unrefine/balance parity suite with DCCRG_BG_RECOMMIT on, so
# every step-boundary swap's post-commit verify_all runs against a
# plan built on the worker thread (the swap wraps itself in a
# transaction; --dccrg-debug makes that transaction verify), plus an
# async-save + kill-mid-overlap smoke: a child process is killed
# (os._exit, no cleanup) while an async checkpoint write is in
# flight, and the parent must resume from the last durable save with
# only sweepable temp litter left behind.
#
# Also runs an autopilot smoke leg under DCCRG_DEBUG=1: an opted-in
# fleet run writes its decision journal and every decision replays
# (re-derives) from the journal alone, the explain/replay CLI round
# trips (tampering detected), and the off-by-default negative pin
# holds — no controller, no knob movement, bitwise-solo results.
#
# Also runs a model-zoo smoke leg under DCCRG_DEBUG=1: an MHD 8^3
# run (conservation pinned) plus the MHD-schema GridFuzzer leg, so
# every mutation's post-commit verify_all runs over the multi-field
# schema, and the MHD pass pair on the 8-device mesh (each pass
# exchanging only its own subsystem) against one device.
#
# Also runs a warm-start smoke leg under DCCRG_DEBUG=1: a cold serve
# manifests its compile-cache records and a fresh pool serves every
# first dispatch warm with bitwise digests, the full warm-cache fault
# matrix (torn/corrupt/stale/io/mid-prewarm death) degrades typed to
# a cold compile — never a wrong program — and the negative pin holds
# (DCCRG_COMPILE_CACHE unset: no pool, bitwise-identical behavior).
#
# Usage: tests/ci_debug_leg.sh [extra pytest args]
set -e
cd "$(dirname "$0")/.."
env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m "(fuzz or faultinject) and not slow" --dccrg-debug \
    -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_fleet.py::test_fleet_fuzz_isolation_scenario" \
    "tests/test_fleet.py::test_cli_runs_a_job_file" \
    -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_integrity.py::test_silent_flip_detected_within_one_quantum" \
    "tests/test_integrity.py::test_repeat_offender_lane_quarantined_and_migrated" \
    "tests/test_integrity.py::test_fleet_fuzz_flip_scenario" \
    -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_telemetry.py::test_exporter_faults_never_trip_a_run" \
    "tests/test_telemetry.py::test_slo_admission_reorders_vs_priority_baseline" \
    "tests/test_telemetry.py::test_fleet_trace_covers_step_wall_clock" \
    -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_bulk_executor.py::test_bulk_matches_xla_roll_path" \
    "tests/test_bulk_executor.py::test_bulk_negative_pin" \
    "tests/test_bulk_executor.py::test_fleet_bulk_bucket_matches_table_path" \
    -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_autopilot.py::test_autopilot_on_preserves_results" \
    "tests/test_autopilot.py::test_explain_and_replay_cli" \
    "tests/test_autopilot.py::test_off_by_default_negative_pin" \
    --dccrg-debug -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_bgrecommit.py::test_bg_plan_parity_across_refine_unrefine_balance" \
    "tests/test_bgrecommit.py::test_balance_drains_pending_build_first" \
    "tests/test_bgrecommit.py::test_async_preempt_emergency_save_then_resume_bitwise" \
    --dccrg-debug -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python - <<'PYEOF'
# async-save kill-mid-overlap smoke: SIGKILL-equivalent death while a
# checkpoint write is overlapped with dispatch; the store must still
# resume from the last durable save, with only sweepable temp litter.
import os, subprocess, sys, tempfile
workdir = tempfile.mkdtemp(prefix="dccrg_kill_overlap_")
child = r'''
import os, sys
import numpy as np, jax.numpy as jnp
from dccrg_tpu import Grid
from dccrg_tpu.supervise import CheckpointStore
os.environ["DCCRG_ASYNC_SAVE"] = "1"
g = (Grid(cell_data={"rho": jnp.float32})
     .set_initial_length((8, 8, 4)).set_periodic(True, True, False)
     .set_load_balancing_method("block").initialize())
cells = g.plan.cells
g.set("rho", cells, (cells.astype(np.float64) % 13).astype(np.float32))
store = CheckpointStore(sys.argv[1], stem="k")
store.save(g, 1); store.drain()          # one durable save
g.set("rho", cells, (cells.astype(np.float64) % 7).astype(np.float32))
store.save(g, 2)                          # in flight...
os._exit(137)                             # ...killed mid-overlap
'''
rc = subprocess.run([sys.executable, "-c", child, workdir],
                    env=dict(os.environ, JAX_PLATFORMS="cpu")).returncode
assert rc == 137, rc
import jax.numpy as jnp
from dccrg_tpu import checkpoint as ckpt, resilience
from dccrg_tpu.supervise import resume_latest
info = resume_latest(workdir, {"rho": jnp.float32}, stem="k",
                     load_balancing_method="block")
assert info is not None and not info.salvaged and info.step >= 1, info
# whatever the kill left behind is recognized stale temp litter, and
# the durable checkpoint the resume used still CRC-verifies
assert resilience.verify_checkpoint(info.path) == []
for p in ckpt.stale_temp_files(workdir):
    os.unlink(p)
left = [n for n in os.listdir(workdir)
        if ".tmp." in n or n.endswith(".mp-tmp")]
assert not left, left
print("kill-mid-overlap smoke OK (resumed step %d)" % info.step)
PYEOF
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_models.py::test_mhd_conservation" \
    "tests/test_models.py::test_mhd_schema_fuzz_leg" \
    "tests/test_models.py::test_mhd_pass_pair_mesh_matches_one_device" \
    --dccrg-debug -p no:cacheprovider "$@"
env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_warmstart.py::test_cold_run_manifests_and_warm_run_hits" \
    "tests/test_warmstart.py::test_every_warm_fault_site_degrades_typed" \
    "tests/test_warmstart.py::test_negative_pin_no_cache_no_pool" \
    --dccrg-debug -p no:cacheprovider "$@"
exec env JAX_PLATFORMS=cpu python -m pytest -q \
    "tests/test_recommit.py::test_native_numpy_plans_bitwise_identical" \
    "tests/test_checkpoint_integrity.py::test_chain_salvage_falls_back_to_verifying_prefix" \
    --dccrg-debug -p no:cacheprovider "$@"
