"""dccrg_tpu — a TPU-native distributed cartesian cell-refinable grid.

A from-scratch JAX/XLA/Pallas framework with the capabilities of the
reference dccrg library (header-only C++/MPI/Zoltan; see SURVEY.md):

- global 64-bit cell addressing under adaptive 2:1-balanced octree
  refinement (``Mapping``),
- per-cell user data as SoA JAX arrays sharded over a TPU device mesh,
- neighbor resolution for arbitrary rectangular neighborhoods,
- halo exchange lowered to XLA collectives (``lax.ppermute`` /
  ``lax.all_to_all``) under ``shard_map``,
- adaptive mesh refinement and load balancing as host-side replanning
  events,
- parallel checkpoint/restart,
- a resilience layer (checksummed atomic checkpoints, a numerics
  watchdog with auto-rollback, OOM-aware gather-mode fallback and
  hang-proof device probing) with deterministic fault injection,
- a distributed-coordination layer (``coord``: timeout-guarded
  barriers, two-phase-commit multi-process checkpoints, cross-rank
  trip consensus, guarded ``jax.distributed`` bring-up),
- preemption-aware run supervision (``supervise``: SIGTERM/SIGINT
  emergency checkpoints with a resumable exit code, a step-hang
  deadline watchdog, auto-resume from the newest verified checkpoint
  and keep-last-K/keep-every-N retention GC),
- a fleet serving layer (``fleet``/``scheduler``: N independent
  same-shape scenario runs stacked along a batch axis into one
  jitted device program, fronted by a priority job queue with
  per-job checkpoint stems, per-slot NaN/OOM isolation and
  preemption-requeue — ``python -m dccrg_tpu.fleet``),
- a silent-data-corruption defense (``integrity``: in-program
  fingerprint/conservation invariants fused into the fleet quantum
  program, sampled shadow-execution audits, DMR job replication, a
  CORRUPT trip class with per-victim rollback and consensus, device
  quarantine with bit-exact survivor migration, and offline at-rest
  fingerprint audits — ``python -m dccrg_tpu.resilience audit``),
- a telemetry subsystem (``telemetry``: process-wide counter/gauge/
  histogram registry with Prometheus text exposition, a low-overhead
  ring-buffered span tracer over every hot boundary — step dispatch,
  halo exchange, adapt/recommit, checkpoint phases, fleet quanta —
  with rank-tagged JSONL traces that merge across processes, and
  strictly best-effort exporters; ``DCCRG_TRACE=1``, ``python -m
  dccrg_tpu.telemetry``) feeding latency-SLO fleet admission
  (``scheduler.SLOPolicy``: per-job ``slo_ms`` deadlines, EWMA
  quantum-latency projection, over-latency bucket shedding),
- a production autopilot (``autopilot``: an opt-in deterministic
  controller, ``DCCRG_AUTOPILOT=1``, tuning fleet quantum length,
  per-stem checkpoint cadence, audit cadence and initial bucket
  capacity within hard bounds from the telemetry the system already
  records — with every decision journaled as a structured record
  that ``python -m dccrg_tpu.autopilot explain|replay`` reconstructs
  and re-derives from the journal alone).

Reference: /root/reference (dccrg.hpp and friends). This package is a
re-design for TPU, not a translation: structure (cell lists, neighbor
tables, partition) is replicated host state rebuilt at structure-change
events; data (cell payloads) lives in HBM and only moves through
compiled collectives.
"""

from .types import ERROR_CELL, ERROR_INDEX
from .length import GridLength
from .topology import GridTopology
from .mapping import Mapping
from .geometry import NoGeometry, CartesianGeometry, StretchedCartesianGeometry
from .grid import DEFAULT_NEIGHBORHOOD_ID, Grid, SlotwiseKernel, default_mesh
from .dense import DenseGrid, dense_mesh
from .verify import VerificationError, verify_all
from .txn import (GridInvariantError, MutationAbortedError, MutationError,
                  grid_transaction)
from .faults import FaultPlan
from .coord import (BarrierTimeoutError, CheckpointCommitError,
                    DistributedInitError, Membership, PeerDeadError,
                    barrier, distributed_init, trip_consensus)
from .resilience import (CheckpointCorruptionError, DeviceProbeError,
                         NumericsError, ResilienceExhaustedError,
                         ResilientRunner, guarded_step, load_checkpoint,
                         save_checkpoint, safe_devices)
from .supervise import (RESUMABLE_EXIT, CheckpointStore, PreemptedError,
                        StepTimeoutError, SupervisedRunner,
                        gc_checkpoints, resume_latest)
from .fleet import FleetJob, GridBatch
from .scheduler import (FleetPreemptedError, FleetScheduler,
                        OwnershipLostError, SLOPolicy)
from .intake import (IntakeError, IntakeRetryExhausted, StreamIntake,
                     submit as submit_job)
from .integrity import IntegrityError, register_conserved
from . import telemetry
from .telemetry import LogHistogram
from . import autopilot
from .autopilot import Autopilot
from .warmstart import WarmCacheError, WarmPool

__version__ = "0.1.0"

__all__ = [
    "ERROR_CELL",
    "ERROR_INDEX",
    "GridLength",
    "GridTopology",
    "Mapping",
    "NoGeometry",
    "CartesianGeometry",
    "StretchedCartesianGeometry",
    "Grid",
    "SlotwiseKernel",
    "DenseGrid",
    "DEFAULT_NEIGHBORHOOD_ID",
    "default_mesh",
    "dense_mesh",
    "VerificationError",
    "verify_all",
    "GridInvariantError",
    "MutationAbortedError",
    "MutationError",
    "grid_transaction",
    "FaultPlan",
    "BarrierTimeoutError",
    "CheckpointCommitError",
    "DistributedInitError",
    "Membership",
    "PeerDeadError",
    "OwnershipLostError",
    "barrier",
    "distributed_init",
    "trip_consensus",
    "CheckpointCorruptionError",
    "DeviceProbeError",
    "NumericsError",
    "ResilienceExhaustedError",
    "ResilientRunner",
    "guarded_step",
    "load_checkpoint",
    "save_checkpoint",
    "safe_devices",
    "RESUMABLE_EXIT",
    "CheckpointStore",
    "PreemptedError",
    "StepTimeoutError",
    "SupervisedRunner",
    "gc_checkpoints",
    "resume_latest",
    "FleetJob",
    "GridBatch",
    "FleetPreemptedError",
    "FleetScheduler",
    "IntakeError",
    "IntakeRetryExhausted",
    "StreamIntake",
    "submit_job",
    "IntegrityError",
    "register_conserved",
    "SLOPolicy",
    "LogHistogram",
    "telemetry",
    "autopilot",
    "Autopilot",
    "WarmCacheError",
    "WarmPool",
]
