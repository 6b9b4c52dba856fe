"""Fleet execution layer: batched many-grid multiplexing.

The production story for this framework is not one 512^3 grid — it is
THOUSANDS of concurrent small/medium scenario runs per chip (the
reference dccrg is the grid layer of simulation codes launched as
fleets of independent runs). On an accelerator the idiomatic form is a
**batch axis over same-shape grids**: N independent uniform grids are
stacked along a leading batch dimension into ONE jitted device program
(a ``jax.vmap`` of the single-grid step over the stacked field
arrays), so N scenarios share one compile, one dispatch, and one HBM
residency pass per step instead of N.

:class:`GridBatch` is that execution layer. Jobs are **bucketed** by
``(shape, periodicity, field schema, step kernel, #params)`` — the
same shape-keyed discipline as the grid's compiled-program caches — so
wildly different scenarios (different dt, seeds, step counts,
priorities) land in shared compiles; per-job parameters (dt etc.)
ride as batched scalars through the vmap. Batch capacities are
rounded with :func:`~dccrg_tpu.grid.bucket_capacity` so a drained,
backfilled bucket keeps its program.

**Per-job isolation** is the contract that makes a multi-tenant batch
safe (pinned by tests/test_fleet.py):

- the numerics watchdog is evaluated **per batch slot**
  (:meth:`GridBatch.finite_slots` — one ``[B]`` bool vector, one
  device round-trip for the whole fleet);
- NaN trips, injected OOMs and requeues touch ONLY the tripped slot:
  a slot rolls back from its own per-job checkpoint
  (:func:`dccrg_tpu.resilience.load_checkpoint_into` into the
  bucket's scratch grid, scattered into the slot) while every other
  slot's bits are untouched — the vmapped step has no cross-batch
  ops, and slot updates go through per-slot selects that preserve
  neighbor bytes exactly;
- a job's fleet-run final state is **bitwise identical** to running
  it alone (``Grid.run_steps``), because the batched gather delivers
  the same neighbor bytes the grid's own stencil paths do.

The job queue, admission, drain/backfill, per-job checkpoint stems,
preemption and retention GC live in
:class:`dccrg_tpu.scheduler.FleetScheduler`; ``python -m
dccrg_tpu.fleet`` runs a job file through it (see
:func:`_main`). Env knobs: ``DCCRG_FLEET_MAX_BATCH`` (slots per
bucket, default 128), ``DCCRG_FLEET_QUANTUM`` (steps per batched
dispatch between scheduler polls, default 8).
"""

from __future__ import annotations

import hashlib
import logging
import os

import numpy as np

import jax
import jax.numpy as jnp

from . import checkpoint as checkpoint_mod
from . import faults, integrity, warmstart
from .grid import DEFAULT_NEIGHBORHOOD_ID, Grid, default_mesh

logger = logging.getLogger("dccrg_tpu.fleet")

#: slot sentinel: a DMR shadow replica of the job in
#: ``GridBatch.shadow_of[slot]`` — occupies a slot (so admission
#: cannot reuse it) without being a schedulable job itself
SHADOW = type("_ShadowSlot", (), {"__repr__": lambda s: "<shadow>"})()


def max_batch_default(default: int = 128) -> int:
    """The ``DCCRG_FLEET_MAX_BATCH`` env knob: maximum batch slots per
    bucket (one bucket = one compiled device program)."""
    try:
        return max(1, int(os.environ.get("DCCRG_FLEET_MAX_BATCH", "")
                          or default))
    except ValueError:
        return default


def quantum_default(default: int = 8) -> int:
    """The ``DCCRG_FLEET_QUANTUM`` env knob: steps per batched
    dispatch between scheduler polls. Larger quanta amortize dispatch
    overhead; smaller quanta tighten the watchdog/checkpoint/preempt
    poll cadence (all of which run at quantum boundaries)."""
    try:
        return max(1, int(os.environ.get("DCCRG_FLEET_QUANTUM", "")
                          or default))
    except ValueError:
        return default


# ---------------------------------------------------------------------
# the step-kernel registry (the CLI's serializable kernel names)
# ---------------------------------------------------------------------

FLEET_KERNELS: dict = {}


class JobSpecError(ValueError):
    """A job record that can NEVER become a valid :class:`FleetJob`
    (missing name, malformed lengths, ...). A ValueError subclass so
    pre-existing job-file handling keeps working; typed so the
    streaming-intake front door can quarantine the record with a
    structured reason instead of retrying a permanent failure."""


class UnknownKernelError(KeyError):
    """A job names a kernel the registry (including the lazily
    imported model zoo) does not know. A KeyError subclass for
    backward compatibility; typed so admission-time validation can
    classify it as a permanent (quarantine) fault rather than a
    transient one."""

    def __init__(self, job: str, kernel, registered):
        self.job = str(job)
        self.kernel = kernel
        self.registered = sorted(registered)
        super().__init__(
            f"job {self.job!r}: unknown kernel {kernel!r} "
            f"(registered: {self.registered})")

    def __str__(self) -> str:  # KeyError quotes its arg; keep prose
        return self.args[0]


def register_kernel(name: str, fn) -> None:
    """Register a grid step kernel under a name job files can
    reference. The kernel has the standard grid-kernel signature
    ``kernel(cell_fields, nbr_fields, offs, mask, *params) ->
    {field: new_values}`` with per-job ``params`` as scalars."""
    FLEET_KERNELS[str(name)] = fn


# per-kernel job defaults: schema, field lists, params and a seeded
# default init — what lets a job file (or a bare FleetJob("x",
# kernel="mhd")) name a model-zoo kernel without spelling out its
# 8-field schema. Registered by dccrg_tpu.models on import.
FLEET_KERNEL_SPECS: dict = {}


def register_kernel_spec(name: str, *, cell_data, fields_in,
                         fields_out, params=(0.1,), init=None) -> None:
    """Register the job defaults of a named kernel: its ``cell_data``
    schema, ``fields_in``/``fields_out`` lists, default ``params``
    and (optionally) a seeded default init ``fn(grid, seed)`` used in
    place of :func:`seeded_random_init` (kernels with positivity or
    stability preconditions — MHD needs positive pressure — register
    one so the generic random fill never feeds them garbage)."""
    FLEET_KERNEL_SPECS[str(name)] = {
        "cell_data": dict(cell_data),
        "fields_in": tuple(fields_in),
        "fields_out": tuple(fields_out),
        "params": tuple(float(p) for p in params),
        "init": init,
    }


def _kernel_spec(name: str):
    """The registered spec for a kernel name, lazily importing the
    model zoo once on a miss (importing ``dccrg_tpu.models`` is what
    registers the zoo kernels)."""
    spec = FLEET_KERNEL_SPECS.get(name)
    if spec is None and name not in FLEET_KERNELS:
        from . import models  # noqa: F401 - registers the zoo

        spec = FLEET_KERNEL_SPECS.get(name)
    return spec


def _diffuse_kernel(c, nbr, offs, mask, dt):
    """Explicit neighbor-coupling relaxation of ``rho`` (the bench/
    fuzz workhorse): rho += dt * sum_nbr (rho_nbr - rho)."""
    rho = c["rho"]
    s = jnp.sum(jnp.where(mask, nbr["rho"], 0.0), axis=1)
    deg = jnp.sum(mask, axis=1).astype(rho.dtype)
    return {"rho": rho + dt * (s - deg * rho)}


def _advect_x_kernel(c, nbr, offs, mask, cfl):
    """First-order upwind advection of ``rho`` along +x, selecting the
    upwind neighbor through the slot offsets."""
    up = (offs[..., 0] < 0) & (offs[..., 1] == 0) & (offs[..., 2] == 0)
    upv = jnp.sum(jnp.where(up & mask, nbr["rho"], 0.0), axis=1)
    return {"rho": (1.0 - cfl) * c["rho"] + cfl * upv}


register_kernel("diffuse", _diffuse_kernel)
register_kernel("advect_x", _advect_x_kernel)


# Bulk-executor (DCCRG_BULK=pallas) variants: the roll-plan Pallas
# executor consumes SlotwiseKernel flux functions (one stencil leg at
# a time), so registry names that should be bulk-capable register a
# slot-wise twin here. Slot accumulation re-associates the neighbor
# sum, so a bulk bucket matches its table-gather twin to float
# re-association (the parity suite uses allclose, not digests).
FLEET_BULK_KERNELS: dict = {}


def register_bulk_kernel(name: str, slotwise) -> None:
    """Register the SlotwiseKernel twin of a named step kernel; a
    GridBatch bucket whose job names this kernel can then select the
    roll-plan Pallas bulk executor under ``DCCRG_BULK=pallas``."""
    FLEET_BULK_KERNELS[str(name)] = slotwise


def _make_diffuse_slotwise():
    from .grid import SlotwiseKernel

    def init(c, dt):
        return jnp.zeros(c["rho"].shape, c["rho"].dtype)

    def slot(acc, c, nbr, offs, mask, dt):
        return acc + jnp.where(mask, nbr["rho"] - c["rho"], 0.0)

    def finish(acc, c, dt):
        return {"rho": c["rho"] + dt * acc}

    return SlotwiseKernel(init, slot, finish)


def _make_advect_x_slotwise():
    from .grid import SlotwiseKernel

    def init(c, cfl):
        return jnp.zeros(c["rho"].shape, c["rho"].dtype)

    def slot(acc, c, nbr, offs, mask, cfl):
        up = (offs[..., 0] < 0) & (offs[..., 1] == 0) & (offs[..., 2] == 0)
        return acc + jnp.where(up & mask, nbr["rho"], 0.0)

    def finish(acc, c, cfl):
        return {"rho": (1.0 - cfl) * c["rho"] + cfl * acc}

    return SlotwiseKernel(init, slot, finish)


register_bulk_kernel("diffuse", _make_diffuse_slotwise())
register_bulk_kernel("advect_x", _make_advect_x_slotwise())


# ---------------------------------------------------------------------
# jobs
# ---------------------------------------------------------------------

class FleetJob:
    """One scenario run: an independent uniform grid with its own
    schema, kernel, parameters, step count, priority and checkpoint
    stem. Jobs whose :meth:`bucket_key` matches share one batched
    device program; everything else about them may differ.

    ``kernel`` is a registry name (:data:`FLEET_KERNELS`) or a
    grid-kernel callable; ``params`` are per-job float scalars passed
    to it as batched extras. ``init`` is a ``fn(grid)`` that fills the
    fields (default: a seeded uniform-random fill — the same bytes a
    solo run initializes with). The ``name`` doubles as the job's
    :class:`~dccrg_tpu.supervise.CheckpointStore` stem, so it must be
    unique within a scheduler."""

    def __init__(self, name, *, length=(16, 16, 16), kernel="diffuse",
                 n_steps=10, cell_data=None, fields_in=None,
                 fields_out=None, params=None, priority=0,
                 periodic=(True, True, True), hood_len=1,
                 checkpoint_every=8, max_retries=3, seed=0, init=None,
                 redundancy=1, slo_ms=None):
        self.name = str(name)
        self.length = tuple(int(v) for v in length)
        self.kernel = kernel
        self.n_steps = int(n_steps)
        # a registered kernel spec (the model zoo) supplies schema,
        # field-list and param defaults the caller left unset; kernels
        # without one keep the classic single-rho defaults
        spec = None if callable(kernel) else _kernel_spec(str(kernel))
        if cell_data is None:
            cell_data = (spec["cell_data"] if spec is not None
                         else {"rho": jnp.float32})
        if fields_in is None:
            fields_in = spec["fields_in"] if spec is not None else ("rho",)
        if fields_out is None:
            fields_out = (spec["fields_out"] if spec is not None
                          else ("rho",))
        if params is None:
            params = spec["params"] if spec is not None else (0.1,)
        self.cell_data = {}
        for fname, spec in cell_data.items():
            if isinstance(spec, tuple):
                shape, dtype = spec
            else:
                shape, dtype = (), spec
            self.cell_data[fname] = (tuple(shape), jnp.dtype(dtype))
        self.fields_in = tuple(fields_in)
        self.fields_out = tuple(fields_out)
        self.params = tuple(float(p) for p in params)
        self.priority = int(priority)
        self.periodic = tuple(bool(p) for p in periodic)
        self.hood_len = int(hood_len)
        self.checkpoint_every = int(checkpoint_every)
        self.max_retries = int(max_retries)
        self.seed = int(seed)
        self.init = init
        # redundancy=2: dual modular redundancy (DMR) — the scheduler
        # steps the job in TWO slots and bitwise-compares their
        # digests at every quantum boundary; a mismatch is a CORRUPT
        # trip (see dccrg_tpu.integrity)
        self.redundancy = max(1, int(redundancy))
        # latency SLO: a completion deadline in milliseconds, measured
        # from the job's first admission to the scheduler queue. The
        # scheduler's SLOPolicy prefers jobs whose PROJECTED completion
        # (telemetry quantum-latency EWMA x remaining quanta) would
        # blow the deadline, and sheds best-effort neighbors out of a
        # bucket whose measured quantum latency blows the tightest
        # admitted SLO. None = best-effort (pure priority admission).
        self.slo_ms = None if slo_ms is None else float(slo_ms)
        self.slo_t0 = None  # policy-clock time of the first add()
        # scheduler-owned runtime state
        self.steps_done = 0
        self.retries = 0
        self.requeues = 0
        self.rollbacks = 0
        self.transient_retries = 0
        self.trips = []  # [(kind, at_step)]
        self.status = "queued"
        self.digest = None
        self.last_save_step = None
        self._last_trip_step = -1
        # integrity runtime state: the slot fingerprint recorded at
        # the end of the last quantum ({field: uint32[2]}), reset by
        # every sanctioned slot rewrite (admission, restore)
        self._fp = None

    def resolved_kernel(self):
        if callable(self.kernel):
            return self.kernel
        fn = FLEET_KERNELS.get(str(self.kernel))
        if fn is None:
            _kernel_spec(str(self.kernel))  # zoo registration on miss
            fn = FLEET_KERNELS.get(str(self.kernel))
        if fn is None:
            raise UnknownKernelError(self.name, self.kernel,
                                     FLEET_KERNELS)
        return fn

    def bucket_key(self):
        """The compile-sharing key: jobs with equal keys stack into
        one batched program. Parameters, seeds, priorities and step
        counts are NOT part of it (they ride as batched scalars or
        scheduler state). Every field's dtype IS part of it (via the
        schema triples): a bfloat16 job can never share a compiled
        program — or a ``[capacity, R]`` state allocation — with a
        float32 bucket."""
        schema = tuple(sorted(
            (n, tuple(shape), str(jnp.dtype(dtype)))
            for n, (shape, dtype) in self.cell_data.items()))
        # a registry name buckets by that name; a callable buckets by
        # its own identity (two jobs share a program only when they
        # share the function object)
        return (self.length, self.periodic, self.hood_len, schema,
                self.kernel,
                self.fields_in, self.fields_out, len(self.params))

    def apply_init(self, grid) -> None:
        """Fill ``grid``'s fields with this job's initial state —
        byte-identical whether the grid is a fleet scratch grid or a
        solo run's own."""
        if self.init is not None:
            self.init(grid)
        else:
            spec = (None if callable(self.kernel)
                    else FLEET_KERNEL_SPECS.get(str(self.kernel)))
            fn = spec.get("init") if spec is not None else None
            (fn if fn is not None else seeded_random_init)(
                grid, self.seed)
        grid.update_copies_of_remote_neighbors()


def seeded_random_init(grid, seed: int) -> None:
    """The default job init: a seeded uniform-random fill of every
    field (deterministic in (schema, cell count, seed))."""
    rng = np.random.default_rng(seed)
    cells = grid.plan.cells
    for name in sorted(grid.fields):
        shape, dtype = grid.fields[name]
        vals = (rng.random((len(cells),) + shape) * 100.0).astype(dtype)
        grid.set(name, cells, vals)


def template_grid(job: FleetJob, device=None) -> Grid:
    """The single-device uniform grid a job describes — the bucket's
    template/scratch grid, and the solo baseline's grid."""
    if device is None:
        device = jax.devices()[0]
    return (Grid(cell_data=dict(job.cell_data))
            .set_initial_length(job.length)
            .set_maximum_refinement_level(0)
            .set_neighborhood_length(job.hood_len)
            .set_periodic(*job.periodic)
            .initialize(default_mesh([device])))


def run_solo(job: FleetJob, device=None) -> str:
    """Run ``job`` alone through the ordinary ``Grid.run_steps`` path
    and return its final-state digest
    (:func:`dccrg_tpu.checkpoint.state_digest`) — the one-grid-at-a-
    time baseline every fleet-run job must match bitwise."""
    g = template_grid(job, device)
    job.apply_init(g)
    extras = tuple(jnp.float32(p) for p in job.params)
    kernel = job.resolved_kernel()
    if job.n_steps:
        g.run_steps(kernel, job.fields_in, job.fields_out, job.n_steps,
                    extra_args=extras)
    return checkpoint_mod.state_digest(g)


# ---------------------------------------------------------------------
# the batched execution layer
# ---------------------------------------------------------------------

# compiled fleet programs, shared across GridBatch instances (and
# therefore across drained/recreated buckets) by (bucket key,
# capacity). FIFO-bounded: the cache outlives batches.
_FLEET_PROGRAMS: dict = {}
_FLEET_PROGRAMS_MAX = 64


class GridBatch:
    """N independent same-shape uniform grids stacked along a leading
    batch axis into one jitted device program.

    The batch owns one **template grid** (also its checkpoint scratch
    grid) whose plan supplies the neighbor gather tables, and per-field
    state arrays of shape ``[capacity, R, *field_shape]``. The step
    program is ``vmap`` of the single-grid table-gather step with
    per-job parameters as batched scalars, run under
    ``lax.fori_loop`` with a per-slot step **budget**: slot ``k``
    advances ``budget[k]`` steps this dispatch and its bytes are
    FROZEN afterwards (a per-slot select keeps the old array bits),
    which is how jobs at different step counts, finished jobs and
    tripped/masked slots coexist in one program."""

    def __init__(self, proto: FleetJob, capacity: int, device=None,
                 skeleton=False):
        self.key = proto.bucket_key()
        self.capacity = int(capacity)
        self.device = device
        self.grid = template_grid(proto, device)
        plan = self.grid.plan
        self.L = int(plan.L)
        self.R = int(plan.R)
        self.n_own = int(plan.n_local[0])
        hood = plan.hoods[DEFAULT_NEIGHBORHOOD_ID]
        # [L, S] rows / mask and the mask-zeroed [L, S, 3] offsets —
        # exactly the neighbor bytes the grid's own stencil paths
        # deliver (invalid slots point at the permanent zero pad row)
        self._rows = np.asarray(hood.nbr_rows[0])
        self._mask = np.asarray(hood.nbr_mask[0])
        self._offs = np.asarray(hood.nbr_offs[0])
        self.fields_in = proto.fields_in
        self.fields_out = proto.fields_out
        self.kernel = proto.resolved_kernel()
        # the DCCRG_BULK=pallas twin (SlotwiseKernel) when the job
        # names a bulk-capable registry kernel; callables have no twin
        self.bulk_kernel = (None if callable(proto.kernel)
                            else FLEET_BULK_KERNELS.get(str(proto.kernel)))
        self.n_extra = len(proto.params)
        self.schema = dict(self.grid.fields)
        # the SDC invariant sets: fields the device fingerprints (32-
        # bit element types bitcast losslessly; SCALAR 16-bit fields —
        # bf16 state — widen each element to its own uint32 word,
        # which matches the host packer's one-padded-word-per-row
        # layout only when the row IS one element, so vector 16-bit
        # fields stay out) and fields the kernel provably conserves
        # under this bucket's periodicity
        self.fp_fields = tuple(
            n for n in sorted(self.schema)
            if jnp.dtype(self.schema[n][1]).itemsize == 4
            or (jnp.dtype(self.schema[n][1]).itemsize == 2
                and self.schema[n][0] == ()))
        self.conserved = integrity.conserved_fields(
            proto.kernel, proto.periodic, proto.fields_out)
        # DMR shadow replicas: shadow slot -> primary slot
        self.shadow_of: dict = {}
        #: host invariants of the last integrity-on dispatch
        #: ({"fp_in"/"fp_out": {field: [B, 2]}, "cs_in"/"cs_out":
        #: {field: [B]}}), None with DCCRG_INTEGRITY=0
        self.last_inv = None
        self.slots: list = [None] * self.capacity
        self._extras = np.zeros((self.capacity, self.n_extra),
                                dtype=np.float32)
        self.state = {}
        # a skeleton batch carries only the program-construction
        # inputs (plan tables, schema, kernel) — no [capacity, R, ...]
        # state allocation. The warm-start pool builds one per
        # manifested key to pre-compile programs without touching HBM.
        if not skeleton:
            for name, (shape, dtype) in self.schema.items():
                z = jnp.zeros((self.capacity, self.R) + shape,
                              dtype=dtype)
                if device is not None:
                    z = jax.device_put(z, device)
                self.state[name] = z
        self.dispatches = 0

    # -- program construction (shared per bucket key) -----------------

    def _program_key(self):
        # the integrity flag is part of the cache key: with
        # DCCRG_INTEGRITY=0 the quantum program is BIT-IDENTICAL to
        # the pre-SDC one (no fingerprint ops, no extra outputs) —
        # the negative pin of the SDC defense, not a cheaper check
        int_on = integrity.integrity_enabled()
        # DCCRG_BULK=pallas buckets whose kernel has a registered bulk
        # twin step through the roll-plan Pallas executor (the fleet
        # quantum is then a batched bulk pass instead of a vmapped
        # table gather); the mode is part of the program key so bulk
        # and table programs never alias
        from .ops import roll_executor

        want_bulk = (roll_executor.bulk_mode() == "pallas"
                     and self.bulk_kernel is not None)
        return (self.key, self.capacity, int_on, want_bulk)

    def _programs(self):
        key = self._program_key()
        hit = _FLEET_PROGRAMS.get(key)
        if hit is not None:
            return hit
        # a pre-compiled program from the warm-start pool is the
        # exact tuple _build_programs would produce, with the trace +
        # compile already paid on the background thread (None when no
        # DCCRG_COMPILE_CACHE pool is active — the negative pin)
        hit = warmstart.take_prewarmed(key, device=self.device)
        if hit is None:
            hit = self._build_programs(key)
        if len(_FLEET_PROGRAMS) >= _FLEET_PROGRAMS_MAX:
            _FLEET_PROGRAMS.pop(next(iter(_FLEET_PROGRAMS)))
        _FLEET_PROGRAMS[key] = hit
        return hit

    def _build_programs(self, key):
        int_on, want_bulk = key[2], key[3]
        from .ops import roll_executor

        bulk_step = None
        if want_bulk:
            bulk_step = roll_executor.make_fleet_bulk_step(
                self.grid, self.bulk_kernel, self.fields_in,
                self.fields_out, self.n_extra, self.capacity)
        rows = jnp.asarray(self._rows)
        mask = jnp.asarray(self._mask)
        offs = jnp.asarray(self._offs)
        L, fin, fout = self.L, self.fields_in, self.fields_out
        kernel, n_extra = self.kernel, self.n_extra

        def step_one(state, ex):
            cell = {n: state[n][:L] for n in fin}
            nbr = {n: state[n][rows] for n in fin}
            extras = tuple(ex[i] for i in range(n_extra))
            out = kernel(cell, nbr, offs, mask, *extras)
            new = dict(state)
            for n in fout:
                new[n] = state[n].at[:L].set(out[n].astype(state[n].dtype))
            return new

        vstep = (bulk_step if bulk_step is not None
                 else jax.vmap(step_one, in_axes=(0, 0)))

        def loop(state, extras, budget, q):
            def body(i, st):
                new = vstep(st, extras)
                live = i < budget  # [B]: per-slot step budget

                def sel(a, b):
                    m = live.reshape((-1,) + (1,) * (a.ndim - 1))
                    return jnp.where(m, a, b)

                # exhausted/masked slots keep their OLD array bits —
                # the per-slot freeze the isolation contract rests on
                return {n: sel(new[n], st[n]) for n in st}

            return jax.lax.fori_loop(0, q, body, state)

        watched = [n for n in sorted(self.schema)
                   if jnp.issubdtype(self.schema[n][1], jnp.inexact)]
        fp_fields, conserved = self.fp_fields, self.conserved
        # locals only: a `self` capture would pin every batch (its
        # [capacity, R, ...] device arrays included) in the
        # module-global program cache for the process lifetime
        cap = self.capacity

        def finite(state):
            ok = jnp.ones((cap,), bool)
            for n in watched:
                v = state[n][:, :L]
                ok = ok & jnp.isfinite(v).reshape(v.shape[0], -1).all(axis=1)
            return ok

        def measure(state):
            # per-slot invariants over the OWNED rows, PACKED into two
            # stacked arrays (one device->host transfer each instead
            # of one per field): exact uint32 fingerprint pairs
            # [F, B, 2] in fp_fields order, float conservation sums
            # [C, B] in conserved order
            fp = (jnp.stack([
                jax.vmap(lambda a: integrity.device_fingerprint(a, L))(
                    state[n]) for n in fp_fields])
                if fp_fields else jnp.zeros((0, cap, 2), jnp.uint32))
            cs = (jnp.stack([
                jnp.sum(state[n][:, :L].reshape(state[n].shape[0], -1),
                        axis=1, dtype=jnp.float32) for n in conserved])
                if conserved else jnp.zeros((0, cap), jnp.float32))
            return fp, cs

        if int_on:
            def run_quantum(state, extras, budget, q):
                # the device computes its own fingerprint of the input
                # AND output state in the same dispatch/HBM residency
                # pass as the step — the in-program invariant
                fp_in, cs_in = measure(state)
                out = loop(state, extras, budget, q)
                fp_out, cs_out = measure(out)
                return out, (fp_in, fp_out, cs_in, cs_out)

            fp_now = jax.jit(lambda state: measure(state)[0])
        else:
            run_quantum, fp_now = loop, None

        # the bulk flag rides the cache entry: the solo-path shadow
        # audit must know whether this program's arithmetic is the
        # table kernel's (bitwise-comparable to Grid.run_steps) or the
        # bulk twin's (matches only to float re-association)
        return (jax.jit(run_quantum), jax.jit(finite), fp_now,
                bulk_step is not None)

    # -- slot management ----------------------------------------------

    def free_slot(self):
        """Lowest free slot index, or None when the batch is full."""
        try:
            return self.slots.index(None)
        except ValueError:
            return None

    @property
    def jobs(self):
        """``[(slot, job)]`` of the occupied slots (DMR shadow
        replicas excluded — they are not schedulable jobs)."""
        return [(i, j) for i, j in enumerate(self.slots)
                if j is not None and j is not SHADOW]

    def admit(self, job: FleetJob, from_grid: bool = True):
        """Place ``job`` into the lowest free slot. With ``from_grid``
        (default) the template/scratch grid's current field data —
        just initialized or just restored from the job's checkpoint —
        is scattered into the slot."""
        slot = self.free_slot()
        if slot is None:
            raise RuntimeError("batch is full")
        self.slots[slot] = job
        self._extras[slot] = np.asarray(job.params, dtype=np.float32)
        if from_grid:
            self.read_grid(slot)
        return slot

    def clear(self, slot: int) -> None:
        """Free a slot (job finished/failed/requeued) together with
        any DMR shadow replicas attached to it. The bytes stay as
        they are — budget 0 freezes them and the next occupant
        overwrites every row."""
        self.slots[slot] = None
        for sh, primary in list(self.shadow_of.items()):
            if primary == slot:
                self.slots[sh] = None
                del self.shadow_of[sh]

    # -- DMR shadow replicas ------------------------------------------

    def admit_shadow(self, primary: int):
        """Occupy a free slot with a SHADOW replica of ``primary``:
        same state bytes, same extras, same budgets every quantum —
        the dual-modular-redundancy pair whose digests the scheduler
        compares at every quantum boundary. Returns the shadow slot,
        or None when the batch has no room (the job then runs
        unreplicated)."""
        slot = self.free_slot()
        if slot is None:
            return None
        self.slots[slot] = SHADOW
        self.shadow_of[slot] = primary
        self._extras[slot] = self._extras[primary]
        self.sync_shadow(primary)
        return slot

    def shadows(self, primary: int) -> list:
        """The shadow slots replicating ``primary``."""
        return [sh for sh, pr in self.shadow_of.items() if pr == primary]

    def sync_shadow(self, primary: int) -> None:
        """Re-copy ``primary``'s rows into its shadow slots bit-exactly
        (admission, and after any sanctioned primary rewrite — a
        rollback or migration — so the replicas re-diverge only
        through real corruption)."""
        for sh in self.shadows(primary):
            for n in self.schema:
                self.state[n] = self.state[n].at[sh].set(
                    self.state[n][primary])

    def read_grid(self, slot: int) -> None:
        """Scatter the scratch grid's field data into ``slot``
        (admission and per-slot restore). Only the target slot's rows
        change; every other slot's bits are preserved exactly."""
        for n in self.schema:
            self.state[n] = self.state[n].at[slot].set(self.grid.data[n][0])

    def write_grid(self, slot: int) -> Grid:
        """Gather ``slot``'s field data into the scratch grid (per-slot
        checkpoint save) and return it."""
        sh = self.grid._sharding()
        for n in self.schema:
            self.grid.data[n] = jax.device_put(self.state[n][slot][None], sh)
        return self.grid

    def extract(self, slot: int) -> dict:
        """Host copies of ``slot``'s field arrays (``[R, *shape]``)."""
        return {n: np.asarray(self.state[n][slot]) for n in self.schema}

    def insert(self, slot: int, host_state: dict) -> None:
        """Write :meth:`extract`-shaped host arrays into ``slot``
        bit-exactly — the migration/audit primitive (bucket rebuilds,
        shadow re-execution). Only the target slot's rows change."""
        for n, arr in host_state.items():
            self.state[n] = self.state[n].at[slot].set(arr)

    # -- the batched dispatch -----------------------------------------

    def step(self, budget) -> int:
        """Advance slot ``k`` by ``budget[k]`` steps in ONE jitted
        batched dispatch; returns the quantum length (max budget).
        Slots with budget 0 (empty, finished, tripped-and-masked) are
        frozen bit-exactly. With integrity on, the dispatch also
        returns the fused per-slot invariants (entry/exit
        fingerprints + conservation sums), published on
        :attr:`last_inv` as host arrays."""
        # quantum boundaries are the fleet's step boundaries: a
        # structure plan a background recommit finished for the scratch
        # grid installs here, never mid-quantum (DCCRG_BG_RECOMMIT —
        # the same swap discipline as Grid.run_steps). Distributed-AMR
        # grids (enable_distributed_amr) must never reach this site
        # with a deferred build: their install is an epoch-fenced
        # COLLECTIVE (distamr commit phase), and a per-host quantum
        # boundary cannot host a collective swap — one host installing
        # while a peer keeps stepping the old plan is exactly the
        # divergence the fenced protocol exists to prevent.
        if self.grid.bg_pending():
            if getattr(self.grid, "_amr_group", None) is not None:
                raise RuntimeError(
                    "distributed-AMR grid reached a per-host swap site "
                    "with a deferred plan build; the fenced collective "
                    "install (distamr) must commit it instead")
            self.grid.bg_install()
        budget = np.asarray(budget, dtype=np.int32)
        q = int(budget.max()) if len(budget) else 0
        if q <= 0:
            return 0
        fn, _finite, fp_now, _bulk = self._programs()
        out = fn(self.state, jnp.asarray(self._extras),
                 jnp.asarray(budget), jnp.int32(q))
        if fp_now is None:  # DCCRG_INTEGRITY=0: the pre-SDC program
            self.state, self.last_inv = out, None
        else:
            self.state, inv = out
            fp_in, fp_out, cs_in, cs_out = jax.device_get(inv)
            self.last_inv = {
                "fp_in": {n: fp_in[i]
                          for i, n in enumerate(self.fp_fields)},
                "fp_out": {n: fp_out[i]
                           for i, n in enumerate(self.fp_fields)},
                "cs_in": {n: cs_in[i]
                          for i, n in enumerate(self.conserved)},
                "cs_out": {n: cs_out[i]
                           for i, n in enumerate(self.conserved)},
            }
        self.dispatches += 1
        return q

    def bulk_active(self) -> bool:
        """Whether this bucket's quantum program steps through the
        roll-plan Pallas bulk executor (DCCRG_BULK=pallas with a
        registered bulk twin that proved eligible). Bulk arithmetic
        matches the table kernel only to float re-association, so
        bitwise cross-program comparisons (the solo-path shadow
        audit) must not span the two."""
        return self._programs()[3]

    def finite_slots(self) -> np.ndarray:
        """Per-slot numerics watchdog: ``[capacity]`` bool, True where
        every watched (inexact) field element of the slot is finite.
        One device round-trip for the whole fleet; a poisoned slot
        cannot hide behind its neighbors."""
        _fn, finite, _fp, _bulk = self._programs()
        return np.asarray(finite(self.state))

    def fingerprint_slots(self) -> dict:
        """Per-slot integrity fingerprints of the CURRENT state:
        ``{field: uint32[capacity, 2]}``. The pairs are exact
        order-independent sums, so they compare bitwise against the
        fused in-dispatch fingerprints (:attr:`last_inv`) — any
        difference means the slot's bytes changed outside a sanctioned
        path. Raises RuntimeError with integrity off (there is no
        fingerprint program then, by design)."""
        _fn, _finite, fp_now, _bulk = self._programs()
        if fp_now is None:
            raise RuntimeError(
                "fingerprint_slots needs DCCRG_INTEGRITY enabled")
        stack = np.asarray(fp_now(self.state))
        return {n: stack[i] for i, n in enumerate(self.fp_fields)}

    def slot_fingerprint(self, slot: int) -> dict:
        """One slot's ``{field: (s1, s2)}`` from
        :meth:`fingerprint_slots`."""
        return {n: (int(v[slot, 0]), int(v[slot, 1]))
                for n, v in self.fingerprint_slots().items()}

    def poison(self, slot: int, fld: str, cells, value) -> None:
        """Write ``value`` into ``fld`` at ``cells`` of ONE slot — the
        fleet-scoped fault-injection landing pad
        (:func:`dccrg_tpu.faults.poison_fleet`)."""
        _dev, rows = self.grid._host_rows(cells)
        self.state[fld] = self.state[fld].at[slot, rows].set(value)

    def flip(self, slot: int, fld: str, cells, bit: int) -> None:
        """Land a FINITE bit-flip in ``fld`` at ``cells`` of ONE slot
        — the silent-corruption landing pad
        (:func:`dccrg_tpu.faults.flip_fleet`). Invisible to
        :meth:`finite_slots` by construction; only the integrity
        layer can see it."""
        _dev, rows = self.grid._host_rows(cells)
        vals = np.asarray(self.state[fld][slot, rows])
        self.state[fld] = self.state[fld].at[slot, rows].set(
            faults.flip_values(vals, bit))

    def digest(self, slot: int) -> str:
        """SHA-256 over the slot's OWNED cell bytes — matches
        :func:`dccrg_tpu.checkpoint.state_digest` of a solo grid
        holding the same state."""
        h = hashlib.sha256()
        for name in sorted(self.schema):
            shape, dtype = self.schema[name]
            h.update(repr((name, tuple(shape), str(dtype))).encode())
            h.update(np.ascontiguousarray(
                np.asarray(self.state[name][slot])[:self.n_own]).tobytes())
        return h.hexdigest()


# ---------------------------------------------------------------------
# CLI: python -m dccrg_tpu.fleet <jobs.json> | --demo N
# ---------------------------------------------------------------------

def job_from_row(row: dict, *, validate_kernel: bool = False) -> FleetJob:
    """Parse ONE job record into a :class:`FleetJob` — the single
    validation/kernel-spec-registry path shared by job files
    (:func:`_jobs_from_spec`) and the streaming-intake spool
    (``dccrg_tpu/intake.py``). Per-job keys: ``name`` (required,
    unique), ``n`` (cube edge) or ``length`` [x, y, z], ``kernel``
    (registry name), ``steps``, ``params`` (list of floats; ``dt`` is
    shorthand for one), ``priority``, ``seed``, ``checkpoint_every``,
    ``periodic`` [bool, bool, bool], ``redundancy`` (2 = DMR: two
    slots step the job and their digests are compared every
    quantum), ``slo_ms`` (completion-deadline milliseconds for the
    scheduler's latency-SLO admission; absent = best-effort).

    Malformed records raise the typed :class:`JobSpecError`;
    ``validate_kernel=True`` additionally resolves the kernel name
    eagerly so an unknown kernel surfaces HERE as the typed
    :class:`UnknownKernelError` (the intake quarantine reason)
    instead of a raw ``KeyError`` at first dispatch."""
    if not isinstance(row, dict):
        raise JobSpecError(f"job row is not a mapping: {row!r}")
    if "name" not in row:
        raise JobSpecError(f"job row without a name: {row}")
    try:
        length = (tuple(int(v) for v in row["length"])
                  if "length" in row else (int(row.get("n", 16)),) * 3)
        if len(length) != 3 or any(v < 1 for v in length):
            raise JobSpecError(
                f"job {row['name']!r}: bad length {length}")
        params = row.get("params")
        if params is None and "dt" in row:
            params = [float(row["dt"])]
        # params None falls through to the kernel's registered spec
        # default (the model zoo) or the classic (0.1,) in FleetJob
        job = FleetJob(
            row["name"], length=length,
            kernel=row.get("kernel", "diffuse"),
            n_steps=int(row.get("steps", 10)), params=params,
            priority=int(row.get("priority", 0)),
            seed=int(row.get("seed", 0)),
            periodic=tuple(row.get("periodic", (True, True, True))),
            checkpoint_every=int(row.get("checkpoint_every", 8)),
            redundancy=int(row.get("redundancy", 1)),
            slo_ms=row.get("slo_ms"),
        )
    except JobSpecError:
        raise
    except (TypeError, ValueError, KeyError) as e:
        raise JobSpecError(
            f"job {row.get('name')!r}: malformed record: {e}") from e
    if validate_kernel and not callable(job.kernel):
        job.resolved_kernel()  # UnknownKernelError on a registry miss
    return job


def _jobs_from_spec(spec: dict) -> list:
    """Parse a job-file dict (``{"jobs": [{...}]}``) into
    :class:`FleetJob` objects via :func:`job_from_row` (one shared
    validation path — see its docstring for the per-job keys)."""
    return [job_from_row(row) for row in spec.get("jobs", [])]


def _main(argv=None) -> int:
    """``python -m dccrg_tpu.fleet jobs.json [--workdir DIR]`` — run a
    fleet job file through :class:`~dccrg_tpu.scheduler
    .FleetScheduler` (``--demo N`` synthesizes N diffuse jobs
    instead). Prints one JSON row per finished job plus a summary;
    exits 75 (resumable) when preempted mid-fleet — rerun with the
    same workdir to resume every requeued job from its emergency
    checkpoint."""
    import argparse
    import json
    import sys
    import tempfile
    import time

    ap = argparse.ArgumentParser(prog="python -m dccrg_tpu.fleet",
                                 description=_main.__doc__)
    ap.add_argument("jobs_file", nargs="?", default=None,
                    help="JSON job file ({'jobs': [{...}]})")
    ap.add_argument("--demo", type=int, default=None, metavar="N",
                    help="synthesize N diffuse jobs instead of a file")
    ap.add_argument("--n", type=int, default=16,
                    help="--demo grid edge length (default 16)")
    ap.add_argument("--steps", type=int, default=20,
                    help="--demo steps per job (default 20)")
    ap.add_argument("--workdir", default=None,
                    help="checkpoint directory (default: a temp dir)")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--quantum", type=int, default=None)
    ap.add_argument("--keep-last", type=int, default=None)
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore existing checkpoints in the workdir")
    ap.add_argument("--autopilot", action="store_true",
                    help="enable the telemetry-driven self-tuning "
                         "controller (same as DCCRG_AUTOPILOT=1; "
                         "decisions journal to DCCRG_DECISION_FILE)")
    args = ap.parse_args(argv)
    if args.autopilot:
        os.environ["DCCRG_AUTOPILOT"] = "1"

    from .scheduler import FleetPreemptedError, FleetScheduler

    if args.demo is not None:
        jobs = [FleetJob(f"demo{i:04d}", length=(args.n,) * 3,
                         n_steps=args.steps, params=(0.05,), seed=i,
                         priority=i % 3)
                for i in range(args.demo)]
    elif args.jobs_file:
        with open(args.jobs_file) as f:
            jobs = _jobs_from_spec(json.load(f))
    else:
        ap.error("either a jobs file or --demo N is required")

    workdir = args.workdir or tempfile.mkdtemp(prefix="dccrg_fleet_")
    sched = FleetScheduler(
        workdir, jobs, max_batch=args.max_batch, quantum=args.quantum,
        keep_last=args.keep_last, resume=not args.no_resume,
        install_signal_handlers=True)
    t0 = time.perf_counter()
    try:
        report = sched.run()
    except FleetPreemptedError as e:
        print(json.dumps({"preempted": True,
                          "requeued": e.requeued,
                          "workdir": workdir}), flush=True)
        return e.exit_code
    wall = time.perf_counter() - t0
    from . import telemetry

    reg = telemetry.registry()
    done = failed = steps = 0
    for name in sorted(report):
        row = dict(report[name], name=name)
        # the per-job end-of-run summary comes from the telemetry
        # registry (the same series dump_prometheus exposes), not
        # ad-hoc prints: quantum-latency quantiles, trip/rollback
        # counters, and throughput over the fleet wall
        h = reg.histogram("dccrg_fleet_quantum_seconds", job=name)
        row.update({
            "quantum_p50_ms": (round(h.quantile(0.5) * 1e3, 3)
                               if h is not None and h.total else None),
            "quantum_p99_ms": (round(h.quantile(0.99) * 1e3, 3)
                               if h is not None and h.total else None),
            "trips_total": int(reg.counter_total(
                "dccrg_fleet_trips_total", job=name)),
            "rollbacks_total": int(reg.counter_total(
                "dccrg_fleet_rollbacks_total", job=name)),
            "steps_per_s": (round(row["steps"] / wall, 3)
                            if wall > 0 else None),
        })
        print(json.dumps(row), flush=True)
        done += row["status"] == "done"
        failed += row["status"] == "failed"
        steps += row["steps"]
    summary = {
        "jobs": len(report), "done": done, "failed": failed,
        "steps_total": steps, "wall_s": round(wall, 3),
        "runs_per_s": round(done / wall, 3) if wall > 0 else None,
        "workdir": workdir}
    if sched.autopilot is not None:
        ap_state = sched.autopilot
        summary["autopilot"] = {
            "decisions": ap_state.seq,
            "quantum": ap_state.quantum,
            "audit_every": ap_state.audit_every,
            "learned_capacities": dict(ap_state.capacity),
        }
    print(json.dumps({"summary": summary}), flush=True)
    return 0 if failed == 0 else 1


if __name__ == "__main__":  # pragma: no cover - exercised via CLI
    import sys

    # `python -m dccrg_tpu.fleet` loads this FILE as __main__ — a
    # second module instance with its own registry dicts. The model
    # zoo registers into the canonical `dccrg_tpu.fleet` module, so
    # run the CLI through that instance or a zoo kernel named by the
    # job file would be "unknown" here
    from dccrg_tpu import fleet as _canonical

    sys.exit(_canonical._main())
