"""Silent-data-corruption (SDC) defense: in-program integrity
invariants and the fingerprint primitives the audit layers share.

Every robustness layer so far defends against *detectable* faults:
NaN/Inf trips the numerics watchdog, OOM walks the gather fallback
chain, rank death times out a barrier, a torn write fails its CRC.
None of them can see a fault that lands **finite, plausible, wrong
bits** in device state — ``comm.all_finite`` passes, the checkpoint
CRC faithfully seals the corrupted bytes, and the fleet serves a
silently wrong answer. At fleet scale this is the dominant unhandled
failure mode ("Cores that don't count", Hochschild et al., HotOS'21;
"Silent Data Corruptions at Scale", Dixit et al., arXiv:2102.11245).

Three defense layers, cheapest first (all off with
``DCCRG_INTEGRITY=0`` — the fleet step program is then bitwise
unchanged, pinned by the negative tests):

1. **In-program invariants** (this module + the fleet quantum program,
   :meth:`dccrg_tpu.fleet.GridBatch._programs`): the device computes
   its own per-slot *fingerprint* — an order-independent
   Fletcher-style pair of uint32 sums over the owned rows — of both
   the input and the output state **in the same HBM pass as the
   step**, plus per-field conservation sums for kernels registered
   conservative. The host compares exactly (integer fingerprints are
   order-independent and therefore bit-reproducible across programs)
   or against the expected drift (float conservation sums, tolerance
   :func:`sum_tolerance`). Catches corruption of resident state
   between dispatches and gross in-compute corruption, every quantum,
   at near-zero cost.
2. **Shadow-execution audits** (:mod:`dccrg_tpu.scheduler`): at a
   sampled cadence (``DCCRG_AUDIT_EVERY``) the last quantum is
   re-executed from the pre-quantum state in a spare fleet slot (or
   the solo path) and the results are compared bitwise — catches
   *any* divergence, including in-compute corruption of
   non-conservative kernels, and attributes it to a slot/device.
   ``FleetJob(redundancy=2)`` is the always-on variant (DMR): two
   slots step the same job and their digests are compared at every
   quantum boundary.
3. **Containment**: a corrupt verdict is a *recoverable trip*
   (``resilience._TRIP_CORRUPT``, between the numerics and OOM
   classes) — the victim rolls back from its own checkpoint chain and
   replays, bounded retries, exactly mirroring the NaN path; repeat
   offenders quarantine their device
   (``DCCRG_QUARANTINE_AFTER``, :class:`~dccrg_tpu.scheduler
   .FleetScheduler`) with bit-exact survivor migration.

The fingerprint is also recorded in every checkpoint's CRC sidecar
(single-controller saves) so ``python -m dccrg_tpu.resilience audit
<ckpt>`` can re-derive it from the file's payload bytes offline: a
checkpoint whose CRCs verify but whose payload no longer matches the
fingerprint taken from live device state at save time is at-rest SDC
under an intact-looking CRC epoch.

Why Fletcher-*style*: a real Fletcher checksum is positional; these
pairs are ``(sum(x), sum((lo16(x)+1)*(hi16(x)+1)))`` over uint32
words in wrapping uint32 arithmetic — commutative and associative
EXACTLY, so device reductions (any order XLA picks), host numpy
reductions and file-payload reductions all agree bit-for-bit on
equal bytes, while compensating multi-word changes that preserve the
linear sum still shift the nonlinear one.
"""

from __future__ import annotations

import os

import numpy as np

from .resilience import ResilienceExhaustedError

logger = __import__("logging").getLogger("dccrg_tpu.integrity")


class IntegrityError(ResilienceExhaustedError):
    """CORRUPT trips exhausted their bounded retries: device state
    repeatedly failed its own fingerprint/conservation invariants
    while every cheaper detector (finiteness, CRCs) passed — the
    persistent silent-data-corruption signature, most likely a
    defective device rather than a transient upset. Raised by
    :class:`~dccrg_tpu.resilience.ResilientRunner` in place of the
    plain :class:`~dccrg_tpu.resilience.ResilienceExhaustedError`
    (which it subclasses, so generic handlers keep working).
    ``details`` maps invariant name -> a short description of the
    mismatch."""

    def __init__(self, msg, details=None):
        super().__init__(msg)
        self.details = dict(details or {})


# ---------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------

def integrity_enabled(default: bool = True) -> bool:
    """The ``DCCRG_INTEGRITY`` env knob: in-program integrity
    invariants on (default) or off. Off means *no program change at
    all* — the fleet quantum program compiles to exactly the
    pre-integrity bytes (the negative pin), not a cheaper check."""
    v = os.environ.get("DCCRG_INTEGRITY", "")
    if v == "":
        return default
    return v not in ("0", "off", "false", "no")


def audit_every_default(default: int = 0) -> int:
    """The ``DCCRG_AUDIT_EVERY`` env knob: run a shadow-execution
    audit every N scheduler ticks (0 = audits off). Each audit
    re-executes ONE slot's last quantum from its pre-quantum state and
    compares bitwise."""
    try:
        return max(0, int(os.environ.get("DCCRG_AUDIT_EVERY", "")
                          or default))
    except ValueError:
        return default


def quarantine_after_default(default: int = 3) -> int:
    """The ``DCCRG_QUARANTINE_AFTER`` env knob: corrupt verdicts
    attributed to one device lane before the scheduler quarantines it
    and migrates the survivors (0 = never quarantine)."""
    try:
        return max(0, int(os.environ.get("DCCRG_QUARANTINE_AFTER", "")
                          or default))
    except ValueError:
        return default


def integrity_rtol(default: float = 1e-4) -> float:
    """The ``DCCRG_INTEGRITY_RTOL`` env knob: relative tolerance for
    conservation-sum drift (float reductions are inexact; the
    fingerprints are the exact layer)."""
    try:
        return float(os.environ.get("DCCRG_INTEGRITY_RTOL", "")
                     or default)
    except ValueError:
        return default


def note_suspect(lane: int, count: int,
                 quarantined: bool = False) -> None:
    """Export one device lane's suspect accounting as live gauges
    (``dccrg_lane_suspects{lane}`` / ``dccrg_lane_quarantined{lane}``)
    — a first-class controller input for the autopilot's audit-cadence
    rule and the operator's dashboard, useful with the autopilot off
    too."""
    from . import telemetry

    telemetry.set_gauge("dccrg_lane_suspects", int(count),
                        lane=str(int(lane)))
    telemetry.set_gauge("dccrg_lane_quarantined",
                        1 if quarantined else 0, lane=str(int(lane)))


def sum_tolerance(base, n_elements: int, steps: int = 1) -> float:
    """Allowed |drift| of a conservation sum over ``steps`` steps of a
    conservative kernel: rounding accumulates ~eps per element-update,
    so the bound scales with the magnitude of the sum, sqrt of the
    element count, and the step count — while a single corrupted cell
    moves the sum by O(cell value) = O(|sum| / n), far above it for
    any practically sized grid."""
    scale = abs(float(base)) + float(n_elements)
    return integrity_rtol() * scale * max(1.0, float(steps)) ** 0.5


# ---------------------------------------------------------------------
# conservation registry: which kernels conserve which fields
# ---------------------------------------------------------------------

# kernel registry name -> (fields, axes that must be periodic for the
# conservation to hold; None = any periodicity)
_CONSERVED: dict = {}


def register_conserved(kernel_name: str, fields, periodic_axes=None):
    """Declare that the registered fleet kernel ``kernel_name``
    conserves the total of ``fields`` (exactly, in real arithmetic),
    provided every axis in ``periodic_axes`` is periodic. The fleet
    layer then checks per-quantum conservation drift for those fields
    when integrity is enabled."""
    _CONSERVED[str(kernel_name)] = (tuple(fields),
                                    None if periodic_axes is None
                                    else tuple(periodic_axes))


# the built-in kernels: diffusion redistributes over a symmetric
# neighbor relation (conserves under any periodicity); upwind
# advection conserves only when the transport axis wraps
register_conserved("diffuse", ("rho",))
register_conserved("advect_x", ("rho",), periodic_axes=(0,))


def conserved_fields(kernel, periodic, fields_out) -> tuple:
    """The fields a job's kernel provably conserves under its
    periodicity — the per-quantum conservation-check set. Callable
    kernels (no registry entry) conserve nothing we can assume."""
    if callable(kernel):
        return ()
    entry = _CONSERVED.get(str(kernel))
    if entry is None:
        return ()
    fields, axes = entry
    if axes is not None and not all(bool(periodic[a]) for a in axes):
        return ()
    return tuple(n for n in fields if n in tuple(fields_out))


# ---------------------------------------------------------------------
# fingerprints: order-independent exact uint32 pairs
# ---------------------------------------------------------------------

def _row_words(arr) -> np.ndarray:
    """``[n, k]`` uint32 word view of per-cell rows: each cell's field
    bytes, zero-padded per row to a multiple of 4. Padding per ROW
    (not per column) keeps the words cell-aligned, so the same cells
    in any order produce the same word multiset — the property the
    order-independent sums need."""
    a = np.ascontiguousarray(arr)
    n = a.shape[0] if a.ndim else 1
    b = a.reshape(n, -1).view(np.uint8)
    pad = (-b.shape[1]) % 4
    if pad:
        b = np.concatenate(
            [b, np.zeros((n, pad), dtype=np.uint8)], axis=1)
    return b.view(np.uint32)


def fingerprint_rows(arr) -> tuple:
    """The ``(s1, s2)`` fingerprint of per-cell rows ``arr`` (leading
    axis = cells): wrapping-uint32 ``sum(x)`` plus a nonlinear second
    sum ``sum((lo16(x)+1) * (hi16(x)+1))`` over the word view. Exact,
    order-independent, and reproduced identically by the device-side
    program (:func:`device_fingerprint`) and the file-payload
    recompute (:func:`file_fingerprint`). The second sum is a
    half-word product rather than ``x*x`` because float bit patterns
    routinely carry 16+ trailing zeros, making plain squares collapse
    to 0 mod 2^32."""
    w = _row_words(arr)
    s1 = int(np.sum(w, dtype=np.uint32))
    lo = (w & np.uint32(0xFFFF)) + np.uint32(1)
    hi = (w >> np.uint32(16)) + np.uint32(1)
    s2 = int(np.sum(lo * hi, dtype=np.uint32))
    return s1, s2


def device_fingerprint(x, n_own: int):
    """jnp body computing the ``(s1, s2)`` pair of one field's owned
    rows ``x[:n_own]`` inside a jitted program — the fused in-program
    invariant. 32-bit element types bitcast losslessly on every
    backend; 16-bit types (bfloat16 state) bitcast to uint16 and widen
    each element to its OWN uint32 word — which equals the host
    packer's padded-row words only for one-element rows, so the fleet
    restricts 16-bit device fingerprints to scalar-shaped fields (the
    host helpers handle any dtype)."""
    import jax
    import jax.numpy as jnp

    v = x[:n_own]
    if v.dtype.itemsize == 2:
        v = jax.lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.uint32)
    elif v.dtype.itemsize != 4:
        raise TypeError(
            f"device fingerprints need a 16- or 32-bit element type, "
            f"got {v.dtype}")
    w = jax.lax.bitcast_convert_type(v, jnp.uint32)
    s1 = jnp.sum(w, dtype=jnp.uint32)
    lo = (w & jnp.uint32(0xFFFF)) + jnp.uint32(1)
    hi = (w >> jnp.uint32(16)) + jnp.uint32(1)
    s2 = jnp.sum(lo * hi, dtype=jnp.uint32)
    return jnp.stack([s1, s2])


def grid_fingerprint(grid, fields=None) -> dict:
    """``{field: (s1, s2)}`` over the grid's OWNED cell bytes — the
    same rows :func:`dccrg_tpu.checkpoint.state_digest` hashes, so two
    grids with equal owned bytes fingerprint equal. Host-side and
    dtype-agnostic; process-local on multi-process meshes (uint32 sums
    combine across ranks by wrapping addition, but the sidecar record
    is only written by single-controller saves)."""
    out = {}
    names = sorted(fields if fields is not None else grid.fields)
    for name in names:
        s1 = s2 = 0
        arr = grid.data[name]
        if isinstance(arr, np.ndarray):
            # a frozen host snapshot (background.freeze_grid): the
            # async-save writer must never touch jax, and the pulled
            # [n_dev, R, ...] array carries the same owned rows the
            # shard walk below reads — bitwise the same fingerprint
            for d in range(grid.n_dev):
                a, b = fingerprint_rows(arr[d, : int(grid.plan.n_local[d])])
                s1 = (s1 + a) & 0xFFFFFFFF
                s2 = (s2 + b) & 0xFFFFFFFF
            out[name] = (s1, s2)
            continue
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        for s in shards:
            d = s.index[0].start or 0
            n_own = int(grid.plan.n_local[d])
            a, b = fingerprint_rows(np.asarray(s.data)[0, :n_own])
            s1 = (s1 + a) & 0xFFFFFFFF
            s2 = (s2 + b) & 0xFFFFFFFF
        out[name] = (s1, s2)
    return out


def file_fingerprint(path: str, cell_data, header_size: int = 0,
                     variable=None) -> dict:
    """Recompute the ``{field: (s1, s2)}`` fingerprint from a
    checkpoint file's payload bytes — the offline half of the at-rest
    SDC audit (``python -m dccrg_tpu.resilience audit``). Only fixed
    (non-ragged) fields fingerprint; ragged fields are skipped (their
    per-cell extents make the column walk ambiguous under
    corruption)."""
    from . import checkpoint as checkpoint_mod

    raw = np.memmap(path, dtype=np.uint8, mode="r")
    try:
        meta = checkpoint_mod.parse_metadata(raw, header_size)
        fields = _normalize_fields(cell_data)
        cols = checkpoint_mod.payload_columns(
            raw, meta, fields, variable=variable)
        return {name: fingerprint_rows(col)
                for name, col in cols.items()}
    finally:
        del raw


def _normalize_fields(cell_data) -> dict:
    out = {}
    for name, spec in cell_data.items():
        if isinstance(spec, tuple):
            shape, dtype = spec
        else:
            shape, dtype = (), spec
        out[name] = (tuple(shape), np.dtype(dtype))
    return out


# ---------------------------------------------------------------------
# conservation sums: device-side collective (the solo-grid check)
# ---------------------------------------------------------------------

def conservation_sums(grid, fields) -> np.ndarray:
    """Global per-field sums over the grid's owned cells, computed
    device-side and psum-reduced across the mesh in ONE cached
    program (:func:`dccrg_tpu.comm.field_sums`, the same discipline as
    ``resilience.check_finite``): every rank pulls the identical
    replicated value, so the drift verdict agrees across ranks by
    construction. Returns ``[len(fields)]`` float64 (host)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from . import comm
    from jax import shard_map

    names = tuple(fields)
    if not names:
        return np.zeros(0, dtype=np.float64)
    key = ("integrity_sums", names,
           tuple(tuple(grid.fields[n][0]) for n in names))
    fn = grid._program_cache.get(key)
    if fn is None:
        axis, mesh = grid.axis, grid.mesh
        n_own = np.asarray(grid.plan.n_local, dtype=np.int32)

        def body(dev_row, *arrs):
            d = dev_row[0, 0]
            # mask ghost/pad rows: only rows < n_local[d] are owned
            rows = np.arange(int(grid.plan.R))
            import jax.numpy as jnp

            own = jnp.asarray(rows)[None] < jnp.asarray(n_own)[d]
            masked = []
            for a in arrs:
                v = a[0]
                m = own.reshape((v.shape[0],) + (1,) * (v.ndim - 1))
                masked.append(jnp.where(m, v, 0))
            return comm.field_sums(masked, axis)[None]

        dev_ids = np.arange(grid.n_dev, dtype=np.int32)[:, None]
        mapped = shard_map(
            body, mesh=mesh, in_specs=(P(axis),) * (1 + len(names)),
            out_specs=P(axis), check_vma=False)
        fn = jax.jit(mapped)
        grid._program_cache[key] = fn
        grid._program_cache[key + ("dev_ids",)] = dev_ids
    dev_ids = grid._program_cache[key + ("dev_ids",)]
    out = fn(dev_ids, *(grid.data[n] for n in names))
    return np.asarray(comm.pull_replicated(out),
                      dtype=np.float64).reshape(-1)[:len(names)]
