"""The distributed grid runtime.

TPU-native equivalent of the reference's ``class Dccrg``
(dccrg.hpp:151-13042), re-architected for JAX/XLA:

- **Structure is replicated host state** (the reference replicates its
  ``cell_process`` map on every rank too, dccrg.hpp:7311): the sorted
  cell list, owners, neighbor lists, and halo plans are numpy arrays
  rebuilt at structure-change events (AMR commit, load balance).
- **Data is sharded device state**: each user-declared per-cell field
  is one JAX array of shape ``[n_dev, R, ...]`` sharded over a 1-D
  device mesh; rows of a device's slice are
  ``[inner cells | outer cells | pad | ghost copies | pad | zero row]``
  (the reference's iteration-cache ordering, dccrg.hpp:11453-11767).
- **Halo exchange is one XLA collective**: the per-peer send/receive
  lists (dccrg.hpp:8729-8891) become static gather/scatter index
  tables, and ``update_copies_of_remote_neighbors()`` lowers to a
  single ``lax.all_to_all`` under ``shard_map``
  (vs per-peer MPI_Isend/Irecv, dccrg.hpp:10703-11209).
- **Stencils are gather-based**: neighbor resolution
  (dccrg.hpp:4375-4897) is precomputed into padded per-cell gather
  tables; ``apply_stencil`` hands kernels dense ``[L, S, ...]``
  neighbor blocks so XLA can fuse and vectorize — no per-cell loops.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field as dataclass_field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from . import background
from . import faults
from . import telemetry
from .geometry import CartesianGeometry, NoGeometry, StretchedCartesianGeometry
from .mapping import Mapping
from .neighbors import (
    build_neighbor_lists,
    find_neighbors_of,
    find_neighbors_to_subset,
    make_neighborhood,
    validate_neighborhood,
    verify_tiling,
)
from .partition import (
    PARTITION_METHODS,
    partition_cells,
    partition_cells_hierarchical,
)
from .topology import GridTopology
from .txn import grid_transaction
from .types import ERROR_CELL
from . import uniform as uniform_mod

logger = logging.getLogger("dccrg_tpu.grid")

# Parity with the reference's default neighborhood id (dccrg.hpp:99).
DEFAULT_NEIGHBORHOOD_ID = -0xDCC

_allocator_tuned = False
_libc = None  # set by _tune_allocator; None = opted out / unavailable


def _tune_allocator():
    """Raise glibc's mmap/trim thresholds before the first large plan
    build: big numpy temporaries otherwise go through mmap and pay a
    page fault per 4K page on every rebuild (~2x on 128^3 structure
    builds on a quiet host). Applied lazily so merely importing the
    package leaves process-global malloc behavior untouched; opt out
    entirely with DCCRG_NO_MALLOPT=1."""
    global _allocator_tuned, _libc
    if _allocator_tuned:
        return
    _allocator_tuned = True

    if os.environ.get("DCCRG_NO_MALLOPT") == "1":
        return
    try:
        import ctypes

        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-3, 1 << 30)  # M_MMAP_THRESHOLD
        libc.mallopt(-1, 1 << 30)  # M_TRIM_THRESHOLD
        _libc = libc
    except Exception:
        pass


def _trim_allocator():
    """Return freed heap to the OS after a large plan build: the raised
    M_TRIM_THRESHOLD means free() alone never trims, so long-running
    host applications embedding the library would otherwise keep the
    build's peak RSS. One explicit malloc_trim after each large rebuild
    keeps the build-speed win without the RSS cost. Must run after the
    build's temporaries are actually dead (i.e. after _build_plan
    returns), not inside it."""
    if _libc is None:
        return
    try:
        _libc.malloc_trim(0)
    except Exception:
        pass


# fixed tier for small host get/set transfers: one compiled
# gather/scatter program per field shape regardless of query-size drift
_GATHER_TIER = 4096


def bucket_capacity(n: int) -> int:
    """Round a capacity up to a quarter-power-of-two bucket (16, 20,
    24, 28, 32, 40, ...): structure changes that stay within a bucket
    keep every array shape identical, so the jitted exchange/stencil/
    step-loop programs (keyed by shape, not epoch) are reused instead
    of recompiled — the difference between an O(ms) and an O(30 s)
    AMR epoch on TPU. Waste is bounded at 25%."""
    n = int(n)
    if n <= 16:
        return 16
    step = 1 << max(max(n - 1, 1).bit_length() - 3, 0)
    return ((n + step - 1) // step) * step




def _synth_key(cf):
    """Static cache-key component for a closed-form plan (None when
    the plan has dense tables)."""
    if cf is None:
        return None
    return (cf["dims"], cf["periodic"], cf["n0"],
            tuple(map(tuple, cf["offsets"])), bool(cf.get("multi")))


def _synth_prep(synth, L, row_gidx=None):
    """(grid index, base validity) per row for closed-form mask
    synthesis: from the row index alone on single-device plans (rows
    ARE grid order), or from the per-row grid index array on
    multi-device closed-form plans (rows are [inner|outer] per device;
    ``row_gidx`` is ``device_row_ids[:L]`` for this device's shard,
    -1 on pad rows)."""
    n0_ = synth[2]
    if row_gidx is None:
        gidx = jnp.arange(L, dtype=jnp.int32)
        base_valid = (gidx < n0_) if L > n0_ else jnp.ones((L,), bool)
    else:
        base_valid = row_gidx >= 0
        gidx = jnp.maximum(row_gidx, 0)
    return gidx, base_valid


def _synth_col(synth, gidx, base_valid, j):
    """One [L] validity column of the closed-form mask (stencil slot
    ``j``) — lets slot-wise kernels avoid materializing the [L, S]
    stack."""
    (nx_, ny_, nz_), per_, _n0, offs_cells, *_ = synth
    xc = gidx % nx_
    yc = (gidx // nx_) % ny_
    zc = gidx // (nx_ * ny_)
    ox, oy, oz = offs_cells[j]
    v = base_valid
    for coord, o, nd, per in ((xc, ox, nx_, per_[0]),
                              (yc, oy, ny_, per_[1]),
                              (zc, oz, nz_, per_[2])):
        if o != 0 and not per:
            t = coord + o
            v = v & (t >= 0) & (t < nd)
    return v


def _synth_mask(synth, L, row_gidx=None):
    """Closed-form [L, S] validity mask (stack of _synth_col)."""
    gidx, base_valid = _synth_prep(synth, L, row_gidx)
    offs_cells = synth[3]
    return jnp.stack(
        [_synth_col(synth, gidx, base_valid, j)
         for j in range(len(offs_cells))], axis=1)



def _halo_send(fl, sr, delta, axis, n_dev):
    """One halo send: gather the send rows and move them — a compact
    per-peer ppermute when ``delta`` is given, the dense tiled
    all_to_all otherwise."""
    buf = fl[jnp.clip(sr, 0)]
    if delta is None:
        return jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    perm = [(p, (p + delta) % n_dev) for p in range(n_dev)]
    return jax.lax.ppermute(buf, axis, perm)


def _halo_scatter(fl, rv, payload, R):
    """Scatter a received payload into ghost rows (-1 slots drop)."""
    rr = jnp.where(rv >= 0, rv, R - 1).reshape(-1)
    return fl.at[rr].set(payload.reshape((-1,) + fl.shape[1:]), mode="drop")


def put_sharded(host_array, sharding):
    """Host -> device upload of a replicatedly-computed array onto a
    (possibly multi-process) sharding: each process serves only the
    shards it can address (``jax.make_array_from_callback``), so the
    same call works on a single controller and under
    ``jax.distributed`` SPMD — the analogue of every MPI rank uploading
    its slice of the replicated structure (dccrg.hpp:7738-7803)."""
    arr = np.asarray(host_array)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def _make_nbr_gather(use_roll, r_shifts, L, nrows, nmask, wr, ws):
    """Per-device neighbor gather for stencil bodies: a table gather,
    or S sequential rolls + a sparse fixup scatter when the table is
    affine (see _HoodPlan.roll_plan). Shared by apply_stencil and the
    fused step loop."""
    if not use_roll:
        return lambda fl: fl[nrows]

    def gather(fl):
        cols = [jnp.roll(fl[:L], -s, axis=0) for s in r_shifts]
        st = jnp.stack(cols, axis=1)  # [L, S, ...]
        rows_flat = wr.reshape(-1)
        slots_flat = jnp.repeat(
            jnp.arange(len(r_shifts), dtype=jnp.int32), wr.shape[1]
        )
        st = st.at[rows_flat, slots_flat].set(fl[ws.reshape(-1)], mode="drop")
        mexp = nmask.reshape(nmask.shape + (1,) * (st.ndim - 2))
        return jnp.where(mexp, st, jnp.zeros((), st.dtype))

    return gather


def _make_nbr_slot_gather(use_roll, r_shifts, L, nrows, wr, ws):
    """Column-``j`` neighbor gather for slot-wise stencils:
    ``gather(fl, j, mask_j) -> [L, ...]``, one stencil slot at a time,
    so the [L, S] neighbor stack (whose O(L*S) HBM residency drove the
    512^3 OOM) is never materialized as a single array — though the
    scheduler can still co-locate several slot temporaries; see
    _run_slotwise. Roll mode zeroes masked
    slots (the rolled values there are junk); table mode returns the
    raw gather like the dense table path (masked slots point at
    zeroed pad rows; kernels gate on the mask either way)."""
    if not use_roll:
        return lambda fl, j, mask_j: fl[nrows[:, j]]

    def gather(fl, j, mask_j):
        col = jnp.roll(fl[:L], -r_shifts[j], axis=0)
        col = col.at[wr[j]].set(fl[ws[j]], mode="drop")
        mexp = mask_j.reshape(mask_j.shape + (1,) * (col.ndim - 1))
        return jnp.where(mexp, col, jnp.zeros((), col.dtype))

    return gather


def _make_roll3d_gather(synth, L):
    """Single-device closed-form slot gather: reshape the flat field to
    the 3-D grid and ``jnp.roll`` — pure slices/concats, NO
    scatter/gather ops. TPU executes dynamic scatters orders of
    magnitude slower than shifts (the round-5 chip A/B at 128^3:
    1.7e8 updates/s for roll-with-fixup-scatter vs 2.5e6/s for table
    gathers, against a 7.6e10/s Pallas bound), so the flat roll plan's
    wrap fixups are replaced by exact 3-D periodic rolls — rows ARE
    grid order on single-device closed-form plans. Non-periodic wraps
    carry junk and are zeroed through the slot mask, exactly like the
    fixup path."""
    (nx, ny, nz), _per, n0, offs_cells, *_ = synth

    def gather(fl, j, mask_j):
        ox, oy, oz = offs_cells[j]
        g3 = fl[:n0].reshape((nz, ny, nx) + fl.shape[1:])
        g3 = jnp.roll(g3, shift=(-oz, -oy, -ox), axis=(0, 1, 2))
        col = g3.reshape((n0,) + fl.shape[1:])
        if L > n0:
            col = jnp.pad(col, [(0, L - n0)] + [(0, 0)] * (col.ndim - 1))
        mexp = mask_j.reshape(mask_j.shape + (1,) * (col.ndim - 1))
        return jnp.where(mexp, col, jnp.zeros((), col.dtype))

    return gather


def _plane_slabs(cf, local_ids, ghost_ids, n_inner, L):
    """Host check and tables for the slab gather (_make_slab3d_gather)
    of a multi-device closed-form plan. Engages when every device holds
    the same number ``Zs`` of whole z planes and its local and ghost
    rows, read ``nx*ny`` at a time, are whole planes in id order.

    Returns ``((Zs, z_offsets, W), fix)`` or None. For z offset
    ``z_offsets[k]`` the slot column starts as the local rows rolled by
    whole planes, which is right wherever the row planes ``[inner |
    outer]`` continue in grid order; ``fix[d, k, w] = (dst, src)``
    (first rows) copies the true neighbor plane over each of the few
    planes where they do not (slab ends, ghost planes). Pad entries
    repeat a real entry, or rewrite a plane with its own rolled value,
    so every entry is idempotent. Planes beyond a walled z end are left
    alone: the slot mask zeroes them. O(planes): inner and outer rows
    are each in id order and ghost ids are sorted, so a chunk whose
    first and last ids bound one plane is that plane."""
    (nx, ny, nz), per_z = cf["dims"], cf["periodic"][2]
    nxy = nx * ny
    n_loc = {len(ids) for ids in local_ids}
    if len(n_loc) != 1 or n_inner is None:
        return None
    Zs, rem = divmod(n_loc.pop(), nxy)
    if Zs == 0 or rem or any(int(n) % nxy for n in n_inner):
        return None

    def planes(ids):
        if len(ids) % nxy:
            return None
        first = ids[::nxy].astype(np.int64) - 1
        last = ids[nxy - 1::nxy].astype(np.int64) - 1
        if (first % nxy).any() or (last - first != nxy - 1).any():
            return None
        return first // nxy

    z_offsets = tuple(sorted({int(o[2]) for o in cf["offsets"]} - {0}))
    fixes = []  # [device][k] -> [(dst, src), ...]
    for lids, gids in zip(local_ids, ghost_ids):
        zl, zg = planes(lids), planes(gids)
        if zl is None or zg is None or not np.array_equal(
                np.sort(zl), np.arange(zl.min(), zl.min() + Zs)):
            return None
        first_row = np.full(nz, -1, np.int64)
        first_row[zg] = L + nxy * np.arange(len(zg))
        first_row[zl] = nxy * np.arange(Zs)
        per_k = []
        for oz in z_offsets:
            rolled = ((np.arange(Zs) + oz) % Zs) * nxy
            tz = zl + oz
            if per_z:
                tz %= nz
            inside = (tz >= 0) & (tz < nz)
            src = np.where(inside, first_row[np.clip(tz, 0, nz - 1)], rolled)
            if (src < 0).any():
                return None  # a neighbor plane this device does not hold
            wrong = np.flatnonzero(src != rolled)
            per_k.append([(r * nxy, src[r]) for r in wrong]
                         or [(0, rolled[0])])
        fixes.append(per_k)
    W = max(len(f) for per_k in fixes for f in per_k) if z_offsets else 1
    fix = np.zeros((len(local_ids), max(len(z_offsets), 1), W, 2), np.int32)
    for d, per_k in enumerate(fixes):
        for k, f in enumerate(per_k):
            fix[d, k] = f + [f[0]] * (W - len(f))
    return (Zs, z_offsets, W), fix


def _make_slab3d_gather(synth, L, slab, fix):
    """Multi-device closed-form slot gather for plans whose rows are
    whole z planes (_plane_slabs). A z offset rolls the local rows by
    whole planes and copies the true neighbor plane over the few planes
    where the ``[inner | outer]`` row order breaks the shift (``fix``
    [K, W, 2] first rows); x/y offsets are exact periodic ``jnp.roll``
    on the plane axes. Only whole-plane slices, concatenations and
    copies: no per-element gather or scatter, unlike the flat roll's
    fixups. Masked slots are zeroed like _make_roll3d_gather."""
    (nx, ny, _nz), _per, _n0, offs_cells, *_ = synth
    Zs, z_offsets, W = slab
    nxy = nx * ny
    n = Zs * nxy

    def gather(fl, j, mask_j):
        ox, oy, oz = offs_cells[j]
        trail = fl.shape[1:]
        col = fl[:n]
        if oz != 0:
            col = jnp.roll(col, -oz * nxy, axis=0)
            fk = fix[z_offsets.index(oz)]
            for w in range(W):
                col = jax.lax.dynamic_update_slice_in_dim(
                    col, jax.lax.dynamic_slice_in_dim(fl, fk[w, 1], nxy),
                    fk[w, 0], 0)
        if ox or oy:
            col = jnp.roll(col.reshape((Zs, ny, nx) + trail),
                           shift=(-oy, -ox), axis=(1, 2))
            col = col.reshape((n,) + trail)
        if L > n:
            col = jnp.pad(col, [(0, L - n)] + [(0, 0)] * len(trail))
        mexp = mask_j.reshape(mask_j.shape + (1,) * len(trail))
        return jnp.where(mexp, col, jnp.zeros((), col.dtype))

    return gather


def _make_slot_gather(kind, synth, L, use_roll, r_shifts, nrows, wr, ws,
                      slab, fix):
    """The slot gather of ``kind`` (Grid._slot_gather_kind), shared by
    apply_stencil and the fused step loop."""
    if kind == "roll3d":
        return _make_roll3d_gather(synth, L)
    if kind == "slab3d":
        return _make_slab3d_gather(synth, L, slab, fix)
    return _make_nbr_slot_gather(use_roll, r_shifts, L, nrows, wr, ws)


def _make_offs_col(uniform_offs, noffs, sc0):
    """Per-slot offsets closure shared by the stencil bodies and the
    dense adapter: raw (NOT premasked — kernels gate on the mask),
    ``[3]`` for uniform plans, ``[L, 3]`` when scaled (``sc0`` is the
    per-row size factor) or table-driven."""
    if uniform_offs:
        if sc0 is not None:
            return lambda j: noffs[j][None, :] * sc0[:, None]
        return lambda j: noffs[j]
    return lambda j: noffs[:, j]


def _run_slotwise(kernel, cell_fields, fields, gather, offs_col, mask_col,
                  n_slots, extra):
    """The one slot loop every slot-wise call site shares:
    init -> slot per stencil leg -> finish. ``fields`` maps name ->
    backing array, ``gather(arr, j, mask_j)`` produces slot j's
    neighbor column. Between slots the carry and the backing arrays
    thread through ``optimization_barrier``: the per-slot gathers have
    no data dependency on each other, so without the barrier XLA's
    scheduler hoists ALL slots' rolls to the front and every column is
    live at once. Peak HBM is REDUCED versus the dense [L, S] contract,
    not hard-bounded at O(cells): the 512^3 advection step program
    compiles for a v5e with 6.6 GB of temporaries, and 20 steps of it
    peaked at 5.9 GB in use on the chip (chip_smoke.py, PR 21). On an
    OOM at dispatch the resilience layer (resilience.guarded_step)
    degrades to the next gather mode instead of crashing the run."""
    carry = kernel.init(cell_fields, *extra)
    names = list(fields)
    vals = [fields[n] for n in names]
    for j in range(n_slots):
        mj = mask_col(j)
        nbr_j = {n: gather(v, j, mj) for n, v in zip(names, vals)}
        carry = kernel.slot(carry, cell_fields, nbr_j, offs_col(j), mj,
                            *extra)
        if j + 1 < n_slots:
            carry, vals_t = jax.lax.optimization_barrier(
                (carry, tuple(vals)))
            vals = list(vals_t)
    return kernel.finish(carry, cell_fields, *extra)


class SlotwiseKernel:
    """Memory-lean stencil kernel: the bulk pass feeds it one neighbor
    slot (stencil leg) at a time, avoiding the dense contract's
    O(cells * slots) neighbor stack. XLA's scheduler still co-locates
    several slot temporaries, so treat this as *reduced*, not O(cells),
    peak HBM; the 512^3 advection grid fits one v5e's 16 GB in roll
    mode (see _run_slotwise). Three callables:

    - ``init(cell_fields, *extra) -> carry``
    - ``slot(carry, cell_fields, nbr_j, offs_j, mask_j, *extra) ->
      carry`` — ``nbr_j[name]`` is ``[L, ...]`` (slot j's neighbor
      values), ``offs_j`` is ``[3]`` / ``[L, 3]`` and is NOT
      pre-masked (gate on ``mask_j``, shape ``[L]``)
    - ``finish(carry, cell_fields, *extra) -> {name: [L, ...]}``

    Instances are also plain dense kernels (``__call__`` loops the
    slots over axis 1), so the surface-sized pass over hard rows near
    refinement and the CPU path use the same object unchanged. The
    slots accumulate sequentially, so results match the dense
    contract's axis-1 reduction only to float re-association."""

    def __init__(self, init, slot, finish):
        self.init = init
        self.slot = slot
        self.finish = finish

    def __call__(self, cell_fields, nbr_fields, offs, mask, *extra):
        return _run_slotwise(
            self, cell_fields, nbr_fields,
            lambda v, j, mj: v[:, j],
            (lambda j: offs[:, j]) if offs.ndim == 3 else
            (lambda j: offs[j]),
            lambda j: mask[..., j], mask.shape[-1], extra)


def default_mesh(devices=None) -> Mesh:
    """1-D device mesh over all (or given) devices, axis name 'dev'."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), ("dev",))


@dataclass
class CellView:
    """A set of cells exposed for iteration (reference ``cells`` /
    ``inner_cells()`` etc. views, dccrg.hpp:7547-7718)."""

    ids: np.ndarray  # uint64 cell ids
    owner: np.ndarray  # device index per cell

    def __len__(self):
        return len(self.ids)

    def __iter__(self):
        return iter(self.ids)


class _HoodPlan:
    """Per-neighborhood static tables (one structure epoch).

    ``lists`` (the flat host-side neighbor-entry stream for queries)
    and the neighbors_to gather tables may be passed as zero-arg
    callables: they are built on first access. The uniform fast path
    (uniform.py) uses this so a 256^3 init never materializes the
    ~0.5G-entry stream unless a query API actually needs it.
    """

    def __init__(self, offsets, nbr_rows, nbr_offs, nbr_mask,
                 send_rows=None, recv_rows=None, n_inner=None, lists=None,
                 to_tables=None, to_rows=None, to_offs=None, to_mask=None,
                 offs_const=None, hard_rows=None, hard_nbr_rows=None,
                 hard_offs=None, hard_mask=None, scale_rows=None,
                 closed_form=None, pair_compact=None):
        self.offsets = offsets  # [K, 3] neighborhood items
        # stencil gather tables, per device, padded. May be ONE thunk
        # (returning (rows, mask)) for closed-form plans, materialized
        # only if a host introspection path asks:
        self._nbr_rows = nbr_rows  # [n_dev, L, S] int32 row (pad: zero row)
        self._nbr_offs = nbr_offs  # [n_dev, L, S, 3] int32 offsets, or thunk
        self._nbr_mask = nbr_mask  # [n_dev, L, S] bool
        # closed-form single-device uniform plans: stencils synthesize
        # the mask from the row index and roll shifts arithmetically —
        # no dense tables exist unless forced (dict with dims/periodic/
        # offsets/n0)
        self.closed_form = closed_form
        # when slot offsets are per-slot constants (uniform grids),
        # stencils synthesize noffs = mask * offs_const on device and
        # the full nbr_offs array is only built if a host query asks
        self.offs_const = offs_const  # [S, 3] int32 or None
        # hybrid plans (split tables): cells near refinement hold up to
        # ~8x more neighbor entries than the uniform bulk, so they get
        # their own compact tables and stencils run a second gather
        # over just those rows instead of padding every row to the
        # hard width
        self.hard_rows = hard_rows  # [n_dev, H] int32 (pad: L) or None
        self.hard_nbr_rows = hard_nbr_rows  # [n_dev, H, Sh] int32
        self.hard_offs = hard_offs  # [n_dev, H, Sh, 3] int32
        self.hard_mask = hard_mask  # [n_dev, H, Sh] bool
        # hybrid plans: offs_const is in CELL units; per-row cell size
        # (index units) scales it on device (far/easy rows only)
        self.scale_rows = scale_rows  # [n_dev, L] int32 or None
        # halo exchange lists: the COMPACT per-entry record
        # (uniform.build_pair_tables) is the primary store — O(ghosts)
        # memory; the dense [n_dev, n_dev, M] views are materialized
        # lazily (all_to_all fallback + host introspection only), so
        # pod-scale meshes never pay the n_dev^2 arrays on the
        # per-delta ppermute path
        self._pair_compact = pair_compact
        self._send_rows = send_rows  # [n_dev(src), n_dev(dst), M] or -1
        self._recv_rows = recv_rows  # [n_dev(dst), n_dev(src), M] or -1
        self.n_inner = n_inner  # [n_dev] rows [0, n_inner) have no remote deps
        self._lists = lists  # NeighborLists or thunk
        if to_tables is None and to_rows is not None:
            to_tables = (to_rows, to_offs, to_mask)
        self._to = to_tables  # (rows, offs, mask) or thunk
        self._roll_plan = None  # computed on demand by roll_plan()
        self._slab = None  # computed on demand by slab_plan()
        # per-epoch memo of device uploads (tables as jit ARGUMENTS:
        # programs are shape-keyed and reused across structure epochs,
        # only the table values re-upload)
        self._dev = {}
        self._pair_host = {}  # field -> predicate-filtered pair tables

    @property
    def pair_compact(self):
        return self._pair_compact

    def _dense_pairs(self):
        if self._send_rows is None:
            from . import uniform as uniform_mod

            self._send_rows, self._recv_rows = uniform_mod.dense_pair_tables(
                self._pair_compact)
        return self._send_rows, self._recv_rows

    @property
    def send_rows(self):
        return self._dense_pairs()[0]

    @property
    def recv_rows(self):
        return self._dense_pairs()[1]

    @property
    def lists(self):
        if callable(self._lists):
            self._lists = self._lists()
        return self._lists

    @property
    def nbr_offs(self):
        if callable(self._nbr_offs):
            self._nbr_offs = self._nbr_offs()
        return self._nbr_offs

    def _to_tables(self):
        if callable(self._to):
            self._to = self._to()
        return self._to

    @property
    def nbr_rows(self):
        if callable(self._nbr_rows):
            self._nbr_rows, self._nbr_mask = self._nbr_rows()
        return self._nbr_rows

    @property
    def nbr_mask(self):
        if callable(self._nbr_mask):
            self._nbr_rows, self._nbr_mask = self._nbr_mask()
        return self._nbr_mask

    def dev(self, name, host_array, sharding=None):
        """Memoized device upload of a named table (replicated when
        no sharding is given)."""
        hit = self._dev.get(name)
        if hit is None:
            hit = (jnp.asarray(host_array) if sharding is None
                   else put_sharded(host_array, sharding))
            self._dev[name] = hit
        return hit

    def roll_plan(self, L: int, cap=bucket_capacity):
        """Affine decomposition of the of-gather: if (almost) every
        masked slot entry satisfies ``row == r + shift_j``, the [L, S]
        neighbor gather lowers to S jnp.rolls (sequential HBM traffic,
        cheap on TPU where arbitrary gathers are slow) plus a sparse
        fixup scatter for the non-affine entries (wrap rows, block
        boundaries, rows near refinement). Returns
        ``(shifts [S], wrong_rows [n_dev, S, W], wrong_src [n_dev, S, W])``
        or None when the tables aren't affine enough to pay off.
        Computed once per structure epoch (cached)."""
        if getattr(self, "_roll_plan", None) is not None:
            return self._roll_plan if self._roll_plan != () else None
        rows = np.asarray(self.nbr_rows, dtype=np.int64)
        mask = np.asarray(self.nbr_mask)
        n_dev, Lr, S = rows.shape
        base = np.arange(Lr, dtype=np.int64)[None, :]
        shifts = np.zeros(S, dtype=np.int64)
        wrong_sets = []
        n_masked = n_wrong = 0
        for j in range(S):
            mj = mask[:, :, j]
            dj = rows[:, :, j] - base
            local = rows[:, :, j] < L  # rolls only cover local rows
            dm = dj[mj & local]
            if len(dm):
                vals, counts = np.unique(dm, return_counts=True)
                shifts[j] = vals[np.argmax(counts)]
            # ghost reads (row >= L) can coincidentally equal r + shift
            # but the roll never sees them: always fix them up
            wrong = mj & ((dj != shifts[j]) | ~local)
            n_masked += int(mj.sum())
            n_wrong += int(wrong.sum())
            wrong_sets.append([np.nonzero(wrong[d])[0] for d in range(n_dev)])
        if n_masked == 0 or n_wrong / n_masked > 0.25:
            self._roll_plan = ()
            return None
        W = cap(max(1, max(len(w) for per in wrong_sets for w in per)))
        wrong_rows = np.full((n_dev, S, W), L, dtype=np.int32)  # pad: dropped
        wrong_src = np.zeros((n_dev, S, W), dtype=np.int32)
        for j, per in enumerate(wrong_sets):
            for d, w in enumerate(per):
                wrong_rows[d, j, : len(w)] = w
                wrong_src[d, j, : len(w)] = rows[d, w, j]
        self._roll_plan = (shifts, wrong_rows, wrong_src)
        return self._roll_plan

    def slab_plan(self, local_ids, ghost_ids, L):
        """``((Zs, z_offsets, W), fix)`` of the slab gather for a
        multi-device closed-form plan whose rows are whole z planes, or
        None (see _plane_slabs). Computed once per structure epoch."""
        if self._slab is None:
            self._slab = _plane_slabs(self.closed_form, local_ids,
                                      ghost_ids, self.n_inner, L) or ()
        return self._slab or None

    def merged_of_tables(self, pad_row):
        """Dense [n_dev, L, S] (rows, offs, mask) merging the far and
        hard pieces of a split-table plan — the include_to fallback and
        table-introspection view. Plain plans return their own arrays.
        ``pad_row`` is the zero pad row index (plan.R - 1)."""
        if self.hard_nbr_rows is None:
            return np.asarray(self.nbr_rows), np.asarray(self.nbr_offs), np.asarray(self.nbr_mask)
        n_dev, L, k = self.nbr_rows.shape
        Sh = self.hard_nbr_rows.shape[2]
        S = max(k, Sh)
        rows = np.full((n_dev, L, S), pad_row, dtype=np.int32)
        offs = np.zeros((n_dev, L, S, 3), dtype=np.int32)
        mask = np.zeros((n_dev, L, S), dtype=bool)
        rows[:, :, :k] = self.nbr_rows
        mask[:, :, :k] = self.nbr_mask
        offs[:, :, :k] = self.nbr_mask[..., None] * np.asarray(self.offs_const)[None, None, :, :]
        if self.scale_rows is not None:
            offs[:, :, :k] *= np.asarray(self.scale_rows)[:, :, None, None]
        for d in range(n_dev):
            hr = np.asarray(self.hard_rows[d])
            real = hr < L
            # hard rows have no far entries: overwrite the full row
            rows[d, hr[real]] = pad_row
            mask[d, hr[real]] = False
            offs[d, hr[real]] = 0
            rows[d, hr[real], :Sh] = self.hard_nbr_rows[d, real]
            mask[d, hr[real], :Sh] = self.hard_mask[d, real]
            offs[d, hr[real], :Sh] = self.hard_offs[d, real]
        return rows, offs, mask

    @property
    def to_rows(self):  # [n_dev, L, T] int32 neighbors_to gather table
        return self._to_tables()[0]

    @property
    def to_offs(self):  # [n_dev, L, T, 3] int32
        return self._to_tables()[1]

    @property
    def to_mask(self):  # [n_dev, L, T] bool
        return self._to_tables()[2]


@dataclass
class _Plan:
    """Full structure epoch: row layout + per-neighborhood tables."""

    cells: np.ndarray  # sorted uint64, all cells (replicated)
    owner: np.ndarray  # int32 per cell
    n_dev: int
    L: int  # local-row capacity
    R: int  # total rows per device (L + ghost cap + 1 zero row)
    n_local: np.ndarray  # [n_dev]
    local_ids: list  # per device: uint64 ids in row order [inner|outer]
    row_of_pos: np.ndarray  # int32 [n_cells]: row on the OWNER device
    ghost_ids: list  # per device: uint64 ids in ghost-row order
    hoods: dict = dataclass_field(default_factory=dict)  # hood id -> _HoodPlan
    epoch: int = 0


class Grid:
    """Distributed cartesian cell-refinable grid on a TPU mesh.

    Mirrors the reference's fluent construction protocol
    (dccrg.hpp:8242-8357):

        grid = (Grid(cell_data={"density": jnp.float32})
                .set_initial_length((64, 64, 64))
                .set_periodic(True, True, True)
                .set_maximum_refinement_level(2)
                .set_neighborhood_length(1)
                .initialize(mesh))
    """

    def __init__(self, cell_data=None, dtype=None):
        # field spec: name -> (shape tuple, dtype). ``dtype`` is the
        # grid-wide storage override: every FLOATING field is re-typed
        # to it (bfloat16 halves the state's HBM residency and
        # exchange/checkpoint bytes; the weakly-typed flux kernels keep
        # computing in float32). float32 stays the default; integer/
        # bool fields keep their declared types either way.
        self.fields = {}
        self.state_dtype = None if dtype is None else jnp.dtype(dtype)
        for name, spec in (cell_data or {}).items():
            if isinstance(spec, tuple):
                shape, fdt = spec
            else:
                shape, fdt = (), spec
            fdt = jnp.dtype(fdt)
            if self.state_dtype is not None and jnp.issubdtype(
                    fdt, jnp.floating):
                fdt = self.state_dtype
            self.fields[name] = (tuple(shape), fdt)
        self._length = (1, 1, 1)
        self._max_ref_lvl = 0
        self._periodic = (False, False, False)
        self._hood_len = 1
        self._lb_method = "morton"
        self._geometry_kind = ("none", {})
        self.initialized = False
        # AMR request state
        self._refines = set()
        self._unrefines = set()
        self._dont_refines = set()
        self._dont_unrefines = set()
        self._removed_cells = np.empty(0, np.uint64)
        self._removed_data = {}
        self._new_cells = np.empty(0, np.uint64)
        # load balancing state
        self._staged_balance = {}
        self._pins = {}
        self._weights = {}
        self._partitioning_options = {}
        self._partitioning_levels = []  # hierarchical partitioning
        # per-field transfer predicates (receiver-dependent payloads)
        self._transfer_predicates = {}
        # capacity hysteresis memo (see _sticky_cap)
        self._cap_memo = {}
        # compiled-program cache, keyed by the STATIC shape signature
        # (L, R, flags, kernel, ...) — never invalidated by structure
        # epochs: with bucketed capacities (bucket_capacity) a rebuild
        # that lands in the same buckets reuses every compiled program
        self._program_cache = {}
        self._pending = {}
        self._txn_depth = 0  # reentrancy counter (txn.grid_transaction)
        # delta-checkpoint dirty tracking (resilience/supervise delta
        # saves): fields whose SAVED bytes may differ from the last
        # checkpoint baseline (None = everything — the conservative
        # state every wholesale load or structural rebuild resets to),
        # and the structure epoch deltas are only valid within (any
        # cell-set or partition change bumps it and forces a keyframe)
        self._ckpt_dirty = None
        self._ckpt_epoch = 0
        self._debug = os.environ.get("DCCRG_DEBUG") == "1"
        # extensible iteration-cache items (dccrg.hpp:7404-7518)
        self._cell_items = {}
        self._cell_item_values = {}
        self._neighbor_items = {}
        self._neighbor_item_values = {}

    # -- fluent pre-initialize setters (dccrg.hpp:8242-8357) ----------

    def _require_uninitialized(self):
        if self.initialized:
            raise RuntimeError("must be called before initialize()")

    def set_initial_length(self, length):
        self._require_uninitialized()
        self._length = tuple(int(v) for v in length)
        return self

    def set_maximum_refinement_level(self, lvl: int):
        """Negative means the maximum possible (dccrg.hpp:8264)."""
        self._require_uninitialized()
        self._max_ref_lvl = int(lvl)
        return self

    def set_periodic(self, x: bool, y: bool, z: bool):
        self._require_uninitialized()
        self._periodic = (bool(x), bool(y), bool(z))
        return self

    def set_neighborhood_length(self, n: int):
        self._require_uninitialized()
        if n < 0:
            raise ValueError("neighborhood length must be >= 0")
        self._hood_len = int(n)
        return self

    def set_load_balancing_method(self, method: str):
        if method not in PARTITION_METHODS:
            raise ValueError(f"unknown method {method!r}, have {PARTITION_METHODS}")
        self._lb_method = method
        return self

    def set_geometry(self, kind="cartesian", **params):
        """kind: 'none' | 'cartesian' (start, level_0_cell_length) |
        'stretched' (coordinates)."""
        self._require_uninitialized()
        if kind not in ("none", "cartesian", "stretched"):
            raise ValueError(f"unknown geometry kind {kind!r}")
        self._geometry_kind = (kind, params)
        return self

    # -- initialization (dccrg.hpp:480-562) ---------------------------

    def initialize(self, mesh: Mesh | None = None, partition: str | None = None):
        self._require_uninitialized()
        self.mesh = mesh if mesh is not None else default_mesh()
        if len(self.mesh.axis_names) != 1:
            raise ValueError("Grid needs a 1-D mesh (axis 'dev')")
        # Multi-process (jax.distributed) meshes are supported: every
        # process runs the same program over the same replicated inputs,
        # so each computes the SAME plan (all partitioners are
        # deterministic numpy) — exactly how every MPI rank in the
        # reference holds the same cell_process map
        # (dccrg.hpp:7311, 7738-7803). What changes per process is only
        # which shards the HOST paths may touch: uploads go through
        # put_sharded (each process serves its addressable shards),
        # get/set are restricted to cells on addressable devices (the
        # reference's rank-local access semantics), and checkpoint I/O
        # writes per-process slices. Collectives (ppermute halo
        # exchange, psum reductions) are mesh-shape agnostic.
        self._proc_local_dev = np.fromiter(
            (d.process_index == jax.process_index()
             for d in self.mesh.devices.flat),
            dtype=bool, count=self.mesh.devices.size,
        )
        # checkpoint-coordination identity: None = use
        # jax.process_index(); the faked test splits pin a per-pass
        # rank here (coord.process_rank, checkpoint._save_process_slice)
        self._ckpt_rank = None
        self.axis = self.mesh.axis_names[0]
        self.n_dev = self.mesh.devices.size

        self.mapping = Mapping(self._length)
        if self._max_ref_lvl < 0:
            self.mapping.set_maximum_refinement_level(
                self.mapping.get_maximum_possible_refinement_level()
            )
        elif not self.mapping.set_maximum_refinement_level(self._max_ref_lvl):
            raise ValueError(
                f"maximum refinement level {self._max_ref_lvl} not possible "
                f"for grid {self._length}"
            )
        self.topology = GridTopology(self._periodic)
        kind, params = self._geometry_kind
        if kind == "none":
            self.geometry = NoGeometry(self.mapping, self.topology)
        elif kind == "cartesian":
            self.geometry = CartesianGeometry(self.mapping, self.topology, **params)
        else:
            self.geometry = StretchedCartesianGeometry(self.mapping, self.topology, **params)

        self.neighborhoods = {DEFAULT_NEIGHBORHOOD_ID: make_neighborhood(self._hood_len)}

        # level-0 cells, partitioned (create_level_0_cells, dccrg.hpp:8089)
        n0 = self.mapping.length.total_level0_cells
        mark = telemetry.phase_timer()
        cells = np.arange(1, n0 + 1, dtype=np.uint64)
        owner = partition_cells(
            self.mapping, cells, self.n_dev, partition or self._lb_method,
            pins=self._pins or None,
        )
        mark("partition")
        self.initialized = True
        self._build_plan(cells, owner)  # marks classify and tables
        mark = telemetry.phase_timer()
        self._allocate_fields()
        mark("fields")
        if self._debug:
            from . import verify as _verify

            _verify.pin_requests_succeeded(self)
        return self

    def clone(self, cell_data=None) -> "Grid":
        """New grid with identical structure (cells, owners, neighbor
        tables, neighborhoods, pins, weights) but its own — default-
        initialized — cell data, optionally of a different schema: the
        reference's cross-Cell_Data copy constructor (dccrg.hpp:344-446).
        """
        if not self.initialized:
            raise RuntimeError("clone() requires an initialized grid")
        spec = cell_data if cell_data is not None else {
            name: (shape, dtype) for name, (shape, dtype) in self.fields.items()
        }
        other = Grid(cell_data=spec)
        other._length = self._length
        other._max_ref_lvl = self._max_ref_lvl
        other._periodic = self._periodic
        other._hood_len = self._hood_len
        other._lb_method = self._lb_method
        other._geometry_kind = self._geometry_kind
        other._pins = dict(self._pins)
        other._weights = dict(self._weights)
        other._partitioning_options = dict(self._partitioning_options)
        other._partitioning_levels = [dict(lv) for lv in self._partitioning_levels]
        other.mesh = self.mesh
        other.axis = self.axis
        other.n_dev = self.n_dev
        other._proc_local_dev = self._proc_local_dev.copy()
        other._ckpt_rank = self._ckpt_rank
        other.mapping = Mapping(
            tuple(int(v) for v in self.mapping.length.get()),
            self.mapping.max_refinement_level,
        )
        other.topology = GridTopology(self._periodic)
        kind, params = self._geometry_kind
        if kind == "none":
            other.geometry = NoGeometry(other.mapping, other.topology)
        elif kind == "cartesian":
            other.geometry = CartesianGeometry(other.mapping, other.topology, **params)
        else:
            other.geometry = StretchedCartesianGeometry(other.mapping, other.topology, **params)
        other.neighborhoods = {hid: offs.copy() for hid, offs in self.neighborhoods.items()}
        other.initialized = True
        other._build_plan(self.plan.cells.copy(), self.plan.owner.copy())
        other._allocate_fields()
        return other

    def neighbor_devices(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> np.ndarray:
        """[n_dev, n_dev] bool: entry [q, p] true when device q receives
        halo data from device p under the neighborhood — the peer sets
        the reference's Some_Reduce reduces over (its process-boundary
        peers, dccrg_mpi_support.hpp:285-380)."""
        c = self.plan.hoods[neighborhood_id].pair_compact
        out = np.zeros((self.n_dev, self.n_dev), dtype=bool)
        out[c["q"], c["p"]] = True
        return out

    # capacities whose arrays are small but whose need varies a lot
    # epoch-to-epoch (hard-shell sizes, pair lists, fixup widths):
    # give them a 2x band so shapes virtually never change
    _WIDE_CAPS = ("G", "M", "S", "S_hard", "Hmax", "T_hard", "rollW", "removed",
                  "scatter")
    # index buffers of per-epoch data movement: never shrink
    _GROW_ONLY_CAPS = ("removed", "scatter")

    def _sticky_cap(self, name, needed: int) -> int:
        """Capacity with hysteresis: grow in buckets with headroom,
        keep the previous capacity while the need still fits, shrink
        only once the need drops well below it — epoch-to-epoch
        structural churn then keeps array shapes identical, so the
        shape-keyed compiled programs are reused instead of
        recompiled."""
        needed = int(needed)
        base = name[0] if isinstance(name, tuple) else name
        wide = base in self._WIDE_CAPS
        prev = self._cap_memo.get(name)
        if prev is not None and needed <= prev and base in self._GROW_ONLY_CAPS:
            return prev
        if prev is not None and prev // (4 if wide else 2) <= needed <= prev:
            return prev
        if prev is None:
            # first build: exact bucket (a static grid should not pay
            # growth headroom it will never use)
            cap = bucket_capacity(needed)
        else:
            # headroom absorbs drift (a refined region that wanders
            # grows some devices' loads a little every epoch); the big
            # L arrays get 25%, the small high-variance ones 2x
            cap = bucket_capacity(needed * 2 if wide else needed + needed // 4)
        self._cap_memo[name] = cap
        return cap

    # -- structure plan building --------------------------------------

    def _build_plan(self, cells: np.ndarray, owner: np.ndarray,
                    changed_hint=None):
        """Rebuild all derived structure: the equivalent of the
        reference's initialize_neighbors + update_remote_neighbor_info +
        recalculate_neighbor_update_send_receive_lists +
        update_cell_pointers pipeline (dccrg.hpp:8371-8420).
        ``changed_hint`` is ``(prev_cells, changed_ids)`` from a
        structure mutation that knows its own dirty set (see
        hybrid.build_hybrid_plan); only the hybrid path consumes it."""
        self._finish_plan(self._construct_plan(cells, owner, changed_hint))

    def _construct_plan(self, cells: np.ndarray, owner: np.ndarray,
                        changed_hint=None):
        """Build a complete structure plan for ``(cells, owner)``
        WITHOUT installing it — the pure half of a rebuild, safe to run
        on a background worker thread while the step loop keeps
        dispatching against the live plan (DCCRG_BG_RECOMMIT; see
        dccrg_tpu.background.PlanBuildWorker). Reads only structural
        inputs and the build caches (capacity memo, hybrid stream-reuse
        cache, plan arena — never the field data), and builds are
        serialized per grid, so the result is bitwise identical to the
        synchronous path's."""
        plan = self._build_plan_impl(cells, owner, changed_hint)
        # the builder's large temporaries are dead only once the impl
        # frame is gone; trim here so malloc_trim can actually return
        # the build's peak to the OS (the arena-held tables stay
        # resident — that is the point)
        if len(cells) > 1 << 20:
            _trim_allocator()
        return plan

    def _build_plan_impl(self, cells: np.ndarray, owner: np.ndarray,
                         changed_hint=None):
        _tune_allocator()
        n_dev = self.n_dev
        if len(cells) > 1 and not np.all(cells[:-1] < cells[1:]):
            order = np.argsort(cells, kind="stable")
            cells = cells[order]
            owner = np.asarray(owner, dtype=np.int32)[order]
        else:  # already sorted (every initialize(); most rebuilds)
            owner = np.asarray(owner, dtype=np.int32)

        # all-level-0 grids take the closed-form fast path (uniform.py):
        # identical tables, no entry stream, bounded temporaries. Both
        # its native and numpy builders index cells with int32, so the
        # fast path is gated at 2^31 cells (the generic path below and
        # the reference's uint64 ids have no such bound).
        n0 = self.mapping.length.total_level0_cells
        if uniform_mod.is_uniform(cells, n0) and n0 < 2**31 - 2:
            return self._build_plan_uniform(cells, owner)

        # refined grids take the hybrid path (hybrid.py): closed-form
        # tables away from refinement, generic engine for the hard
        # subset near it — O(refinement surface), not O(grid)
        if n0 < 2**31 - 2 and os.environ.get("DCCRG_FORCE_GENERIC") != "1":
            return self._build_plan_hybrid(cells, owner, changed_hint)

        # per-hood neighbor lists (host), with neighbor positions in the
        # sorted cell array resolved once per hood (reused everywhere)
        hood_lists = {
            hid: build_neighbor_lists(self.mapping, self.topology, cells, offs)
            for hid, offs in self.neighborhoods.items()
        }
        hood_gidx = {
            hid: (np.searchsorted(cells, hl.of_neighbor),
                  np.searchsorted(cells, hl.to_neighbor))
            for hid, hl in hood_lists.items()
        }

        # remote-dependency classification against the union of hoods
        # (the reference tracks boundary cells per neighborhood;
        # rows are ordered by the default hood's classification)
        nl = hood_lists[DEFAULT_NEIGHBORHOOD_ID]
        nbr_idx, to_nbr_idx = hood_gidx[DEFAULT_NEIGHBORHOOD_ID]
        src_owner = owner[nl.of_source]
        nbr_owner = owner[nbr_idx]
        remote_edge = src_owner != nbr_owner
        # outer: local cell with a remote neighbor in of- or to-lists
        outer_flag = np.zeros(len(cells), dtype=bool)
        np.add.at(outer_flag, nl.of_source[remote_edge], True)
        remote_to = owner[nl.to_source] != owner[to_nbr_idx]
        np.add.at(outer_flag, nl.to_source[remote_to], True)

        local_ids, ghost_ids, n_inner_arr = [], [], np.zeros(n_dev, np.int64)
        for d in range(n_dev):
            mine = owner == d
            inner = cells[mine & ~outer_flag]
            outer = cells[mine & outer_flag]
            local_ids.append(np.concatenate([inner, outer]))
            n_inner_arr[d] = len(inner)
            # ghosts: remote cells this device reads (neighbors_of of its
            # cells) or must send to (covered by send lists); ghost rows
            # only store copies we receive -> remote neighbors_of plus
            # remote neighbors_to sources we *read* in to-gathers.
            gh = []
            for hid, hl in hood_lists.items():
                of_g, to_g = hood_gidx[hid]
                m = (owner[hl.of_source] == d) & (owner[of_g] != d)
                gh.append(hl.of_neighbor[m])
                m2 = (owner[hl.to_source] == d) & (owner[to_g] != d)
                gh.append(hl.to_neighbor[m2])
            ghost_ids.append(np.unique(np.concatenate(gh)) if gh else
                             np.empty(0, np.uint64))

        n_local = np.array([len(x) for x in local_ids], dtype=np.int64)
        n_ghost = np.array([len(x) for x in ghost_ids], dtype=np.int64)
        L = self._sticky_cap("L", max(1, int(n_local.max())))
        G = int(n_ghost.max()) if n_dev > 1 else 0
        G = self._sticky_cap("G", G) if G else 0
        R = L + G + 1  # final row = permanent zero pad

        # row lookups: row_by_gidx[d][global cell index] -> row on
        # device d (or -1), used by the table builders; row_of_pos is
        # the owner-device row per cell (host get/set lookups).
        row_by_gidx = np.full((n_dev, len(cells)), -1, dtype=np.int32)
        row_of_pos = np.full(len(cells), -1, dtype=np.int32)
        for d in range(n_dev):
            lpos = np.searchsorted(cells, local_ids[d])
            lrows = np.arange(len(local_ids[d]), dtype=np.int32)
            row_by_gidx[d, lpos] = lrows
            row_of_pos[lpos] = lrows
            if len(ghost_ids[d]):
                row_by_gidx[d, np.searchsorted(cells, ghost_ids[d])] = L + np.arange(
                    len(ghost_ids[d]), dtype=np.int32
                )

        plan = _Plan(
            cells=cells,
            owner=owner,
            n_dev=n_dev,
            L=L,
            R=R,
            n_local=n_local,
            local_ids=local_ids,
            row_of_pos=row_of_pos,
            ghost_ids=ghost_ids,
        )

        for hid, offs in self.neighborhoods.items():
            plan.hoods[hid] = self._build_hood_plan(
                plan, hood_lists[hid], offs,
                n_inner_arr if hid == DEFAULT_NEIGHBORHOOD_ID else None,
                hood_gidx[hid], row_by_gidx, hid,
            )
        return plan

    def _build_plan_uniform(self, cells: np.ndarray, owner: np.ndarray):
        """Closed-form plan construction for all-level-0 grids
        (uniform.py): same layout and tables as the generic path, no
        neighbor-entry stream, bounded temporaries."""
        layout, hood_data = uniform_mod.build_uniform_plan(
            self.mapping, self.topology, self.neighborhoods, cells, owner,
            self.n_dev, cap=self._sticky_cap,
        )
        plan = _Plan(
            cells=cells,
            owner=owner,
            n_dev=self.n_dev,
            L=layout["L"],
            R=layout["R"],
            n_local=layout["n_local"],
            local_ids=layout["local_ids"],
            row_of_pos=layout["row_of_pos"],
            ghost_ids=layout["ghost_ids"],
        )
        mapping, topology = self.mapping, self.topology
        for hid, offs in self.neighborhoods.items():
            hd = hood_data[hid]

            def lists_thunk(offs=offs):
                return build_neighbor_lists(mapping, topology, cells, offs)

            closed = "closed_form" in hd
            hood = _HoodPlan(
                offsets=offs,
                nbr_rows=hd["tables_thunk"] if closed else hd["nbr_rows"],
                nbr_offs=hd["nbr_offs"],
                nbr_mask=hd["tables_thunk"] if closed else hd["nbr_mask"],
                offs_const=hd["offs_const"],
                closed_form=hd.get("closed_form"),
                to_tables=hd["to_thunk"],
                pair_compact=hd["pair_compact"],
                n_inner=(layout["n_inner"]
                         if hid == DEFAULT_NEIGHBORHOOD_ID else None),
                lists=lists_thunk,
            )
            if closed:
                # roll shifts + wrap fixups were computed arithmetically
                hood._roll_plan = hd["roll_plan"]
            plan.hoods[hid] = hood
        return plan

    def _build_plan_hybrid(self, cells: np.ndarray, owner: np.ndarray,
                           changed_hint=None):
        """Plan construction for refined grids (hybrid.py): closed-form
        lattice tables for level-0 cells away from refinement, generic
        engine only for the hard subset near it. Same layout and
        semantics as the generic builder."""
        from . import hybrid as hybrid_mod

        if getattr(self, "_hybrid_reuse", None) is None:
            # epoch-to-epoch cache of the hard-shell neighbor streams
            # (see hybrid.py): only the dirty region reruns the engine
            self._hybrid_reuse = {}
        if getattr(self, "_plan_arena", None) is None:
            # pooled backing stores of the big plan tables, reused
            # across structure epochs so a recommit never faults in
            # multi-GB fresh pages (see hybrid.PlanArena)
            self._plan_arena = hybrid_mod.PlanArena()
        arena = self._plan_arena
        # the live plan and the active transaction's rollback snapshot
        # keep their buffers; everything older is recycled — an aborted
        # build can never have scribbled on a plan a rollback restores
        arena.begin(protect=(getattr(self, "plan", None),
                             getattr(self, "_txn_plan", None)))
        layout, hood_data = hybrid_mod.build_hybrid_plan(
            self.mapping, self.topology, self.neighborhoods, cells, owner,
            self.n_dev, cap=self._sticky_cap, reuse=self._hybrid_reuse,
            arena=arena, changed_hint=changed_hint,
        )
        plan = _Plan(
            cells=cells,
            owner=owner,
            n_dev=self.n_dev,
            L=layout["L"],
            R=layout["R"],
            n_local=layout["n_local"],
            local_ids=layout["local_ids"],
            row_of_pos=layout["row_of_pos"],
            ghost_ids=layout["ghost_ids"],
        )
        arena.bind(plan)
        mapping, topology = self.mapping, self.topology
        for hid, offs in self.neighborhoods.items():
            hd = hood_data[hid]

            def lists_thunk(offs=offs):
                return build_neighbor_lists(mapping, topology, cells, offs)

            plan.hoods[hid] = _HoodPlan(
                offsets=offs,
                nbr_rows=hd["nbr_rows"],
                nbr_offs=hd["nbr_offs"],
                nbr_mask=hd["nbr_mask"],
                offs_const=hd["offs_const"],
                hard_rows=hd["hard_rows"],
                hard_nbr_rows=hd["hard_nbr_rows"],
                hard_offs=hd["hard_offs"],
                hard_mask=hd["hard_mask"],
                scale_rows=layout["scale_rows"],
                to_tables=hd["to_thunk"],
                pair_compact=hd["pair_compact"],
                n_inner=(layout["n_inner"]
                         if hid == DEFAULT_NEIGHBORHOOD_ID else None),
                lists=lists_thunk,
            )
        return plan

    def _finish_plan(self, plan: _Plan):
        plan.epoch = getattr(self, "plan", None).epoch + 1 if getattr(self, "plan", None) else 0
        self.plan = plan
        # any rebuild invalidates a gather mode forced by the OOM
        # fallback (resilience._apply_mode re-pins and re-marks it)
        self._plan_gather_mode = None
        # compiled programs are shape-keyed and survive the epoch; the
        # per-epoch device tables live on the (replaced) hood plans

        self._update_data_items()

        # continuous self-checking, like the reference's DEBUG builds
        # (dccrg.hpp:12454-13036). User data is still mid-migration at
        # this point; _restructure/_allocate_fields check it after.
        # Inside a transaction the post-commit verify_all covers these
        # same checks (and more) on the final state — skip the
        # mid-commit pass rather than paying the O(grid) neighbor
        # recompute twice per mutation.
        if self._debug and not getattr(self, "_txn_depth", 0):
            from . import verify as _verify

            _verify.is_consistent(self)
            _verify.verify_neighbors(self)
            _verify.verify_remote_neighbor_info(self)
            # pin placement is checked where pins are APPLIED
            # (initialize / balance_load / load_cells): a pin made
            # between balance_loads only takes effect at the next one
            # (dccrg.hpp:5913-6139)

    def _build_hood_plan(self, plan: _Plan, nl, offsets, n_inner_arr, gidx,
                         row_by_gidx, hid):
        n_dev, L, R = plan.n_dev, plan.L, plan.R
        cells, owner = plan.cells, plan.owner

        def build_table(src_gidx, nbr_gidx, offs_arr):
            """Pad ragged per-cell entries into [n_dev, L, S] tables —
            fully vectorized (the entry stream is already ordered by
            source cell, so a stable sort by (device, source row) keeps
            each cell's neighborhood-item order)."""
            entry_dev = owner[src_gidx].astype(np.int64)
            src_rows = row_by_gidx[entry_dev, src_gidx].astype(np.int64)
            nrows = row_by_gidx[entry_dev, nbr_gidx]
            # every neighbor must have a row (local or ghost) on the
            # source's device — -1 would silently alias the pad row
            if len(nrows) and int(nrows.min()) < 0:
                raise AssertionError(
                    "ghost coverage bug: neighbor without a row on its "
                    "reader's device"
                )
            key = entry_dev * L + src_rows
            order = np.argsort(key, kind="stable")
            ksort = key[order]
            n = len(ksort)
            if n == 0:
                S = 1
                return (
                    np.full((n_dev, L, S), R - 1, dtype=np.int32),
                    np.zeros((n_dev, L, S, 3), dtype=np.int32),
                    np.zeros((n_dev, L, S), dtype=bool),
                )
            # slot = rank of the entry within its (device, row) group
            change = np.empty(n, dtype=bool)
            change[0] = True
            change[1:] = ksort[1:] != ksort[:-1]
            group_start = np.maximum.accumulate(
                np.where(change, np.arange(n), 0)
            )
            slot = np.arange(n) - group_start
            S = self._sticky_cap(("S", hid), max(1, int(slot.max()) + 1))
            rows = np.full((n_dev * L * S,), R - 1, dtype=np.int32)
            offs = np.zeros((n_dev * L * S, 3), dtype=np.int32)
            mask = np.zeros((n_dev * L * S,), dtype=bool)
            flat = ksort * S + slot
            rows[flat] = nrows[order]
            offs[flat] = offs_arr[order]
            mask[flat] = True
            return (
                rows.reshape(n_dev, L, S),
                offs.reshape(n_dev, L, S, 3),
                mask.reshape(n_dev, L, S),
            )

        nbr_rows, nbr_offs, nbr_mask = build_table(
            nl.of_source, gidx[0], nl.of_offset
        )

        def to_tables():
            return build_table(nl.to_source, gidx[1], nl.to_offset)

        # --- halo send/receive lists (dccrg.hpp:8729-8891) ---
        # device q receives every remote neighbor it reads; sender p is
        # that cell's owner. Lists sorted by cell id. Keys are cell
        # POSITIONS (ids are sorted, so position order == id order);
        # the shared lexsort-grouping construction lives in uniform.py.
        ghost_pos = [np.searchsorted(cells, plan.ghost_ids[q])
                     for q in range(n_dev)]
        pair_compact = uniform_mod.build_pair_tables(
            ghost_pos, n_dev,
            lambda keys: owner[keys],
            lambda p_s, keys: row_by_gidx[p_s, keys],
            lambda q_s, keys, gpos: row_by_gidx[q_s, keys],
            lambda needed: self._sticky_cap(("M", hid), needed),
        )

        return _HoodPlan(
            offsets=offsets,
            nbr_rows=nbr_rows,
            nbr_offs=nbr_offs,
            nbr_mask=nbr_mask,
            to_tables=to_tables,
            pair_compact=pair_compact,
            n_inner=(n_inner_arr if n_inner_arr is not None else None),
            lists=nl,
        )

    # -- field storage -------------------------------------------------

    def _sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    @property
    def _multiproc(self) -> bool:
        """True when the mesh spans processes this controller cannot
        address (jax.distributed SPMD, or a test faking it)."""
        return not bool(self._proc_local_dev.all())

    def _require_local(self, dev, what):
        """Multi-process host access is rank-local, as in the
        reference: a process touches only cells on its own devices
        (dccrg.hpp operator[] is valid for local cells)."""
        if self._multiproc and not self._proc_local_dev[dev].all():
            raise KeyError(
                f"{what}: cell(s) live on devices owned by another "
                "process; host access is process-local on multi-process "
                "meshes (like the reference's rank-local operator[])"
            )

    def _shard_read(self, field, dev, rows):
        """Host read via per-device addressable shards — no collective,
        valid under multi-process for process-local cells. Rows are
        sliced ON the device shard before the host copy, so a few-cell
        read transfers only those rows, not the whole shard."""
        arr = self.data[field]
        by_dev = {}
        for s in arr.addressable_shards:
            by_dev[s.index[0].start] = s.data
        out = np.empty((len(dev),) + arr.shape[2:], dtype=arr.dtype)
        for d in np.unique(dev):
            m = dev == d
            out[m] = np.asarray(by_dev[int(d)][0, rows[m]])
        return out

    def _allocate_fields(self):
        self.data = {}
        sh = self._sharding()
        for name, (shape, dtype) in self.fields.items():
            full = (self.n_dev, self.plan.R) + shape
            # jit-produced zeros (not a host transfer): valid on
            # multi-process meshes where device_put of host zeros isn't
            key = ("zeros", full, str(dtype))
            fn = self._program_cache.get(key)
            if fn is None:
                fn = jax.jit(partial(jnp.zeros, full, dtype),
                             out_shardings=sh)
                self._program_cache[key] = fn
            self.data[name] = fn()
        self._mark_ckpt_dirty()

    def _mark_ckpt_dirty(self, fields=None) -> None:
        """Record fields whose saved bytes may have changed since the
        last delta-checkpoint baseline (consumed by the incremental
        save path in :mod:`dccrg_tpu.supervise` / resilience).
        ``None`` marks everything dirty. Ghost-only writes (halo
        exchanges) never call this: checkpoints serialize owned rows
        only, so ghost refreshes cannot change the saved bytes."""
        if fields is None:
            self._ckpt_dirty = None
        elif getattr(self, "_ckpt_dirty", None) is not None:
            self._ckpt_dirty.update(fields)

    def device_row_ids(self) -> "jnp.ndarray":
        """Sharded ``[n_dev, R] int32`` array of ``cell id - 1`` per
        row (``-1`` on pad rows) — the device-side mirror of
        ``plan.local_ids``/``ghost_ids``, for initializing fields ON
        device instead of staging host arrays (on uniform grids the
        geometry center is affine in this index, so e.g. a 512^3 field
        init needs no host f64 centers at all; the reference
        initializes in one pass over already-resident memory,
        tests/advection/initialize.hpp:36-80). Cached per structure
        epoch. On a complete single-device level-0 grid the array is
        synthesized from an iota without any host staging."""
        plan = self.plan
        cached = getattr(plan, "_row_ids_dev", None)
        if cached is not None:
            return cached
        n0 = self.mapping.length.total_level0_cells
        if (self.n_dev == 1 and len(plan.cells) == n0
                and int(plan.cells[-1]) == n0):
            # complete level-0 grid, one device: rows are id order
            idx = jnp.arange(plan.R, dtype=jnp.int32)[None, :]
            arr = jnp.where(idx < n0, idx, jnp.int32(-1))
            arr = jax.device_put(arr, self._sharding())
        else:
            # int64 rows when ids exceed int32 (deeply refined AMR
            # grids): the closed-form multi stencil path can never get
            # here (build_uniform_plan is gated at < 2^31 cells), so
            # only field-init consumers see the wide dtype. Without
            # x64, jnp.asarray would silently WRAP int64 to int32 —
            # keep the loud failure in that configuration.
            wide = bool(len(plan.cells)
                        and int(plan.cells[-1]) > np.iinfo(np.int32).max)
            if wide and not jax.config.jax_enable_x64:
                raise ValueError(
                    "cell ids exceed int32 and JAX x64 is disabled; "
                    "enable jax_enable_x64 for device_row_ids() on "
                    "deeply refined grids, or initialize via set_many"
                )
            host = np.full((self.n_dev, plan.R), -1,
                           dtype=np.int64 if wide else np.int32)
            for d in range(self.n_dev):
                nl = int(plan.n_local[d])
                host[d, :nl] = plan.local_ids[d].astype(np.int64) - 1
                ng = len(plan.ghost_ids[d])
                if ng:  # ghost rows sit at [L, L+ng) (see hybrid.py)
                    host[d, plan.L : plan.L + ng] = (
                        plan.ghost_ids[d].astype(np.int64) - 1
                    )
            arr = put_sharded(host, self._sharding())
        plan._row_ids_dev = arr
        return arr

    def local_row_mask(self) -> "jnp.ndarray":
        """Sharded ``[n_dev, R] float32`` mask: 1 on local rows, 0 on
        ghost and pad rows — the device-side reduction mask (masked
        sums / dots over owned cells only). Built on device from an
        iota and cached per structure epoch (on the plan object, so a
        same-bucket repartition that keeps array shapes still
        invalidates it)."""
        plan = self.plan
        cached = getattr(plan, "_local_mask_dev", None)
        if cached is not None:
            return cached
        fn = getattr(self, "_local_mask_fn", None)
        if fn is None:
            @partial(jax.jit, static_argnames=("shape",),
                     out_shardings=self._sharding())
            def fn(nl, shape):
                rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                return (rows < nl).astype(jnp.float32)

            self._local_mask_fn = fn
        nl = jnp.asarray(np.asarray(plan.n_local)[:, None].astype(np.int32))
        arr = fn(nl, shape=(self.n_dev, plan.R))
        plan._local_mask_dev = arr
        return arr

    def _host_rows(self, ids):
        """(device, row) for each cell id (host lookup)."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.uint64))
        if ids is self.plan.cells or (
            len(ids) == len(self.plan.cells) and ids[0] == self.plan.cells[0]
            and ids[-1] == self.plan.cells[-1]
            and np.array_equal(ids, self.plan.cells)
        ):
            # whole-grid access (init paths): skip the binary search
            return self.plan.owner.copy(), self.plan.row_of_pos.astype(np.int64)
        pos = np.searchsorted(self.plan.cells, ids)
        if np.any(pos >= len(self.plan.cells)) or np.any(self.plan.cells[np.minimum(pos, len(self.plan.cells)-1)] != ids):
            if getattr(self, "_bg_build", None) is not None:
                # a deferred recommit (DCCRG_BG_RECOMMIT) may hold the
                # epoch these ids belong to — the adapt-then-project
                # pattern reads/writes new children right after
                # stop_refining. A data access that NEEDS the new
                # epoch IS a boundary: install (blocking) and retry,
                # so apps stay oblivious while accesses the live epoch
                # can serve keep costing nothing.
                self.bg_install(wait=True)
                return self._host_rows(ids)
            raise KeyError("unknown cell id(s)")
        dev = self.plan.owner[pos]
        rows = self.plan.row_of_pos[pos].astype(np.int64)
        return dev, rows

    def get(self, field: str, ids) -> np.ndarray:
        """Host read of per-cell data (reference operator[] access).
        Small queries gather ON device and pull only the requested
        rows (a full 512^3 field is half a GB; a few cells should not
        cost a whole-array transfer); large/whole-grid reads pull the
        array once."""
        scalar = np.isscalar(ids) or np.asarray(ids).ndim == 0
        dev, rows = self._host_rows(ids)
        if self._multiproc:
            # rank-local access, via addressable shards (no collective:
            # other processes may be get()ing different cells)
            self._require_local(dev, "get")
            out = self._shard_read(field, dev, rows)
        elif (0 < len(rows) <= _GATHER_TIER
                and len(rows) < len(self.plan.cells) // 4):
            out = self._device_gather(field, dev, rows)
        else:
            host = np.asarray(self.data[field])
            out = host[dev, rows]
        return out[0] if scalar else out

    def _device_gather(self, name, dev, rows, cap=None):
        """Compact device-side gather of rows ``(dev, rows)`` of field
        ``name``: indices pad to a fixed tier (pad reads hit the zero
        pad row), every device extracts its own rows under shard_map,
        a psum merges them, and only [cap] rows cross to the host.
        One compiled program per (shape, dtype, R)."""
        shape, dtype = self.fields[name]
        n = len(rows)
        if cap is None:
            cap = _GATHER_TIER if n <= _GATHER_TIER else bucket_capacity(n)
        R = self.plan.R
        dev_p = np.zeros(cap, dtype=np.int32)
        row_p = np.full(cap, R - 1, dtype=np.int32)
        dev_p[:n] = dev
        row_p[:n] = rows
        key = ("devgather", shape, str(dtype), cap, R)
        fn = self._program_cache.get(key)
        if fn is None:
            mesh, axis = self.mesh, self.axis

            def body(arr, dv, rw):
                mine = dv == jax.lax.axis_index(axis)
                r = jnp.where(mine, rw, R - 1)  # zero pad row
                vals = arr[0, r]
                mexp = mine.reshape(mine.shape + (1,) * len(shape))
                vals = jnp.where(mexp, vals, jnp.zeros((), arr.dtype))
                return jax.lax.psum(vals, axis)

            fn = jax.jit(_shard_map(
                body, mesh=mesh,
                in_specs=(P(self.axis), P(), P()),
                out_specs=P(),
            ))
            self._program_cache[key] = fn
        from . import comm

        # the psum replicates the result on every device; pull through
        # comm so real multi-process meshes (not fully addressable
        # from one controller) read their local copy
        out = comm.pull_replicated(fn(self.data[name], jnp.asarray(dev_p),
                                      jnp.asarray(row_p)))
        # psum promotes bool to int; keep the field dtype for both paths
        return out[:n].astype(dtype, copy=False)

    def set(self, field: str, ids, values) -> None:
        """Host write of per-cell data (init / tests / boundary setup)."""
        self.set_many(ids, {field: values})

    def set_many(self, ids, values_by_field, preserve_ghosts=True) -> None:
        """Host write of several fields for the same cell set in one
        pass (the row resolution happens once). With
        ``preserve_ghosts=False`` and ``ids`` covering every cell, the
        old device arrays are not read back at all — ghost rows read
        zero until the next halo exchange refreshes them (the pattern
        of per-epoch static-field initialization)."""
        self._mark_ckpt_dirty(values_by_field)
        dev, rows = self._host_rows(ids)
        fresh = (not preserve_ghosts
                 and len(np.atleast_1d(np.asarray(ids))) == len(self.plan.cells))
        # single-device full-cover writes: with no ghosts there is no
        # inner/outer reorder, so rows are the identity and the scatter
        # is a contiguous copy
        identity = fresh and self.n_dev == 1 and len(rows) == len(self.plan.cells)
        # partial writes scatter ON DEVICE: only the written rows cross
        # the host boundary, instead of a full array pull + re-upload
        # per field (the staged-balance landing path and every host
        # set() ride this). On multi-process meshes every non-full
        # write rides this tier: the scatter has no collective and each
        # device applies only its own process's writes (rank-local set,
        # like the reference's operator[] assignment)
        # a TRUE cover (every cell exactly once) — a same-length list
        # with duplicates must not take the zero-filled merge below, or
        # the missed cell's data would be silently zeroed. The sort
        # only runs in the rare multi-process full-length case.
        full_cover = (
            self._multiproc and not fresh
            and len(np.atleast_1d(np.asarray(ids))) == len(self.plan.cells)
            and np.array_equal(
                np.sort(np.atleast_1d(np.asarray(ids, dtype=np.uint64))),
                self.plan.cells)
        )
        if full_cover:
            # replicated full-cover write with ghost preservation:
            # upload the new values (put_sharded serves local shards),
            # then merge ON DEVICE so old ghost rows survive — no
            # foreign-shard host read needed
            mask = self.local_row_mask() > 0
            sh = self._sharding()
            for name, values in values_by_field.items():
                shape, dtype = self.fields[name]
                host = np.zeros((self.n_dev, self.plan.R) + shape,
                                dtype=dtype)
                host[dev, rows] = values
                new = put_sharded(host, sh)
                key = ("covermerge", shape, str(dtype))
                fn = self._program_cache.get(key)
                if fn is None:
                    def _merge(old, nw, m, _nd=len(shape)):
                        mx = m.reshape(m.shape + (1,) * _nd)
                        return jnp.where(mx, nw, old)
                    fn = jax.jit(_merge, out_shardings=sh)
                    self._program_cache[key] = fn
                self.data[name] = fn(self.data[name], new, mask)
            return
        partial = ((not fresh) and len(rows) < len(self.plan.cells)
                   ) or (self._multiproc and not fresh)
        if self._multiproc and not fresh:
            self._require_local(dev, "set")
        for name, values in values_by_field.items():
            shape, dtype = self.fields[name]
            if fresh:
                # full-cover init: values are replicated across
                # processes (every process passes the whole grid's
                # values), so each process uploads its own shards
                host = np.zeros((self.n_dev, self.plan.R) + shape, dtype=dtype)
                if identity:
                    host[0, : len(rows)] = np.asarray(values, dtype=dtype)
                    self.data[name] = put_sharded(host, self._sharding())
                    continue
            elif partial:
                self.data[name] = self._device_scatter(
                    name, dev, rows, np.asarray(values, dtype=dtype))
                continue
            else:
                host = np.asarray(self.data[name]).copy()
            host[dev, rows] = values
            self.data[name] = put_sharded(host, self._sharding())

    def _device_scatter(self, name, dev, rows, values):
        """Masked per-device scatter of ``values`` into rows
        ``(dev, rows)`` of field ``name``: indices and values are
        padded to a bucketed capacity (pad writes land as zeros on the
        permanent zero pad row), broadcast to every device, and each
        device applies only its own writes under shard_map — no
        collective and no full-array host round trip."""
        shape, dtype = self.fields[name]
        # duplicate targets in one set_many: keep the LAST write, the
        # host path's (numpy) semantics — XLA scatter leaves the winner
        # among duplicate indices unspecified
        flat = dev.astype(np.int64) * self.plan.R + rows
        if len(np.unique(flat)) != len(flat):
            _, last_rev = np.unique(flat[::-1], return_index=True)
            keep = np.sort(len(flat) - 1 - last_rev)
            dev, rows = dev[keep], rows[keep]
            values = np.broadcast_to(
                values, (len(flat),) + self.fields[name][0])[keep]
        n = len(rows)
        # fixed small tier, then a grow-only sticky cap per (shape,
        # dtype): adapt-epoch projection writes (new children /
        # unrefined parents, surface-sized) land in one program per
        # field whatever their per-epoch drift (the zero-new-programs
        # invariant, test_advection_amr), at small bases in the tier
        # and at large ones in the sticky cap
        cap = (_GATHER_TIER if n <= _GATHER_TIER
               else self._sticky_cap(("scatter", shape, str(dtype)), n))
        R = self.plan.R
        dev_p = np.zeros(cap, dtype=np.int32)
        row_p = np.full(cap, R - 1, dtype=np.int32)
        val_p = np.zeros((cap,) + shape, dtype=dtype)
        dev_p[:n] = dev
        row_p[:n] = rows
        if n:
            val_p[:n] = np.broadcast_to(values, (n,) + shape)
        # keyed by (shape, dtype), not field name: same-shaped fields
        # share one compiled scatter
        key = ("devscatter", shape, str(dtype), cap, R)
        fn = self._program_cache.get(key)
        if fn is None:
            mesh, axis = self.mesh, self.axis

            def body(arr, dv, rw, vl):
                mine = dv == jax.lax.axis_index(axis)
                r = jnp.where(mine, rw, R - 1)
                mexp = mine.reshape(mine.shape + (1,) * len(shape))
                safe = jnp.where(mexp, vl, jnp.zeros((), arr.dtype))
                return arr.at[0, r].set(safe, mode="drop")

            fn = jax.jit(_shard_map(
                body, mesh=mesh,
                in_specs=(P(self.axis), P(), P(), P()),
                out_specs=P(self.axis),
            ))
            self._program_cache[key] = fn
        return fn(self.data[name], jnp.asarray(dev_p), jnp.asarray(row_p),
                  jnp.asarray(val_p))

    # -- iteration views (dccrg.hpp:7594-7718) -------------------------

    # neighbor-type bitmask constants (dccrg.hpp:91-148)
    HAS_NO_NEIGHBOR = 0
    HAS_LOCAL_NEIGHBOR_OF = 1 << 0
    HAS_LOCAL_NEIGHBOR_TO = 1 << 1
    HAS_REMOTE_NEIGHBOR_OF = 1 << 2
    HAS_REMOTE_NEIGHBOR_TO = 1 << 3
    HAS_LOCAL_NEIGHBOR_BOTH = HAS_LOCAL_NEIGHBOR_OF | HAS_LOCAL_NEIGHBOR_TO
    HAS_REMOTE_NEIGHBOR_BOTH = HAS_REMOTE_NEIGHBOR_OF | HAS_REMOTE_NEIGHBOR_TO

    def neighbor_type_masks(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> np.ndarray:
        """Per-cell neighbor-type bitmask in plan.cells order: which of
        each cell's neighbors_of / neighbors_to live on its own device
        ("local") vs another device (reference is_neighbor_type_match,
        dccrg.hpp:2968-3075)."""
        plan = self.plan
        nl = plan.hoods[neighborhood_id].lists
        masks = np.zeros(len(plan.cells), dtype=np.int32)
        of_nbr_owner = plan.owner[np.searchsorted(plan.cells, nl.of_neighbor)]
        same = plan.owner[nl.of_source] == of_nbr_owner
        np.bitwise_or.at(masks, nl.of_source[same], self.HAS_LOCAL_NEIGHBOR_OF)
        np.bitwise_or.at(masks, nl.of_source[~same], self.HAS_REMOTE_NEIGHBOR_OF)
        to_nbr_owner = plan.owner[np.searchsorted(plan.cells, nl.to_neighbor)]
        same_to = plan.owner[nl.to_source] == to_nbr_owner
        np.bitwise_or.at(masks, nl.to_source[same_to], self.HAS_LOCAL_NEIGHBOR_TO)
        np.bitwise_or.at(masks, nl.to_source[~same_to], self.HAS_REMOTE_NEIGHBOR_TO)
        return masks

    def get_cells(
        self,
        criteria=None,
        exact_match: bool = False,
        neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
    ) -> np.ndarray:
        """Cell ids, optionally filtered by neighbor-type criteria
        (reference get_cells, dccrg.hpp:661-753). Without criteria:
        every cell. With criteria: cells whose neighbor-type bitmask
        matches any criterion — equality under ``exact_match``,
        otherwise a non-empty intersection with the merged criteria.
        Always id-sorted (the reference's ``sorted`` flag exists because
        its hash-map iteration order is arbitrary; here there is only
        one order)."""
        if neighborhood_id not in self.plan.hoods:
            return np.empty(0, np.uint64)
        cells = self.plan.cells.copy()
        if criteria is None:
            return cells
        criteria = [int(c) for c in np.atleast_1d(criteria)]
        masks = self.neighbor_type_masks(neighborhood_id)
        if exact_match:
            keep = np.isin(masks, criteria)
        else:
            merged = 0
            for c in criteria:
                merged |= c
            keep = (masks & merged) > 0
        return cells[keep]

    # -- extensible iteration-cache items ------------------------------
    # (reference Additional_Cell_Items / Additional_Neighbor_Items,
    # dccrg.hpp:7404-7518: user mixins whose update() runs at cache
    # rebuild; e.g. Is_Local / Center in tests/advection/cell.hpp).
    # Here an item is a vectorized function evaluated over the whole
    # cell (or neighbor-entry) set at every structure rebuild.

    def add_cell_data_item(self, name: str, fn) -> None:
        """Register ``fn(grid, ids) -> array`` recomputed at every
        structure rebuild and cached for the epoch."""
        self._cell_items[name] = fn
        if self.initialized:
            self._cell_item_values[name] = np.asarray(fn(self, self.plan.cells))

    def remove_cell_data_item(self, name: str) -> None:
        self._cell_items.pop(name, None)
        self._cell_item_values.pop(name, None)

    def cell_data_item(self, name: str, ids=None) -> np.ndarray:
        """The cached item values, for all cells (plan order) or the
        given ids."""
        vals = self._cell_item_values[name]
        if ids is None:
            return vals.copy()
        scalar = np.isscalar(ids) or np.asarray(ids).ndim == 0
        ids = np.atleast_1d(np.asarray(ids, dtype=np.uint64))
        pos = np.searchsorted(self.plan.cells, ids)
        if np.any(pos >= len(self.plan.cells)) or np.any(self.plan.cells[pos] != ids):
            raise KeyError("unknown cell id(s)")
        out = vals[pos]
        return out[0] if scalar else out

    def add_neighbor_data_item(self, name: str, fn,
                               neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> None:
        """Register ``fn(grid, src_ids, nbr_ids, offsets) -> array``
        over the neighborhood's flat neighbor entries, recomputed at
        every structure rebuild."""
        self._neighbor_items[name] = (fn, neighborhood_id)
        if self.initialized:
            nl = self.plan.hoods[neighborhood_id].lists
            self._neighbor_item_values[name] = np.asarray(
                fn(self, self.plan.cells[nl.of_source], nl.of_neighbor, nl.of_offset)
            )

    def remove_neighbor_data_item(self, name: str) -> None:
        self._neighbor_items.pop(name, None)
        self._neighbor_item_values.pop(name, None)

    def neighbor_data_item(self, name: str, cell=None) -> np.ndarray:
        """Item values for all neighbor entries, or one cell's."""
        vals = self._neighbor_item_values[name]
        if cell is None:
            return vals.copy()
        _, hid = self._neighbor_items[name]
        nl = self.plan.hoods[hid].lists
        pos = self._cell_pos(cell)
        if pos is None:
            raise ValueError(f"unknown cell {cell}")
        return vals[nl.of_source == pos]

    def _update_data_items(self) -> None:
        for name, fn in self._cell_items.items():
            self._cell_item_values[name] = np.asarray(fn(self, self.plan.cells))
        # drop items whose neighborhood has been removed
        for name in [n for n, (_, hid) in self._neighbor_items.items()
                     if hid not in self.plan.hoods]:
            self.remove_neighbor_data_item(name)
        for name, (fn, hid) in self._neighbor_items.items():
            nl = self.plan.hoods[hid].lists
            self._neighbor_item_values[name] = np.asarray(
                fn(self, self.plan.cells[nl.of_source], nl.of_neighbor, nl.of_offset)
            )

    def is_inner(self, cell) -> bool:
        """True when no neighbor relation of the cell crosses a device
        boundary (dccrg_iterator_support.hpp:33-56)."""
        pos = self._cell_pos(cell)
        if pos is None:
            raise ValueError(f"unknown cell {cell}")
        d = int(self.plan.owner[pos])
        row = int(self.plan.row_of_pos[pos])
        return row < self._n_inner(d)

    def is_outer(self, cell) -> bool:
        return not self.is_inner(cell)

    def local_cells(self) -> CellView:
        return CellView(self.plan.cells.copy(), self.plan.owner.copy())

    def inner_cells(self) -> CellView:
        ids = np.concatenate(
            [self.plan.local_ids[d][: self._n_inner(d)] for d in range(self.n_dev)]
        ) if self.n_dev else np.empty(0, np.uint64)
        return self._view_of(ids)

    def outer_cells(self) -> CellView:
        ids = np.concatenate(
            [
                self.plan.local_ids[d][self._n_inner(d): self.plan.n_local[d]]
                for d in range(self.n_dev)
            ]
        )
        return self._view_of(ids)

    def remote_cells(self) -> CellView:
        """Cells with copies on some device that doesn't own them."""
        ids = np.unique(np.concatenate([g for g in self.plan.ghost_ids if len(g)]) if any(
            len(g) for g in self.plan.ghost_ids) else np.empty(0, np.uint64))
        return self._view_of(ids)

    def all_cells(self) -> CellView:
        return self.local_cells()

    def _n_inner(self, d):
        return int(self.plan.hoods[DEFAULT_NEIGHBORHOOD_ID].n_inner[d])

    def _view_of(self, ids):
        ids = np.sort(ids)
        pos = np.searchsorted(self.plan.cells, ids)
        return CellView(ids, self.plan.owner[pos])

    # -- neighbor queries (dccrg.hpp:831-3236) -------------------------

    def _cell_pos(self, cell):
        """Index of ``cell`` in the sorted replicated cell list, or
        None for an unknown id (the reference's cell_process lookup)."""
        pos = int(np.searchsorted(self.plan.cells, np.uint64(cell)))
        if pos >= len(self.plan.cells) or self.plan.cells[pos] != np.uint64(cell):
            return None
        return pos

    def _cell_neighbors_of(self, pos, hood):
        """(neighbor ids, offsets) of one cell. When the flat entry
        stream is already materialized it is the fastest lookup; on the
        uniform fast path (lazy stream) a single-cell find_neighbors_of
        answers in O(K log n) instead of forcing the multi-GB stream
        build the fast path exists to avoid."""
        if callable(hood._lists):
            src, nbr, off, _item = find_neighbors_of(
                self.mapping, self.topology, self.plan.cells,
                self.plan.cells[pos : pos + 1], hood.offsets,
            )
            return nbr, off
        nl = hood.lists
        m = nl.of_source == pos
        return nl.of_neighbor[m], nl.of_offset[m]

    def _cell_neighbors_to(self, pos, hood):
        """(ids, offsets) of cells that consider this cell a neighbor.
        Direct subset query when the entry stream is lazy (uniform and
        hybrid fast paths), entry stream otherwise."""
        if callable(hood._lists):
            _qi, src, off = find_neighbors_to_subset(
                self.mapping, self.topology, self.plan.cells,
                self.plan.cells[pos : pos + 1], hood.offsets,
            )
            return src, off
        nl = hood.lists
        m = nl.to_source == pos
        return nl.to_neighbor[m], nl.to_offset[m]

    def get_neighbors_of(self, cell, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """[(neighbor id, (dx, dy, dz))] in neighborhood-item order."""
        pos = self._cell_pos(cell)
        if pos is None:
            raise ValueError(f"unknown cell {cell}")
        nbrs, offs = self._cell_neighbors_of(pos, self.plan.hoods[neighborhood_id])
        return list(zip(nbrs.tolist(), map(tuple, offs)))

    def get_neighbors_to(self, cell, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        pos = self._cell_pos(cell)
        if pos is None:
            raise ValueError(f"unknown cell {cell}")
        nbrs, offs = self._cell_neighbors_to(pos, self.plan.hoods[neighborhood_id])
        return list(zip(nbrs.tolist(), map(tuple, offs)))

    def get_face_neighbors_of(self, cell):
        """[(neighbor id, direction)] with directions +-1/2/3 as in the
        reference (dccrg.hpp:2828-2955): +-1 = x, +-2 = y, +-3 = z."""
        out = []
        size = int(self.mapping.get_cell_length_in_indices(np.uint64(cell)))
        for nid, off in self.get_neighbors_of(cell):
            nsize = int(self.mapping.get_cell_length_in_indices(np.uint64(nid)))
            for dim in range(3):
                lo, hi = off[dim], off[dim] + nsize
                other = [d for d in range(3) if d != dim]
                if all(off[d] < size and off[d] + nsize > 0 for d in other):
                    if hi == 0:
                        out.append((nid, -(dim + 1)))
                    elif lo == size:
                        out.append((nid, dim + 1))
        return out

    def get_neighbors_of_at_offset(self, cell, x, y, z,
                                   neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """Neighbors of ``cell`` inside the neighborhood window at
        offset (x, y, z) — [(id, (dx, dy, dz))], empty for the zero
        offset, an offset outside the neighborhood, or an unknown cell
        (reference get_neighbors_of_at_offset, dccrg.hpp:3110-3160).

        Matches by window intersection, so a coarser neighbor covering
        several windows is returned at each of them (as the reference's
        index matching does), even though the stored neighbor list
        holds it only once."""
        if (x, y, z) == (0, 0, 0):
            return []
        hood = self.plan.hoods.get(neighborhood_id)
        if hood is None:
            return []
        if not np.any(np.all(hood.offsets == np.array([x, y, z]), axis=1)):
            return []
        pos = self._cell_pos(cell)
        if pos is None:
            return []
        nbrs, offs = self._cell_neighbors_of(pos, hood)
        if len(nbrs) == 0:
            return []
        size = int(self.mapping.get_cell_length_in_indices(np.uint64(cell)))
        win = self.mapping.get_indices(np.uint64(cell)).astype(np.int64)
        win += np.array([x, y, z], dtype=np.int64) * size
        il = self.mapping.get_index_length().astype(np.int64)
        for d in range(3):
            if self.topology.is_periodic(d):
                win[d] %= il[d]
            elif not 0 <= win[d] < il[d]:
                return []
        nidx = self.mapping.get_indices(nbrs).astype(np.int64)
        nsize = self.mapping.get_cell_length_in_indices(nbrs).astype(np.int64)
        hit = np.ones(len(nbrs), dtype=bool)
        for d in range(3):
            if self.topology.is_periodic(d):
                h = np.zeros(len(nbrs), dtype=bool)
                for shift in (-il[d], 0, il[d]):
                    h |= (nidx[:, d] + shift < win[d] + size) & (
                        nidx[:, d] + nsize + shift > win[d]
                    )
                hit &= h
            else:
                hit &= (nidx[:, d] < win[d] + size) & (nidx[:, d] + nsize > win[d])
        return list(zip(nbrs[hit].tolist(), map(tuple, offs[hit])))

    def get_remote_neighbors_of(self, cell,
                                neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
                                sorted: bool = False):
        """Neighbors of ``cell`` owned by a different device than the
        cell itself (reference get_remote_neighbors_of,
        dccrg.hpp:3175-3234)."""
        return self._remote_neighbors(cell, neighborhood_id, sorted, to=False)

    def get_remote_neighbors_to(self, cell,
                                neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
                                sorted: bool = False):
        """Cells considering ``cell`` a neighbor that live on a
        different device (reference get_remote_neighbors_to,
        dccrg.hpp:3236-3296)."""
        return self._remote_neighbors(cell, neighborhood_id, sorted, to=True)

    def _remote_neighbors(self, cell, neighborhood_id, sorted, to):
        hood = self.plan.hoods.get(neighborhood_id)
        if hood is None:
            return np.empty(0, np.uint64)
        pos = self._cell_pos(cell)
        if pos is None:
            return np.empty(0, np.uint64)
        if to:
            nbrs, _ = self._cell_neighbors_to(pos, hood)
        else:
            nbrs, _ = self._cell_neighbors_of(pos, hood)
        own = int(self.plan.owner[pos])
        nbr_owner = self.plan.owner[np.searchsorted(self.plan.cells, nbrs)]
        out = nbrs[nbr_owner != own]
        return np.sort(out) if sorted else out

    def find_cells(self, indices_min, indices_max,
                   minimum_refinement_level: int = 0,
                   maximum_refinement_level: int | None = None) -> np.ndarray:
        """Existing cells whose index volume overlaps the inclusive box
        [indices_min, indices_max] and whose refinement level is within
        the given range (reference find_cells, dccrg.hpp:4908-5030).
        Indices are in smallest-possible-cell units; result id-sorted."""
        if maximum_refinement_level is None:
            maximum_refinement_level = self.mapping.max_refinement_level
        if minimum_refinement_level > maximum_refinement_level:
            raise ValueError("minimum refinement level > maximum")
        if maximum_refinement_level > self.mapping.max_refinement_level:
            raise ValueError("maximum refinement level too large")
        lo = np.asarray(indices_min, dtype=np.int64)
        hi = np.asarray(indices_max, dtype=np.int64)
        if np.any(lo > hi):
            raise ValueError("minimum index > maximum index")
        cells = self.plan.cells
        lvl = self.mapping.get_refinement_level(cells)
        keep = (lvl >= minimum_refinement_level) & (lvl <= maximum_refinement_level)
        idx = self.mapping.get_indices(cells).astype(np.int64)
        size = self.mapping.get_cell_length_in_indices(cells).astype(np.int64)
        overlap = np.all((idx <= hi) & (idx + size[:, None] - 1 >= lo), axis=1)
        return cells[keep & overlap]

    # -- user neighborhoods (dccrg.hpp:6491-6681) ----------------------

    def add_neighborhood(self, neighborhood_id, offsets) -> bool:
        if neighborhood_id in self.neighborhoods:
            return False
        offsets = validate_neighborhood(offsets, self._hood_len)
        self.neighborhoods[neighborhood_id] = offsets
        if self.initialized:
            self._build_plan(self.plan.cells, self.plan.owner)
        return True

    def remove_neighborhood(self, neighborhood_id) -> None:
        if neighborhood_id == DEFAULT_NEIGHBORHOOD_ID:
            raise ValueError("cannot remove the default neighborhood")
        self.neighborhoods.pop(neighborhood_id, None)
        if self.initialized:
            self._build_plan(self.plan.cells, self.plan.owner)

    # -- halo exchange (dccrg.hpp:978-1014, 5046-5413) -----------------

    def set_transfer_predicate(self, field: str, fn) -> None:
        """Per-peer, per-neighborhood selection of what a cell sends —
        the TPU counterpart of the reference's 5-argument
        ``get_mpi_datatype(cell, sender, receiver, receiving, hood)``
        (dccrg_get_cell_datatype.hpp:48-213), where a cell may expose
        different data to different peers.

        ``fn(cell_ids, sender, receiver, neighborhood_id) -> bool
        array`` is evaluated at plan time per device pair; a False
        entry drops that cell's ``field`` payload for that pair (both
        sides skip it — the symmetric equivalent of the reference's
        requirement that sender and receiver datatypes agree). Pass
        ``None`` to clear.

        Predicates are sampled into cached pair tables when set; a
        closure whose behavior changes later must be re-registered via
        this setter to invalidate those caches."""
        if not self.initialized:
            raise RuntimeError(
                "set_transfer_predicate() requires initialize() first "
                "(predicates are sampled against the built plan)")
        if fn is None:
            self._transfer_predicates.pop(field, None)
        else:
            if field not in self.fields:
                raise KeyError(f"unknown field {field!r}")
            self._transfer_predicates[field] = fn
        # pair tables are runtime arguments of the compiled programs;
        # only the cached (host + device) tables need rebuilding
        for hood in self.plan.hoods.values():
            hood._pair_host.clear()
            stale = [k for k in hood._dev
                     if isinstance(k, tuple) and k[0] in ("pair", "peer")]
            for k in stale:
                del hood._dev[k]

    @staticmethod
    def _pair_groups(c):
        """(starts, ends) of the (sender, receiver) groups in a compact
        pair record (entries are sorted by (p, q))."""
        pq = c["p"] * np.int64(c["n_dev"]) + c["q"]
        starts = np.r_[0, np.flatnonzero(np.diff(pq)) + 1] \
            if len(pq) else np.empty(0, np.int64)
        ends = np.r_[starts[1:], len(pq)] if len(pq) else starts
        return starts.astype(np.int64), ends.astype(np.int64)

    def _field_pair_compact(self, neighborhood_id, field):
        """The hood's compact pair record, filtered by the field's
        transfer predicate if set (dropped entries removed; surviving
        entries KEEP their slot positions, so holes mirror the dense
        tables' -1 slots)."""
        hood = self.plan.hoods[neighborhood_id]
        c = hood.pair_compact
        fn = self._transfer_predicates.get(field)
        if fn is None:
            return c
        cached = hood._pair_host.get(("c", field))
        if cached is not None:
            return cached
        keep = np.ones(len(c["p"]), dtype=bool)
        starts, ends = self._pair_groups(c)
        # the predicate contract is per-(sender, receiver): each live
        # pair gets its own call (O(devices x peers) calls)
        for s, e in zip(starts, ends):
            p0, q0 = int(c["p"][s]), int(c["q"][s])
            ids = self.plan.local_ids[p0][c["srow"][s:e]]
            k = np.asarray(fn(ids, p0, q0, neighborhood_id), dtype=bool)
            if k.shape != ids.shape:
                raise ValueError(
                    "transfer predicate must return one bool per cell"
                )
            keep[s:e] = k
        out = dict(c)
        for key in ("p", "q", "pos", "srow", "rrow"):
            out[key] = c[key][keep]
        hood._pair_host[("c", field)] = out
        return out

    def _field_pair_tables(self, neighborhood_id, field):
        """(send_rows, recv_rows) DENSE views for one field — the
        all_to_all fallback and host introspection format; the
        per-delta ppermute path uses _field_pair_compact and never
        materializes these."""
        hood = self.plan.hoods[neighborhood_id]
        if self._transfer_predicates.get(field) is None:
            return hood.send_rows, hood.recv_rows
        cached = hood._pair_host.get(field)
        if cached is not None:
            return cached
        out = uniform_mod.dense_pair_tables(self._field_pair_compact(
            neighborhood_id, field))
        hood._pair_host[field] = out
        return out

    # halo exchanges with at most this many peer offsets use one
    # ppermute per offset instead of a dense all_to_all: each device
    # typically talks to ~2 neighbors, so the all_to_all's [n_dev, M]
    # buffer wastes ~n_dev/peers of the interconnect bandwidth
    _MAX_PEER_OFFSETS = 8

    def _peer_deltas(self, neighborhood_id):
        """Sorted device-offset set {(q-p) mod n_dev} with halo
        traffic, or None when the all_to_all fallback should be used
        (too many distinct offsets)."""
        hood = self.plan.hoods[neighborhood_id]
        if ("deltas",) in hood._dev:
            return hood._dev[("deltas",)]
        c = hood.pair_compact
        deltas = tuple(sorted(set(
            np.unique((c["q"] - c["p"]) % self.n_dev).tolist())))
        if len(deltas) > self._MAX_PEER_OFFSETS:
            deltas = None  # all_to_all fallback (memoized as None too)
        hood._dev[("deltas",)] = deltas
        return deltas

    def _pair_tables_device(self, neighborhood_id, field_names):
        """Per-field (send, recv) device tables, hood-memoized.

        With a small peer-offset set, tables are per-delta compact
        slices ``[n_dev, M_delta]`` (one ppermute each); otherwise the
        dense ``[n_dev, n_dev, M]`` all_to_all tables."""
        hood = self.plan.hoods[neighborhood_id]
        sh = self._sharding()
        deltas = self._peer_deltas(neighborhood_id)
        sends, recvs = [], []
        for n in field_names:
            if deltas is None:
                s, r = self._field_pair_tables(neighborhood_id, n)
                sends.append(hood.dev(("pair", n, "s"), s, sh))
                recvs.append(hood.dev(("pair", n, "r"), r, sh))
                continue
            # per-delta compact tables straight from the compact pair
            # record — the dense [n_dev, n_dev, M] arrays are never
            # touched on this path (pod-scale memory stays linear);
            # fc/dvec are only computed when some delta's tables are
            # not yet cached (the warm path is dictionary hits)
            fc = dvec = None
            for d in deltas:
                key_s, key_r = ("peer", n, d, "s"), ("peer", n, d, "r")
                if key_s not in hood._dev:
                    if fc is None:
                        fc = self._field_pair_compact(neighborhood_id, n)
                        dvec = (fc["q"] - fc["p"]) % self.n_dev
                    sel = dvec == d
                    # shrink to this delta's own (sticky) width; slots
                    # may have predicate holes, so cover the LAST valid
                    # slot, not the count
                    need = (int(fc["pos"][sel].max()) + 1
                            if sel.any() else 1)
                    Md = self._sticky_cap(("Md", neighborhood_id, d), need)
                    Md = min(Md, fc["M"])
                    sd = np.full((self.n_dev, Md), -1, dtype=np.int32)
                    rd = np.full((self.n_dev, Md), -1, dtype=np.int32)
                    inw = sel & (fc["pos"] < Md)
                    # device p SENDS to p+d; device q RECEIVES from q-d
                    # — both tables sharded by the acting device
                    sd[fc["p"][inw], fc["pos"][inw]] = fc["srow"][inw]
                    rd[fc["q"][inw], fc["pos"][inw]] = fc["rrow"][inw]
                    hood.dev(key_s, sd, sh)
                    hood.dev(key_r, rd, sh)
                sends.append(hood._dev[key_s])
                recvs.append(hood._dev[key_r])
        return tuple(sends), tuple(recvs)

    def _exchange_programs(self, neighborhood_id, n_f):
        """(start, finish, fused, n_t) jitted exchange programs for n_f
        fields — tables and field arrays are arguments, so one program
        serves every epoch whose (bucketed) shapes match.

        With a small peer-offset set the collective is one
        ``lax.ppermute`` per offset over compact [n_dev, M_delta]
        buffers (each device talks to its ~2 neighbors; a dense
        all_to_all would move n_dev/peers times the bytes); otherwise
        it falls back to the all_to_all over [n_dev, M]. ``n_t`` is
        the number of table slots per field per direction."""
        deltas = self._peer_deltas(neighborhood_id)
        n_dev = self.n_dev
        key = ("exchange", n_f, self.plan.R, deltas, n_dev)
        hit = self._program_cache.get(key)
        if hit is not None:
            return hit
        R = self.plan.R
        axis = self.axis
        mesh = self.mesh
        n_t = 1 if deltas is None else len(deltas)

        def start_body(*args):
            sends = args[: n_f * n_t]
            fields = args[n_f * n_t :]
            outs = []
            for i, f in enumerate(fields):
                fl = f[0]
                for j in range(n_t):
                    sr = sends[i * n_t + j][0]
                    dlt = None if deltas is None else deltas[j]
                    outs.append(_halo_send(fl, sr, dlt, axis, n_dev)[None])
            return tuple(outs)

        def finish_body(*args):
            recvs = args[: n_f * n_t]
            bufs = args[n_f * n_t : 2 * n_f * n_t]
            fields = args[2 * n_f * n_t :]
            outs = []
            for i, f in enumerate(fields):
                fl = f[0]
                for j in range(n_t):
                    fl = _halo_scatter(fl, recvs[i * n_t + j][0],
                                       bufs[i * n_t + j][0], R)
                fl = fl.at[R - 1].set(0)  # keep the zero pad row zero
                outs.append(fl[None])
            return tuple(outs)

        start_mapped = _shard_map(
            start_body,
            mesh=mesh,
            in_specs=(P(axis),) * (n_f * n_t + n_f),
            out_specs=(P(axis),) * (n_f * n_t),
        )
        finish_mapped = _shard_map(
            finish_body,
            mesh=mesh,
            in_specs=(P(axis),) * (2 * n_f * n_t + n_f),
            out_specs=(P(axis),) * n_f,
        )

        start = jax.jit(lambda *a: start_mapped(*a))
        finish = jax.jit(lambda *a: finish_mapped(*a))

        @jax.jit
        def fused(*args):
            sends = args[: n_f * n_t]
            recvs = args[n_f * n_t : 2 * n_f * n_t]
            fields = args[2 * n_f * n_t :]
            bufs = start_mapped(*sends, *fields)
            return finish_mapped(*recvs, *bufs, *fields)

        hit = (start, finish, fused, n_t)
        self._program_cache[key] = hit
        return hit

    def _exchange_split_fns(self, neighborhood_id, field_names):
        """Split-phase halo exchange: ``start`` runs the all_to_all and
        returns only the received ghost payload; ``finish`` scatters
        that payload into the *current* field arrays, touching ghost
        rows only — the reference's receives write ``remote_neighbors``
        exclusively (dccrg.hpp:10726-10935), so user writes to local
        rows between start and wait must survive. Returns callables
        bound to this epoch's pair tables; the underlying compiled
        programs are shared across epochs."""
        start_j, finish_j, _fused, _n_t = self._exchange_programs(
            neighborhood_id, len(field_names))
        sends, recvs = self._pair_tables_device(neighborhood_id, field_names)

        def start(*fields):
            return start_j(*sends, *fields)

        def finish(*bufs_and_fields):
            return finish_j(*recvs, *bufs_and_fields)

        return start, finish

    def update_copies_of_remote_neighbors(
        self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID, fields=None
    ) -> None:
        """Refresh ghost copies of remote neighbors: the reference's
        update_copies_of_remote_neighbors() (dccrg.hpp:978), one fused
        all_to_all. ``fields`` selects which per-cell fields move (the
        get_mpi_datatype() / transfer_switch boundary)."""
        self._check_not_in_flight(neighborhood_id)
        if self.n_dev == 1:
            return
        with telemetry.span("grid.exchange"):
            names = tuple(sorted(fields)) if fields is not None else tuple(sorted(self.fields))
            _start, _finish, fused, _n_t = self._exchange_programs(
                neighborhood_id, len(names))
            sends, recvs = self._pair_tables_device(neighborhood_id, names)
            out = fused(*sends, *recvs, *(self.data[n] for n in names))
            for n, arr in zip(names, out):
                self.data[n] = arr

    def _check_not_in_flight(self, neighborhood_id):
        entry = self._pending.get(neighborhood_id)
        if entry is not None and entry[0] == self.plan.epoch:
            raise RuntimeError(
                f"neighborhood {neighborhood_id} already has an in-flight halo "
                "update; call wait_remote_neighbor_copy_updates first"
            )
        if entry is not None:
            # orphaned by a structure rebuild: its wait would raise
            # anyway, and this fresh update supersedes it
            del self._pending[neighborhood_id]

    # split-phase parity API (dccrg.hpp:5046-5413). Dispatch is async
    # in JAX, so start returns immediately; wait scatters ONLY the
    # received ghost rows into the then-current arrays — local-row
    # writes made between start and wait survive, matching the
    # reference's receives-touch-remote_neighbors-only semantics
    # (dccrg.hpp:10726-10935).
    def start_remote_neighbor_copy_updates(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID, fields=None):
        self._check_not_in_flight(neighborhood_id)
        names = tuple(sorted(fields)) if fields is not None else tuple(sorted(self.fields))
        if self.n_dev == 1:
            self._pending[neighborhood_id] = (self.plan.epoch, names, None, None)
            return
        with telemetry.span("grid.exchange.start"):
            start, finish = self._exchange_split_fns(neighborhood_id, names)
            bufs = start(*(self.data[n] for n in names))
        self._pending[neighborhood_id] = (self.plan.epoch, names, finish, bufs)

    def wait_remote_neighbor_copy_updates(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> None:
        if neighborhood_id not in self._pending:
            return
        epoch, names, finish, bufs = self._pending.pop(neighborhood_id)
        if epoch != self.plan.epoch:
            raise RuntimeError(
                "grid structure changed between start_remote_neighbor_copy_updates "
                "and wait_remote_neighbor_copy_updates; the in-flight halo payload "
                "is stale"
            )
        if finish is None:  # single-device: nothing was exchanged
            return
        with telemetry.span("grid.exchange.wait"):
            out = finish(*bufs, *(self.data[n] for n in names))
            for n, arr in zip(names, out):
                self.data[n] = arr

    def wait_remote_neighbor_copy_update_receives(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> None:
        self.wait_remote_neighbor_copy_updates(neighborhood_id)

    def wait_remote_neighbor_copy_update_sends(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID) -> None:
        pass

    def get_number_of_update_send_cells(
        self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID, field: str | None = None
    ) -> int:
        """Total cells sent per halo update (dccrg.hpp:5428); with
        ``field``, the count after that field's transfer predicate."""
        if field is None:
            return len(self.plan.hoods[neighborhood_id].pair_compact["p"])
        return len(self._field_pair_compact(neighborhood_id, field)["p"])

    def get_number_of_update_receive_cells(
        self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID, field: str | None = None
    ) -> int:
        if field is None:
            return len(self.plan.hoods[neighborhood_id].pair_compact["q"])
        return len(self._field_pair_compact(neighborhood_id, field)["q"])

    # -- stencil execution ---------------------------------------------

    def apply_stencil(
        self,
        kernel,
        fields_in,
        fields_out,
        neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
        include_to=False,
        extra_args=(),
    ):
        """Run a gather-based stencil kernel over all local cells.

        ``kernel(cell_fields, nbr_fields, offs, mask, *extra)`` receives
        per-device blocks: ``cell_fields[name]`` is ``[L, ...]``,
        ``nbr_fields[name]`` is ``[L, S, ...]`` (neighbors gathered,
        zeros at padding), ``offs`` is ``[L, S, 3]`` and ``mask``
        ``[L, S]``. With ``include_to=True`` a second
        (nbr_to_fields, to_offs, to_mask) triple follows. Must return a
        dict name -> [L, ...] for every name in ``fields_out``.

        The updated field rows are written back; ghost copies are NOT
        refreshed (call update_copies_of_remote_neighbors).
        """
        fields_in = tuple(fields_in)
        fields_out = tuple(fields_out)
        fn, tables = self._make_stencil(
            kernel, fields_in, fields_out, neighborhood_id, include_to,
            n_extra=len(extra_args),
        )
        out = fn(*tables, *(self.data[n] for n in fields_in),
                 *(self.data[n] for n in fields_out), *extra_args)
        for n, arr in zip(fields_out, out):
            self.data[n] = arr
        self._mark_ckpt_dirty(fields_out)


    def _on_accelerator(self) -> bool:
        return self.mesh.devices.flat[0].platform not in ("cpu",)

    def _use_roll_gather(self) -> bool:
        """Roll-decomposed gathers trade a dense random gather for S
        sequential rolls + a sparse fixup: a clear win on TPU (random
        gathers crawl), a small loss on the CPU backend (caches absorb
        the near-sequential gather, the stack materialization doesn't
        pay). Default: on for accelerators, off for CPU; override with
        DCCRG_ROLL_STENCIL=0/1."""
        env = os.environ.get("DCCRG_ROLL_STENCIL")
        if env in ("0", "1"):
            return env == "1"
        return self._on_accelerator()

    def _slot_gather_kind(self, hood, cf, use_roll):
        """``(kind, slab)`` of a slot-wise stencil's neighbor gather, by
        the layout the plan has: ``"roll3d"`` (single-device closed
        form: rows are grid order), ``"slab3d"`` (multi-device closed
        form whose rows are whole z planes; ``slab`` is its
        ``hood.slab_plan``), ``"roll_fixup"`` (flat roll + fixup
        scatter) or ``"table"``."""
        if cf is not None and not cf.get("multi"):
            return "roll3d", None
        if cf is not None:
            plan = self.plan
            slab = hood.slab_plan(plan.local_ids, plan.ghost_ids, plan.L)
            if slab is not None:
                return "slab3d", slab
        return ("roll_fixup" if use_roll else "table"), None

    def _make_stencil(self, kernel, fields_in, fields_out, neighborhood_id, include_to,
                      n_extra=0):
        """(program, bound tables) for a gather stencil. The jitted
        program takes every table as an argument and is cached by its
        STATIC signature (capacities, flags, kernel) — bucketed plan
        rebuilds reuse it; only the table values re-upload."""
        hood = self.plan.hoods[neighborhood_id]
        L, R = self.plan.L, self.plan.R
        sh = self._sharding()
        split = hood.hard_nbr_rows is not None and not include_to
        merged = include_to and hood.hard_nbr_rows is not None
        roll = None
        cf = None
        if merged:
            uniform_offs = False
            if "m_rows" not in hood._dev:
                m_rows, m_offs, m_mask = hood.merged_of_tables(R - 1)
                hood.dev("m_rows", m_rows, sh)
                hood.dev("m_offs", m_offs, sh)
                hood.dev("m_mask", m_mask, sh)
            tables = [hood._dev["m_rows"], hood._dev["m_offs"],
                      hood._dev["m_mask"]]
        else:
            uniform_offs = hood.offs_const is not None
            cf = hood.closed_form if not include_to else None
            # affine tables lower the gather to rolls + sparse fixups;
            # closed-form plans HAVE no tables, so they always roll and
            # additionally synthesize the mask in-body
            if cf is not None:
                roll = hood.roll_plan(L)
            elif uniform_offs and not include_to and self._use_roll_gather():
                roll = hood.roll_plan(
                    L, cap=lambda n: self._sticky_cap(("rollW", neighborhood_id), n))
            else:
                roll = None
            if roll is not None:
                tables = [hood.dev("roll_dummy",
                                   np.zeros((self.n_dev, 1, 1), np.int32), sh)]
            else:
                tables = [hood.dev("nbr_rows", hood.nbr_rows, sh)]
            if uniform_offs:
                # per-slot constant offsets: synthesized in-body from
                # the mask instead of storing [n_dev, L, S, 3] in HBM
                tables.append(hood.dev("offs_const", hood.offs_const))
            else:
                tables.append(hood.dev("nbr_offs", hood.nbr_offs, sh))
            if cf is not None:
                if cf.get("multi"):
                    # multi-device closed-form: the mask is synthesized
                    # from the per-row grid index (rows are NOT grid
                    # order), shipped in the mask slot
                    tables.append(self.device_row_ids())
                else:
                    tables.append(hood.dev("mask_dummy",
                                           np.zeros((self.n_dev, 1, 1), bool),
                                           sh))
            else:
                tables.append(hood.dev("nbr_mask", hood.nbr_mask, sh))
        r_shifts = tuple(int(s) for s in roll[0]) if roll is not None else None
        slotwise = isinstance(kernel, SlotwiseKernel)
        g_kind, slab = (self._slot_gather_kind(hood, cf, roll is not None)
                        if slotwise else (None, None))
        if slab is not None:
            slab, fix = slab  # static (Zs, z offsets, W); table
            tables.append(hood.dev("slab_fix", fix, sh))
        elif roll is not None:
            tables.append(hood.dev("roll_wr", roll[1], sh))
            tables.append(hood.dev("roll_ws", roll[2], sh))
        scaled = uniform_offs and hood.scale_rows is not None
        if scaled:
            tables.append(hood.dev("scale_rows", hood.scale_rows, sh))
        if split:
            tables.append(hood.dev("hard_rows", hood.hard_rows, sh))
            tables.append(hood.dev("hard_nbr_rows", hood.hard_nbr_rows, sh))
            tables.append(hood.dev("hard_offs", hood.hard_offs, sh))
            tables.append(hood.dev("hard_mask", hood.hard_mask, sh))
        if include_to:
            tables.append(hood.dev("to_rows", hood.to_rows, sh))
            tables.append(hood.dev("to_offs", hood.to_offs, sh))
            tables.append(hood.dev("to_mask", hood.to_mask, sh))

        synth = _synth_key(cf)
        key = ("stencil", kernel, fields_in, fields_out, include_to, n_extra,
               L, R, uniform_offs, scaled, split, merged, r_shifts, synth,
               slab)
        fn = self._program_cache.get(key)
        if fn is not None:
            return fn, tables

        n_in, n_out = len(fields_in), len(fields_out)
        axis, mesh = self.axis, self.mesh
        use_roll = r_shifts is not None
        if slotwise and include_to:
            raise ValueError("SlotwiseKernel does not support include_to")
        if slotwise:
            telemetry.inc("dccrg_slot_gather_programs_total", gather=g_kind)

        def body(nrows, noffs, nmask, *args):
            nrows = nrows[0]
            row_gidx = None
            if synth is not None:
                row_gidx = nmask[0][:L] if synth[4] else None
                nmask = None  # synthesized on demand (dense) / per-slot
            else:
                nmask = nmask[0]
            wr = ws = slab_fix = None
            if slab:
                slab_fix, *args = args
                slab_fix = slab_fix[0]
            elif use_roll:
                wr, ws, *args = args
                wr, ws = wr[0], ws[0]
            if scaled:
                sc, *args = args
                sc0 = sc[0]
            if not uniform_offs:
                noffs = noffs[0]
            if split:
                hr, hnr, hof, hm, *args = args
                hr, hnr, hof, hm = hr[0], hnr[0], hof[0], hm[0]
            if include_to:
                trows, toffs, tmask, *args = args
                trows, toffs, tmask = trows[0], toffs[0], tmask[0]
            ins = args[:n_in]
            outs_cur = args[n_in: n_in + n_out]
            extra = args[n_in + n_out:]
            cell_fields = {n: f[0][:L] for n, f in zip(fields_in, ins)}
            if slotwise:
                # per-slot gather + accumulate: the [L, S] neighbor
                # stack (and [L, S, 3] offsets) never materialize
                if synth is not None:
                    sgidx, sbase = _synth_prep(synth, L, row_gidx=row_gidx)
                    mask_col = lambda j: _synth_col(synth, sgidx, sbase, j)
                else:
                    mask_col = lambda j: nmask[:, j]
                n_slots = len(r_shifts) if use_roll else nrows.shape[1]
                slot_gather = _make_slot_gather(
                    g_kind, synth, L, use_roll, r_shifts, nrows, wr, ws,
                    slab, slab_fix)
                result = _run_slotwise(
                    kernel, cell_fields,
                    {n: f[0] for n, f in zip(fields_in, ins)}, slot_gather,
                    _make_offs_col(uniform_offs, noffs,
                                   sc0 if scaled else None),
                    mask_col, n_slots, extra)
            else:
                if nmask is None:
                    nmask = _synth_mask(synth, L, row_gidx=row_gidx)
                if uniform_offs:
                    noffs = nmask[:, :, None] * noffs[None, :, :]
                    if scaled:
                        # offs_const is in cell units; scale by per-row
                        # size
                        noffs = noffs * sc0[:, None, None]
                gather_nbr = _make_nbr_gather(
                    use_roll, r_shifts, L, nrows, nmask, wr, ws)
                nbr_fields = {n: gather_nbr(f[0])
                              for n, f in zip(fields_in, ins)}
                if include_to:
                    to_fields = {n: f[0][trows]
                                 for n, f in zip(fields_in, ins)}
                    result = kernel(
                        cell_fields, nbr_fields, noffs, nmask, to_fields,
                        toffs, tmask, *extra,
                    )
                else:
                    result = kernel(cell_fields, nbr_fields, noffs, nmask,
                                    *extra)
            if split:
                # second pass over the hard rows (near refinement) with
                # their own, wider gather tables; results scattered over
                # the far pass's output (pad index L drops)
                hrc = jnp.minimum(hr, L - 1)
                h_cell = {n: cell_fields[n][hrc] for n in fields_in}
                h_nbr = {n: f[0][hnr] for n, f in zip(fields_in, ins)}
                h_result = kernel(h_cell, h_nbr, hof, hm, *extra)
                for n in fields_out:
                    result[n] = result[n].at[hr].set(
                        h_result[n].astype(result[n].dtype), mode="drop"
                    )
            outs = []
            for n, cur in zip(fields_out, outs_cur):
                fl = cur[0]
                fl = fl.at[:L].set(result[n].astype(fl.dtype))
                outs.append(fl[None])
            return tuple(outs)

        mapped = _shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis), P() if uniform_offs else P(axis), P(axis))
            + ((P(axis),) if slab else (P(axis), P(axis)) if use_roll else ())
            + ((P(axis),) if scaled else ())
            + ((P(axis),) * 4 if split else ())
            + ((P(axis), P(axis), P(axis)) if include_to else ())
            + (P(axis),) * (n_in + n_out) + (P(),) * n_extra,
            out_specs=(P(axis),) * n_out,
            check_vma=False,
        )

        fn = jax.jit(lambda *a: mapped(*a))
        self._program_cache[key] = fn
        return fn, tables

    # -- fused multi-step execution ------------------------------------

    def compile_step_loop(
        self,
        kernel,
        fields_in,
        fields_out,
        exchange_fields=None,
        neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
        n_extra=0,
    ):
        """One jitted program running ``n_steps`` time steps on device.

        Each iteration refreshes ghost rows of ``exchange_fields`` (as
        update_copies_of_remote_neighbors; several devices only), then
        gathers neighbors and runs ``kernel`` (same signature as
        apply_stencil's) over every local row, then writes the result
        into ``fields_out``: scopes ``dccrg.exchange``, ``dccrg.bulk``
        and ``dccrg.apply``; a hybrid plan's hard rows (cells near a
        change of refinement level, whose neighbours sit in their own
        wider tables) are recomputed in ``dccrg.hard``. The whole time
        loop is a single XLA program (lax.fori_loop) with no host
        round-trips. The
        reference's start/solve-inner/wait/solve-outer overlap
        (dccrg.hpp:5046-5413, tests/advection/2d.cpp:327-343) is not
        restated inside it: the bulk pass reads the landed ghosts.

        ``exchange_fields`` must be a subset of ``fields_out`` (fields
        that change per step); static fields' ghosts are assumed valid
        for the whole epoch. Returns ``(fn, tables, static_in)`` where
        ``fn(n_steps, *tables, *in, *out, *extra) -> out arrays`` with
        dynamic ``n_steps``; the program is cached by its static shape
        signature and survives (bucketed) structure epochs. Use
        :meth:`run_steps` for the stateful wrapper.
        """
        fields_in = tuple(fields_in)
        fields_out = tuple(fields_out)
        if exchange_fields is None:
            exchange_fields = fields_out
        exchange_fields = tuple(exchange_fields)
        if not set(exchange_fields) <= set(fields_out):
            raise ValueError(
                "exchange_fields must be a subset of fields_out; static "
                "fields' ghosts are refreshed once per structure epoch"
            )
        # DCCRG_BULK=pallas: the roll-plan-driven Pallas bulk executor
        # (ops/roll_executor.py) replaces the XLA roll path where the
        # plan is eligible (single-device closed-form, scalar fields,
        # SlotwiseKernel); anything else falls through. With the env
        # unset (or =xla) this branch is never entered and the
        # pre-executor program compiles bit-identically — the negative
        # pin, same discipline as DCCRG_INTEGRITY=0.
        if os.environ.get("DCCRG_BULK", "").strip().lower() == "pallas":
            from .ops import roll_executor

            built = roll_executor.compile_bulk_step_loop(
                self, kernel, fields_in, fields_out, exchange_fields,
                neighborhood_id, n_extra)
            if built is not None:
                return built
        hood = self.plan.hoods[neighborhood_id]
        L, R = self.plan.L, self.plan.R
        sh = self._sharding()
        uniform_offs = hood.offs_const is not None
        split = hood.hard_nbr_rows is not None
        cf = hood.closed_form
        if cf is not None:
            roll = hood.roll_plan(L)  # table-free plans always roll
        elif uniform_offs and self._use_roll_gather():
            roll = hood.roll_plan(
                L, cap=lambda n: self._sticky_cap(("rollW", neighborhood_id), n))
        else:
            roll = None
        r_shifts = tuple(int(s) for s in roll[0]) if roll is not None else None
        use_roll = r_shifts is not None
        static_in = tuple(n for n in fields_in if n not in fields_out)
        n_static, n_out = len(static_in), len(fields_out)
        exch_idx = tuple(fields_out.index(n) for n in exchange_fields)
        n_x = len(exch_idx)

        tables = []
        if use_roll:
            tables.append(hood.dev("roll_dummy",
                                   np.zeros((self.n_dev, 1, 1), np.int32), sh))
        else:
            tables.append(hood.dev("nbr_rows", hood.nbr_rows, sh))
        if uniform_offs:
            tables.append(hood.dev("offs_const", hood.offs_const))
        else:
            tables.append(hood.dev("nbr_offs", hood.nbr_offs, sh))
        if cf is not None:
            if cf.get("multi"):
                tables.append(self.device_row_ids())
            else:
                tables.append(hood.dev("mask_dummy",
                                       np.zeros((self.n_dev, 1, 1), bool),
                                       sh))
        else:
            tables.append(hood.dev("nbr_mask", hood.nbr_mask, sh))
        sends, recvs = self._pair_tables_device(
            neighborhood_id, tuple(fields_out[j] for j in exch_idx)
        )
        deltas = self._peer_deltas(neighborhood_id)
        n_t = 1 if deltas is None else len(deltas)
        tables.extend(sends)
        tables.extend(recvs)
        slotwise = isinstance(kernel, SlotwiseKernel)
        g_kind, slab = (self._slot_gather_kind(hood, cf, use_roll)
                        if slotwise else (None, None))
        if slab is not None:
            slab, fix = slab  # static (Zs, z offsets, W); table
            tables.append(hood.dev("slab_fix", fix, sh))
        elif use_roll:
            tables.append(hood.dev("roll_wr", roll[1], sh))
            tables.append(hood.dev("roll_ws", roll[2], sh))
        scaled = uniform_offs and hood.scale_rows is not None
        if scaled:
            tables.append(hood.dev("scale_rows", hood.scale_rows, sh))
        if split:
            tables.append(hood.dev("hard_rows", hood.hard_rows, sh))
            tables.append(hood.dev("hard_nbr_rows", hood.hard_nbr_rows, sh))
            tables.append(hood.dev("hard_offs", hood.hard_offs, sh))
            tables.append(hood.dev("hard_mask", hood.hard_mask, sh))
        synth = _synth_key(cf)
        key = ("steploop", kernel, fields_in, fields_out, exch_idx, n_extra,
               L, R, uniform_offs, scaled, split, r_shifts, synth, deltas,
               slab)
        fn = self._program_cache.get(key)
        if fn is not None:
            return fn, tables, static_in

        axis, mesh, n_dev = self.axis, self.mesh, self.n_dev
        if slotwise:
            telemetry.inc("dccrg_slot_gather_programs_total", gather=g_kind)

        def body(n_steps, nrows, noffs, nmask, *args):
            send_rs = [a[0] for a in args[: n_x * n_t]]
            recv_rs = [a[0] for a in args[n_x * n_t : 2 * n_x * n_t]]
            args = args[2 * n_x * n_t:]
            nrows = nrows[0]
            row_gidx = None
            if synth is not None:
                row_gidx = nmask[0][:L] if synth[4] else None
                nmask = None  # synthesized on demand (dense) / per-slot
            else:
                nmask = nmask[0]
            wr = ws = slab_fix = None
            if slab:
                slab_fix, *args = args
                slab_fix = slab_fix[0]
            elif use_roll:
                wr, ws, *args = args
                wr, ws = wr[0], ws[0]
            if scaled:
                sc, *args = args
                sc0 = sc[0]
            if not uniform_offs:
                noffs = noffs[0]
            if split:
                hr, hnr, hof, hm, *args = args
                hr, hnr, hof, hm = hr[0], hnr[0], hof[0], hm[0]
                hrc = jnp.minimum(hr, L - 1)
            def exchange_one(fl, xi):
                # per-peer-offset ppermutes of compact buffers, or the
                # dense all_to_all fallback (see _exchange_programs)
                for j in range(n_t):
                    dlt = None if deltas is None else deltas[j]
                    payload = _halo_send(fl, send_rs[xi * n_t + j], dlt,
                                         axis, n_dev)
                    fl = _halo_scatter(fl, recv_rs[xi * n_t + j], payload, R)
                return fl.at[R - 1].set(0)
            if slotwise:
                n_slots = len(r_shifts) if use_roll else nrows.shape[1]
                if synth is not None:
                    sgidx, sbase = _synth_prep(synth, L, row_gidx=row_gidx)
                    mask_col = lambda j: _synth_col(synth, sgidx, sbase, j)
                else:
                    mask_col = lambda j: nmask[:, j]
                slot_gather = _make_slot_gather(
                    g_kind, synth, L, use_roll, r_shifts, nrows, wr, ws,
                    slab, slab_fix)
            else:
                if nmask is None:
                    nmask = _synth_mask(synth, L, row_gidx=row_gidx)
                if uniform_offs:
                    noffs = nmask[:, :, None] * noffs[None, :, :]
                    if scaled:
                        noffs = noffs * sc0[:, None, None]
                gather_nbr = _make_nbr_gather(
                    use_roll, r_shifts, L, nrows, nmask, wr, ws)

            statics = {n: a[0] for n, a in zip(static_in, args[:n_static])}
            state0 = tuple(a[0] for a in args[n_static:n_static + n_out])
            extra = args[n_static + n_out:]

            def step(_, state):
                # each phase in a named scope: the compiled ops carry it
                # in their op_name, which telemetry.publish_scopes maps
                # to the op names a profiler trace shows
                state = list(state)
                if n_dev > 1:
                    with jax.named_scope("dccrg.exchange"):
                        for xi, j in enumerate(exch_idx):
                            state[j] = exchange_one(state[j], xi)
                with jax.named_scope("dccrg.bulk"):
                    full = dict(statics)
                    full.update(zip(fields_out, state))
                    cell_fields = {n: full[n][:L] for n in fields_in}
                    if slotwise:
                        result = _run_slotwise(
                            kernel, cell_fields,
                            {n: full[n] for n in fields_in}, slot_gather,
                            _make_offs_col(uniform_offs, noffs,
                                           sc0 if scaled else None),
                            mask_col, n_slots, extra)
                    else:
                        nbr_fields = {n: gather_nbr(full[n])
                                      for n in fields_in}
                        result = kernel(cell_fields, nbr_fields, noffs,
                                        nmask, *extra)
                if split:
                    with jax.named_scope("dccrg.hard"):
                        h_cell = {n: cell_fields[n][hrc] for n in fields_in}
                        h_nbr = {n: full[n][hnr] for n in fields_in}
                        h_result = kernel(h_cell, h_nbr, hof, hm, *extra)
                        for n in fields_out:
                            result[n] = result[n].at[hr].set(
                                h_result[n].astype(result[n].dtype),
                                mode="drop")
                with jax.named_scope("dccrg.apply"):
                    for j, n in enumerate(fields_out):
                        state[j] = state[j].at[:L].set(
                            result[n].astype(state[j].dtype))
                return tuple(state)

            out = jax.lax.fori_loop(0, n_steps, step, state0)
            return tuple(o[None] for o in out)

        mapped = _shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(axis),
                      P() if uniform_offs else P(axis), P(axis))
            + (P(axis),) * (2 * n_x * n_t)
            + ((P(axis),) if slab else (P(axis), P(axis)) if use_roll else ())
            + ((P(axis),) if scaled else ())
            + ((P(axis),) * 4 if split else ())
            + (P(axis),) * (n_static + n_out) + (P(),) * n_extra,
            out_specs=(P(axis),) * n_out,
            check_vma=False,
        )

        def dccrg_step_loop(*a):
            # a fixed name: the compiled module is jit_dccrg_step_loop
            return mapped(*a)

        fn = jax.jit(dccrg_step_loop)
        self._program_cache[key] = fn
        return fn, tables, static_in

    def run_steps(
        self,
        kernel,
        fields_in,
        fields_out,
        n_steps,
        exchange_fields=None,
        neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
        extra_args=(),
    ) -> None:
        """Run ``n_steps`` fused exchange+stencil steps and install the
        results (see compile_step_loop)."""
        # the background-recommit swap point: a FINISHED plan installs
        # here, at a step boundary, before this dispatch compiles
        # against the (then previous) epoch; an unfinished build keeps
        # the loop on the live plan — zero stall (DCCRG_BG_RECOMMIT)
        if getattr(self, "_bg_build", None) is not None:
            self.bg_install()
        fields_in = tuple(fields_in)
        fields_out = tuple(fields_out)
        step_span = telemetry.span("grid.step")
        with step_span:
            fn, tables, static_in = self.compile_step_loop(
                kernel, fields_in, fields_out, exchange_fields,
                neighborhood_id, n_extra=len(extra_args),
            )
            args = (
                jnp.int32(n_steps),
                *tables,
                *(self.data[n] for n in static_in),
                *(self.data[n] for n in fields_out),
                *extra_args,
            )
            out = fn(*args)
            for n, arr in zip(fields_out, out):
                self.data[n] = arr
        if step_span is not telemetry.NULL_SPAN and telemetry.profiling():
            # the device has this call queued; the op -> phase table of
            # its program is read from the compile cache, once per program
            telemetry.publish_scopes(fn, args)
        self._mark_ckpt_dirty(fields_out)
        # DCCRG_WATCHDOG=N: self-check the stepped fields for NaN/Inf
        # every ~N steps (one device-side scalar; see resilience.py) —
        # a silent blow-up surfaces as NumericsError instead of
        # garbage physics hours later
        from . import resilience

        wd = resilience.watchdog_interval()
        if wd > 0:
            self._watchdog_accum = getattr(self, "_watchdog_accum", 0) \
                + int(n_steps)
            if self._watchdog_accum >= wd:
                self._watchdog_accum = 0
                resilience.assert_finite(self, fields_out)

    def run_steps_guarded(
        self,
        kernel,
        fields_in,
        fields_out,
        n_steps,
        exchange_fields=None,
        neighborhood_id=DEFAULT_NEIGHBORHOOD_ID,
        extra_args=(),
    ) -> str:
        """:meth:`run_steps` with graceful OOM degradation: on XLA
        ``RESOURCE_EXHAUSTED`` the dispatch walks the gather-mode
        fallback chain (current -> slot-wise roll -> dense tables),
        logging each downgrade. Returns the mode that completed
        (see resilience.guarded_step)."""
        from . import resilience

        return resilience.guarded_step(
            self, kernel, fields_in, fields_out, n_steps,
            exchange_fields=exchange_fields,
            neighborhood_id=neighborhood_id, extra_args=extra_args,
        )

    # -- load balancing (dccrg.hpp:1046-1064, 3770-4182, 8482-8720) ----

    def balance_load(self, use_zoltan: bool = True) -> None:
        """Repartition cells over devices and move their data: the
        reference's balance_load (dccrg.hpp:1046). ``use_zoltan=False``
        keeps the partition from pin requests only (parity with the
        reference's flag).

        Atomic: the three stages run in ONE transaction — a failure
        in any of them rolls the whole balance back
        (:class:`~dccrg_tpu.txn.MutationAbortedError`) and the grid
        keeps its previous partition, data placement and staging."""
        with telemetry.span("grid.balance"), \
                grid_transaction(self, op="balance_load"):
            self.initialize_balance_load(use_zoltan)
            self.continue_balance_load()
            self.finish_balance_load()

    def initialize_balance_load(self, use_zoltan: bool = True) -> None:
        """Stage 1: compute the new partition (dccrg.hpp:3770-3909).
        SFC partitioning with weights replaces Zoltan_LB_Balance;
        pin requests are merged in afterwards, as the reference merges
        pins with Zoltan output (dccrg.hpp:8552-8576)."""
        if getattr(self, "_pending_owner", None) is not None:
            raise RuntimeError("balance_load already initialized")
        with grid_transaction(self, op="initialize_balance_load"):
            self._initialize_balance_load_impl(use_zoltan)

    def _initialize_balance_load_impl(self, use_zoltan: bool) -> None:
        self._staged_balance = {}
        cells = self.plan.cells
        if use_zoltan:
            weights = None
            if self._weights:
                weights = np.ones(len(cells), dtype=np.float64)
                for cid, w in self._weights.items():
                    pos = np.searchsorted(cells, np.uint64(cid))
                    if pos < len(cells) and cells[pos] == np.uint64(cid):
                        weights[pos] = w
            # connectivity edges for the "cut" method (the role of
            # Zoltan's graph callbacks, dccrg.hpp:12091-12252). On
            # closed-form plans the of-lists are a lazy thunk whose
            # first build is O(grid); the edge arrays only depend on
            # the CELL SET (not the partition), so they are cached on
            # the grid and survive repeated balances until an AMR
            # commit changes the cells.
            edges = None
            methods = [lv.get("method") for lv in self._partitioning_levels]
            if self._lb_method == "cut" or "cut" in methods:
                # keyed on the grid's cell-set epoch (bumped by
                # _restructure whenever the cell set changes) — a
                # content fingerprint could collide across AMR commits
                ck = getattr(self, "_cells_epoch", 0)
                cached = getattr(self, "_cut_edges", None)
                if cached is not None and cached[0] == ck:
                    edges = cached[1]
                else:
                    nl = self.plan.hoods[DEFAULT_NEIGHBORHOOD_ID].lists
                    edges = (nl.of_source.astype(np.int64),
                             np.searchsorted(cells, nl.of_neighbor))
                    self._cut_edges = (ck, edges)
            if self._partitioning_levels:
                new_owner = partition_cells_hierarchical(
                    self.mapping, cells, self.n_dev,
                    self._partitioning_levels,
                    weights=weights, pins=self._pins or None, edges=edges,
                )
            else:
                new_owner = partition_cells(
                    self.mapping, cells, self.n_dev, self._lb_method,
                    weights=weights, pins=self._pins or None, edges=edges,
                )
        else:
            new_owner = self.plan.owner.copy()
            for cid, dest in self._pins.items():
                pos = np.searchsorted(cells, np.uint64(cid))
                if pos < len(cells) and cells[pos] == np.uint64(cid):
                    new_owner[pos] = dest
        faults.fire("balance.commit", phase="partition")
        self._pending_owner = new_owner

    def continue_balance_load(self, fields=None) -> None:
        """Stage 2: transfer the data of cells that change owner, for
        the given field group (dccrg.hpp:3932-3964). Callable
        repeatedly with different ``fields`` — the reference's
        multi-stage protocol for ragged payloads
        (tests/load_balancing/multi_stage_load_balancing.cpp): a field
        group captured here is what arrives at the destination at
        finish_balance_load, even if the source data (or another
        field's capacity) changes between stages. Fields never staged
        by any continue call move atomically at finish."""
        if getattr(self, "_pending_owner", None) is None:
            raise RuntimeError("initialize_balance_load not called")
        names = list(fields) if fields is not None else list(self.fields)
        for n in names:
            if n not in self.fields:
                raise KeyError(f"unknown field {n!r}")
        # validate=False: staging only captures snapshot references in
        # _staged_balance — no structure the verifiers check can change,
        # so the (repeatable) stage skips the O(grid) debug validation
        with grid_transaction(self, op="continue_balance_load",
                              validate=False):
            faults.fire("balance.commit", phase="stage")
            moving = self.plan.cells[self._pending_owner != self.plan.owner]
            for n in names:
                # DEVICE-side staging: jax arrays are immutable, so the
                # stage is a zero-copy snapshot reference — the captured
                # version survives later set()s (which install new arrays)
                # and the landing at finish is an on-device gather; moved
                # payloads never leave HBM (the reference moves balance
                # payloads rank-to-rank, dccrg.hpp:3932-3964)
                self._staged_balance[n] = (
                    moving.copy(), self.data[n] if len(moving) else None
                )

    def staged_balance_data(self, field: str):
        """(moving cell ids, values) captured by continue_balance_load
        for a field — the receiver-side peek between stages (the
        reference's receivers see arrived data in their cell_data
        before finish)."""
        ids, snap = self._staged_balance[field]
        if snap is None:
            return ids.copy(), None
        dev, rows = self._host_rows(ids)  # plan unchanged since staging
        if self._multiproc:
            # rank-local peek: only this process's moving cells, read
            # from addressable shards of the snapshot (no collective)
            lm = self._proc_local_dev[dev]
            by_dev = {s.index[0].start: s.data
                      for s in snap.addressable_shards}
            out = np.empty((int(lm.sum()),) + snap.shape[2:],
                           dtype=snap.dtype)
            ldev, lrows = dev[lm], rows[lm]
            for d in np.unique(ldev):
                m = ldev == d
                out[m] = np.asarray(by_dev[int(d)][0, lrows[m]])
            return ids[lm].copy(), out
        return ids.copy(), np.asarray(snap[dev, rows])

    def finish_balance_load(self) -> None:
        """Stage 3: install the new partition, rebuild all derived
        structure (dccrg.hpp:3980-4182), and land the staged field
        groups at their destinations. Atomic: a failure rolls back to
        the staged (post-continue) state, so finish can be retried."""
        if getattr(self, "_pending_owner", None) is None:
            raise RuntimeError("initialize_balance_load not called")
        with grid_transaction(self, op="finish_balance_load"):
            self._finish_balance_load_impl()

    def _finish_balance_load_impl(self) -> None:
        new_owner = self._pending_owner
        faults.fire("balance.commit", phase="finish")
        moved = self.plan.cells[new_owner != self.plan.owner]
        # per-device view of the movement (reference
        # get_cells_added/removed_by_balance_load, dccrg.hpp)
        self._balance_added = {
            d: moved[new_owner[np.searchsorted(self.plan.cells, moved)] == d]
            for d in range(self.n_dev)
        }
        self._balance_removed = {
            d: moved[self.plan.owner[np.searchsorted(self.plan.cells, moved)] == d]
            for d in range(self.n_dev)
        }
        self._pending_owner = None
        staged = self._staged_balance
        self._staged_balance = {}
        # old row positions of every staged group, before the plan is
        # rebuilt: the landing gathers straight from the device
        # snapshots (no host copy of moved payloads; the reference
        # moves them rank-to-rank, dccrg.hpp:3932-3964)
        old_pos = {n: self._host_rows(ids)
                   for n, (ids, snap) in staged.items() if snap is not None}
        old_R = self.plan.R
        self._restructure(self.plan.cells.copy(), new_owner)
        faults.fire("balance.commit", phase="land")
        if self._debug:
            from . import verify as _verify

            _verify.pin_requests_succeeded(self)
        sh = self._sharding()
        # all staged groups share one moving-id set per balance: build
        # the relocation index tables once, not once per field
        tbl_ids, src_dev, mask_dev = None, None, None
        for n, (ids, snap) in staged.items():
            if snap is None or n not in self.fields:
                continue
            shape, dtype = self.fields[n]
            if tbl_ids is None or not np.array_equal(ids, tbl_ids):
                od, orw = old_pos[n]
                nd, nrw = self._host_rows(ids)
                src = np.full(self.n_dev * self.plan.R, -1, dtype=np.int64)
                src[nd.astype(np.int64) * self.plan.R + nrw] = (
                    od.astype(np.int64) * old_R + orw)
                src2 = src.reshape(self.n_dev, self.plan.R)
                src_dev = put_sharded(src2, sh)
                mask_dev = put_sharded(src2 >= 0, sh)
                tbl_ids = ids
            snap_shape = tuple(snap.shape[2:])
            key = ("balance_land", snap_shape, shape, str(dtype))
            fn = self._program_cache.get(key)
            if fn is None:
                @partial(jax.jit, out_shardings=sh)
                def fn(cur, snp, srcs, mask, _ss=snap_shape, _ts=shape):
                    flat = snp.reshape((-1,) + snp.shape[2:])
                    g = flat[jnp.clip(srcs, 0)]
                    if _ss != _ts:
                        # a stage in between grew/shrank the field (the
                        # particles resize-by-count flow): pad/truncate
                        # the staged rows to the current capacity
                        fixed = jnp.zeros(g.shape[:2] + _ts, g.dtype)
                        sl = tuple(slice(0, min(a, b))
                                   for a, b in zip(_ss, _ts))
                        ix = (slice(None), slice(None)) + sl
                        g = fixed.at[ix].set(g[ix])
                    mexp = mask.reshape(mask.shape + (1,) * len(_ts))
                    return jnp.where(mexp, g.astype(cur.dtype), cur)
                self._program_cache[key] = fn
            self.data[n] = fn(self.data[n], snap, src_dev, mask_dev)

    def get_cells_added_by_balance_load(self, device: int | None = None):
        """Cells the last balance_load moved ONTO a device (all moved
        cells when device is None) — reference
        get_cells_added_by_balance_load."""
        added = getattr(self, "_balance_added", {})
        if device is not None:
            return added.get(int(device), np.empty(0, np.uint64)).copy()
        return (np.sort(np.concatenate(list(added.values())))
                if added else np.empty(0, np.uint64))

    def get_cells_removed_by_balance_load(self, device: int | None = None):
        """Cells the last balance_load moved OFF a device."""
        removed = getattr(self, "_balance_removed", {})
        if device is not None:
            return removed.get(int(device), np.empty(0, np.uint64)).copy()
        return (np.sort(np.concatenate(list(removed.values())))
                if removed else np.empty(0, np.uint64))

    def get_cells_to_send(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """{(sender, receiver): cell ids} of one halo update — the
        reference's per-peer send lists (dccrg.hpp get_cells_to_send)."""
        c = self.plan.hoods[neighborhood_id].pair_compact
        starts, ends = self._pair_groups(c)
        out = {}
        for s, e in zip(starts, ends):
            p0, q0 = int(c["p"][s]), int(c["q"][s])
            out[(p0, q0)] = self.plan.local_ids[p0][c["srow"][s:e]]
        return out

    def get_cells_to_receive(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """{(sender, receiver): cell ids} computed from the RECEIVE
        rows (ghost rows on the receiver), independently of
        get_cells_to_send's sender rows — the two must agree, and tests
        cross-check them (reference get_cells_to_receive)."""
        c = self.plan.hoods[neighborhood_id].pair_compact
        starts, ends = self._pair_groups(c)
        L = self.plan.L
        out = {}
        for s, e in zip(starts, ends):
            p0, q0 = int(c["p"][s]), int(c["q"][s])
            out[(p0, q0)] = self.plan.ghost_ids[q0][c["rrow"][s:e] - L]
        return out

    def get_neighborhood_of(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """The neighborhood's offset list (reference
        get_neighborhood_of)."""
        return np.asarray(self.neighborhoods[neighborhood_id]).copy()

    def get_neighborhood_to(self, neighborhood_id=DEFAULT_NEIGHBORHOOD_ID):
        """Negated offsets (the to-direction items)."""
        return -self.get_neighborhood_of(neighborhood_id)

    def get_pin_requests(self) -> dict:
        """Current pin requests {cell id: device} (reference
        get_pin_requests; the new/committed distinction collapses on a
        single controller)."""
        return dict(self._pins)

    # pinning (dccrg.hpp:5913-6139)

    def pin(self, cell, process: int) -> bool:
        """Force a cell onto a device across future balance_loads."""
        if not self.is_local(cell) or not 0 <= int(process) < self.n_dev:
            return False
        self._pins[int(cell)] = int(process)
        return True

    def unpin(self, cell) -> bool:
        return self._pins.pop(int(cell), None) is not None

    def unpin_local_cells(self, device: int | None = None) -> None:
        """Remove pins of cells owned by the given device (all, when
        None — host code sees every device)."""
        for cid in list(self._pins):
            if not self.is_local(cid):  # stale pin (cell gone): prune
                del self._pins[cid]
            elif device is None or self.get_process(cid) == device:
                del self._pins[cid]

    def unpin_all_cells(self) -> None:
        self._pins.clear()

    # cell weights (dccrg.hpp:6318-6380)

    def set_cell_weight(self, cell, weight: float) -> bool:
        if not self.is_local(cell):
            return False
        if weight < 0:
            return False
        self._weights[int(cell)] = float(weight)
        return True

    def get_cell_weight(self, cell) -> float:
        return self._weights.get(int(cell), 1.0)

    # partitioning options (dccrg.hpp:5590-5880). The SFC partitioner
    # has no Zoltan parameter space; options are recorded for parity
    # and 'method'/'LB_METHOD' selects the curve.

    def set_partitioning_option(self, name: str, value) -> None:
        if name.upper() in ("LB_METHOD", "METHOD"):
            self.set_load_balancing_method(str(value))
        self._partitioning_options[name] = value

    def get_partitioning_options(self, hierarchial_partitioning_level: int | None = None):
        """Flat options dict, or (with a level argument) that hierarchy
        level's option names (dccrg.hpp:5814)."""
        if hierarchial_partitioning_level is None:
            return dict(self._partitioning_options)
        lv = self._hierarchy_level(hierarchial_partitioning_level)
        return [k for k in lv if k not in ("processes", "method")]

    # hierarchical partitioning (Zoltan hierarchical replacement,
    # dccrg.hpp:5629-5880): levels group devices, e.g. (host, chip)

    def _hierarchy_level(self, level: int) -> dict:
        if not 0 <= int(level) < len(self._partitioning_levels):
            raise IndexError(
                f"no hierarchial partitioning level {level} "
                f"(have {len(self._partitioning_levels)})"
            )
        return self._partitioning_levels[int(level)]

    def add_partitioning_level(self, processes: int):
        """Append a hierarchy level whose parts hold ``processes``
        devices each (dccrg.hpp:5634). On TPU a natural two-level
        hierarchy is (devices-per-host, 1)."""
        if int(processes) < 1:
            raise ValueError("processes per part must be >= 1")
        self._partitioning_levels.append({"processes": int(processes)})
        return self

    def remove_partitioning_level(self, hierarchial_partitioning_level: int):
        self._hierarchy_level(hierarchial_partitioning_level)
        del self._partitioning_levels[int(hierarchial_partitioning_level)]
        return self

    def add_partitioning_option(self, level: int, name: str, value):
        """Set an option on a hierarchy level (dccrg.hpp:5731);
        'LB_METHOD'/'method' selects the curve for that level's split."""
        lv = self._hierarchy_level(level)
        lv[name] = value
        if name.upper() in ("LB_METHOD", "METHOD"):
            method = str(value).lower()
            if method not in PARTITION_METHODS:
                raise ValueError(
                    f"unknown method {value!r} for level {level}, have {PARTITION_METHODS}"
                )
            lv["method"] = method  # validated lowercase wins over the raw value
        return self

    def remove_partitioning_option(self, level: int, name: str):
        lv = self._hierarchy_level(level)
        lv.pop(name, None)
        if name.upper() in ("LB_METHOD", "METHOD"):
            lv.pop("method", None)
        return self

    def get_partitioning_option_value(self, level: int, name: str):
        return self._hierarchy_level(level).get(name)

    # -- adaptive mesh refinement (dccrg.hpp:2456-3507, 9730-10693) ----

    def refine_completely(self, cell) -> bool:
        """Request refinement of a cell into its 8 children
        (dccrg.hpp:2456). Committed by stop_refining()."""
        if not self.is_local(cell):
            return False
        if self.mapping.get_refinement_level(np.uint64(cell)) >= self.mapping.max_refinement_level:
            return False
        self._refines.add(int(cell))
        # a refine overrides pending unrefines of the sibling groups it
        # touches (dccrg.hpp:2517-2551); resolved again at commit
        self._unrefines.discard(int(cell))
        return True

    def unrefine_completely(self, cell) -> bool:
        """Request removal of the cell's sibling group, replaced by the
        parent (dccrg.hpp:2582)."""
        if not self.is_local(cell):
            return False
        if self.mapping.get_refinement_level(np.uint64(cell)) == 0:
            return False
        if int(cell) in self._refines:
            return False
        self._unrefines.add(int(cell))
        return True

    def dont_refine(self, cell) -> bool:
        """Forbid refinement (incl. induced) of the cell (dccrg.hpp:2766)."""
        if not self.is_local(cell):
            return False
        self._dont_refines.add(int(cell))
        return True

    def dont_unrefine(self, cell) -> bool:
        """Forbid unrefinement of the cell's sibling group (dccrg.hpp:2701)."""
        if not self.is_local(cell):
            return False
        self._dont_unrefines.add(int(cell))
        return True

    def refine_completely_at(self, coordinate) -> bool:
        """Coordinate variant (dccrg.hpp:3401-3470)."""
        c = self.get_existing_cell(coordinate)
        return bool(c != ERROR_CELL) and self.refine_completely(c)

    def unrefine_completely_at(self, coordinate) -> bool:
        c = self.get_existing_cell(coordinate)
        return bool(c != ERROR_CELL) and self.unrefine_completely(c)

    def dont_refine_at(self, coordinate) -> bool:
        c = self.get_existing_cell(coordinate)
        return bool(c != ERROR_CELL) and self.dont_refine(c)

    def dont_unrefine_at(self, coordinate) -> bool:
        c = self.get_existing_cell(coordinate)
        return bool(c != ERROR_CELL) and self.dont_unrefine(c)

    def enable_distributed_amr(self, *, kv=None, rank=None,
                               n_ranks=None, membership=None,
                               prefix="dccrg/amr", timeout=None):
        """Route this grid's adapt epochs through the fleet-wide,
        crash-consistent commit protocol (dccrg_tpu/distamr.py):
        ``stop_refining`` becomes an epoch-fenced collective install
        coordinated over the KV, every rank's local requests merged by
        a deadline-bounded proposal exchange. Returns the installed
        :class:`~dccrg_tpu.distamr.AmrCommitGroup`. A ``membership``
        lease view lets a retry after a rank death re-form the
        collective over the survivors."""
        from . import distamr

        self._amr_group = distamr.AmrCommitGroup(
            self, kv=kv, rank=rank, n_ranks=n_ranks,
            membership=membership, prefix=prefix, timeout=timeout)
        return self._amr_group

    def disable_distributed_amr(self) -> None:
        """Drop the commit group: ``stop_refining`` reverts to the
        single-controller path."""
        self._amr_group = None

    def stop_refining(self) -> np.ndarray:
        """Commit all refinement requests; returns the created cells
        (dccrg.hpp:3483-3507). Data of refined parents and removed
        cells stays readable through get_old_data() until
        clear_refined_unrefined_data().

        Atomic: a failure anywhere inside the commit (including
        injected faults) rolls the grid — requests included — back to
        its pre-commit state and re-raises as
        :class:`~dccrg_tpu.txn.MutationAbortedError`; retrying the
        commit is then safe. With ``DCCRG_DEBUG=1`` the committed
        state is verified and rolled back on a broken invariant
        (:class:`~dccrg_tpu.txn.GridInvariantError`).

        With an :meth:`enable_distributed_amr` group installed the
        commit instead runs the fleet-wide fenced protocol — same
        return value, same atomicity per rank, plus the distributed
        rollback/fencing guarantees documented in
        dccrg_tpu/distamr.py. Without one, this is byte-for-byte the
        single-controller commit."""
        group = getattr(self, "_amr_group", None)
        if group is not None:
            from . import distamr

            return distamr.distributed_stop_refining(self, group)
        return self._stop_refining_local()

    def _stop_refining_local(self) -> np.ndarray:
        from .amr import resolve_adaptation

        with telemetry.span("grid.adapt"), \
                grid_transaction(self, op="stop_refining"):
            faults.fire("adapt.commit", phase="resolve")
            res = resolve_adaptation(
                self.mapping,
                self.plan.cells,
                self.plan.owner,
                self.neighborhoods[DEFAULT_NEIGHBORHOOD_ID],
                self._refines,
                self._unrefines,
                self._dont_refines,
                self._dont_unrefines,
                pins=self._pins,
                weights=self._weights,
                topology=self.topology,
                hood_len=self._hood_len,
            )
            faults.fire("adapt.commit", phase="resolved")
            self._refines.clear()
            self._unrefines.clear()
            self._dont_refines.clear()
            self._dont_unrefines.clear()

            # preserve data of disappearing cells for the app's projection
            old_ids = np.concatenate([res.refined_parents, res.removed_cells])
            self._removed_data = {}
            if len(old_ids):
                # gather the disappearing cells' rows ON DEVICE and pull
                # only that slice (not every field's full array), through
                # the psum gather whose replicated (structure-derived) args
                # make it consistent across processes too; the sticky cap
                # keeps the program from retracing per epoch
                dev, rows = self._host_rows(old_ids)
                capn = self._sticky_cap("removed", len(old_ids))
                for name in self.fields:
                    self._removed_data[name] = (
                        old_ids, self._device_gather(name, dev, rows, cap=capn)
                    )
            else:
                self._removed_data = {name: (old_ids, None) for name in self.fields}
            faults.fire("adapt.commit", phase="preserved")
            self._removed_cells = res.removed_cells
            self._new_cells = res.new_cells
            self._unrefined_parents = res.unrefined_parents

            # dirty-set propagation into the hybrid recommit: the ids
            # that appear in exactly one of the pre/post cell lists
            self._pending_changed_cells = res.changed_cells
            self._restructure(res.cells, res.owner, defer_ok=True)
            return res.new_cells.copy()

    def _restructure(self, new_cells, new_owner, defer_ok=False):
        with telemetry.span("grid.recommit"):
            return self._restructure_impl(new_cells, new_owner,
                                          defer_ok=defer_ok)

    def _restructure_impl(self, new_cells, new_owner, defer_ok=False):
        """Rebuild the plan for a new cell set, carrying over the data
        of surviving cells (the reference's rebuild at
        dccrg.hpp:10642-10690, with data movement folded in).

        With ``DCCRG_BG_RECOMMIT=1`` and ``defer_ok`` (the
        ``stop_refining`` commit — a balance must land its staged data
        on the new plan immediately, so it never defers), the plan
        build runs on a background worker while stepping continues on
        the live plan; :meth:`run_steps` (and ``GridBatch.step``)
        installs the finished plan at the next step boundary via
        :meth:`bg_install`. Until the swap, queries and checkpoints
        reflect the previous (consistent) structure epoch.

        Data moves entirely on device: each surviving cell's (old dev,
        old row) -> (new dev, new row) relocation is ONE sharded gather
        per field (XLA inserts the cross-device collective), instead of
        pulling every field to host and re-uploading."""
        # builds are serialized per grid: a still-pending background
        # plan installs (or inline-rebuilds) before a new one starts
        self.bg_install(wait=True)
        old_plan = self.plan

        # dirty-set hint for the hybrid recommit: stop_refining knows
        # exactly which ids changed; an owner-only restructure (a
        # repartition) changes none. The hint is keyed on the previous
        # plan's cell array OBJECT so a stale hint can never alias a
        # different epoch (hybrid.build_hybrid_plan verifies identity).
        pending = getattr(self, "_pending_changed_cells", None)
        self._pending_changed_cells = None
        same_cells = (len(new_cells) == len(old_plan.cells)
                      and np.array_equal(new_cells, old_plan.cells))
        if same_cells:
            changed_hint = (old_plan.cells, np.empty(0, dtype=np.uint64))
        elif pending is not None:
            changed_hint = (old_plan.cells, pending)
        else:
            changed_hint = None

        if (defer_ok and background.bg_recommit_enabled()
                and not self._multiproc):
            self._bg_build = background.PlanBuildWorker(
                self, new_cells, new_owner, changed_hint).start()
            return

        plan = self._construct_plan(new_cells, new_owner, changed_hint)
        self._install_plan(plan, same_cells=same_cells)

    def _install_plan(self, plan, same_cells=None):
        """Install a constructed plan as the live structure epoch and
        relocate the surviving cells' data — the impure half of a
        restructure, always on the thread that owns the grid (the
        step-boundary swap point for background builds)."""
        old_plan = self.plan
        old_R = old_plan.R
        # any restructure (cell-set change OR repartition) ends the
        # delta-checkpoint structure epoch: the offset table and the
        # per-rank slice layout both derive from cells/owners, so the
        # next periodic save must be a full keyframe (the AMR commit's
        # AmrResult.changed_cells dirty seed feeds the plan rebuild;
        # for checkpointing the whole payload is conservatively dirty)
        self._ckpt_epoch = getattr(self, "_ckpt_epoch", 0) + 1
        self._mark_ckpt_dirty()
        new_cells = plan.cells
        if same_cells is None:
            same_cells = (len(new_cells) == len(old_plan.cells)
                          and np.array_equal(new_cells, old_plan.cells))
        if not same_cells:
            # cell-set epoch: caches keyed on the cell SET (not the
            # partition) — e.g. the cut partitioner's edge arrays —
            # invalidate here and nowhere else
            self._cells_epoch = getattr(self, "_cells_epoch", 0) + 1
        surviving = new_cells[np.isin(new_cells, old_plan.cells)]
        old_dev, old_rows = self._host_rows(surviving)
        old_flat = old_dev.astype(np.int64) * old_R + old_rows

        self._finish_plan(plan)
        faults.fire("grid.restructure", phase="planned")
        new_dev, new_rows = self._host_rows(surviving)
        new_flat = new_dev.astype(np.int64) * self.plan.R + new_rows

        src = np.full(self.n_dev * self.plan.R, -1, dtype=np.int64)
        src[new_flat] = old_flat
        sh = self._sharding()
        # On accelerators every host round-trip crosses the interconnect
        # — move data with an on-device gather. On the CPU backend the
        # "transfer" is a memcpy and the host scatter is cheaper than
        # compiling a per-epoch-shape gather program.
        if (self._on_accelerator() or self._multiproc
                or os.environ.get("DCCRG_DEVICE_RESTRUCTURE") == "1"):
            src2 = src.reshape(self.n_dev, self.plan.R)
            src_dev = put_sharded(src2, sh)
            mask_dev = put_sharded(src2 >= 0, sh)
            n_dev = self.n_dev

            def move_for(n_extra_dims):
                key = ("restructure_move", n_extra_dims)
                fn = self._program_cache.get(key)
                if fn is None:
                    @partial(jax.jit, out_shardings=sh)
                    def fn(old, srcs, mask):
                        flat = old.reshape((-1,) + old.shape[2:])
                        g = flat[jnp.clip(srcs, 0)]
                        return jnp.where(
                            mask.reshape(mask.shape + (1,) * n_extra_dims), g, 0
                        )
                    self._program_cache[key] = fn
                return fn

            for name, (shape, dtype) in self.fields.items():
                self.data[name] = move_for(len(shape))(
                    self.data[name], src_dev, mask_dev
                )
        else:
            keep = src >= 0
            srcc = np.clip(src, 0, None)
            for name, (shape, dtype) in self.fields.items():
                old_host = np.asarray(self.data[name]).reshape(
                    (self.n_dev * old_R,) + shape
                )
                arr = np.where(
                    keep.reshape((-1,) + (1,) * len(shape)), old_host[srcc], 0
                ).astype(dtype, copy=False)
                self.data[name] = jnp.asarray(
                    arr.reshape((self.n_dev, self.plan.R) + shape), device=sh
                )
        faults.fire("grid.restructure", phase="moved")

        # covered by the transaction's post-commit verify_all when one
        # is active (every mutation path); kept for direct callers
        if self._debug and not getattr(self, "_txn_depth", 0):
            from . import verify as _verify

            _verify.verify_user_data(self)

    # -- background recommit (DCCRG_BG_RECOMMIT; see background.py) ----

    def bg_pending(self) -> bool:
        """True while a background plan build is in flight or awaiting
        its step-boundary swap."""
        return getattr(self, "_bg_build", None) is not None

    def bg_install(self, wait: bool = False) -> bool:
        """The step-boundary swap point: install the background-built
        plan if one is finished (``wait=True`` blocks for it — the
        residual stall lands in ``dccrg_recommit_stall_seconds``) and
        relocate the surviving cells' data, exactly as the synchronous
        restructure would have. A worker crash falls back to the
        inline rebuild here. The install runs inside its own
        transaction, so a failure mid-swap (injected faults included)
        rolls back to the live pre-swap epoch and surfaces as
        MutationAbortedError. Returns True when a plan was installed."""
        bg = getattr(self, "_bg_build", None)
        if bg is None:
            return False
        if not bg.ready() and not wait:
            return False
        bg.wait()
        # consumed BEFORE the swap transaction: its entry barrier (and
        # any nested mutation) must not re-enter this install
        self._bg_build = None
        t0 = time.perf_counter()
        with telemetry.span("grid.recommit.swap"), \
                grid_transaction(self, op="bg_recommit_swap"):
            if bg.error is not None:
                logger.warning(
                    "background recommit worker failed (%s: %s); "
                    "rebuilding inline", type(bg.error).__name__, bg.error)
                plan = self._construct_plan(bg.cells, bg.owner,
                                            bg.changed_hint)
            else:
                plan = bg.plan
            self._install_plan(plan)
        telemetry.observe("dccrg_recommit_stall_seconds",
                          time.perf_counter() - t0, where="swap")
        return True

    def bg_discard(self) -> None:
        """Drop a pending background build without installing it (the
        transaction-rollback path: an aborted mutation must leave the
        live plan AND the snapshot plan exactly as they were). Blocks
        until the worker thread has actually stopped touching the
        arena; the orphaned build generation's buffers are reclaimed
        by the next build's ``arena.begin`` (it is never protected)."""
        bg = getattr(self, "_bg_build", None)
        if bg is None:
            return
        bg.done.wait()
        self._bg_build = None

    def _prewarm_plan(self, plan) -> None:
        """Pre-materialize the lazily-derived per-hood tables the first
        post-swap dispatch would otherwise compute on the step loop
        (the roll-plan affine decomposition — an O(L*S) numpy pass),
        with the same capacity function the compile path passes. Runs
        on the background worker; best-effort (a failure here simply
        re-surfaces at compile time)."""
        try:
            for hid, hood in plan.hoods.items():
                if hood.closed_form is not None:
                    hood.roll_plan(plan.L)
                elif hood.offs_const is not None and self._use_roll_gather():
                    hood.roll_plan(plan.L, cap=lambda n, hid=hid:
                                   self._sticky_cap(("rollW", hid), n))
        except Exception:  # noqa: BLE001 - prewarm must never kill a build
            logger.debug("plan prewarm failed", exc_info=True)

    def get_removed_cells(self) -> np.ndarray:
        """Cells removed by the last stop_refining (dccrg.hpp:3519)."""
        return self._removed_cells.copy()

    def get_old_data(self, field, ids):
        """Data of cells that disappeared in the last stop_refining
        (refined parents and removed children) — the reference keeps
        these reachable via grid[cell] until clear (dccrg.hpp:10355)."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.uint64))
        stored_ids, values = self._removed_data[field]
        order = np.argsort(stored_ids, kind="stable")
        sorted_ids = stored_ids[order]
        pos = np.searchsorted(sorted_ids, ids)
        if np.any(pos >= len(sorted_ids)) or np.any(sorted_ids[np.minimum(pos, len(sorted_ids) - 1)] != ids):
            raise KeyError("cell not among refined/removed cells")
        return values[order][pos]

    def clear_refined_unrefined_data(self) -> None:
        """Drop the preserved old data (dccrg.hpp:5550)."""
        self._removed_data = {}
        self._removed_cells = np.empty(0, np.uint64)
        self._new_cells = np.empty(0, np.uint64)

    # vectorized projection helpers (the idiomatic TPU versions of the
    # per-cell loops in tests/advection/adapter.hpp:229-301)

    def _owned_subset(self, ids):
        """The subset of ``ids`` on this process's devices — the
        projection helpers write rank-locally on multi-process meshes
        (the reference projects each process's own cells; under
        distributed AMR the commit's ``_new_cells``/parents span the
        whole fleet, and each peer projects its own share)."""
        if len(ids) == 0 or not self._multiproc:
            return ids
        dev, _rows = self._host_rows(ids)
        return ids[self._proc_local_dev[dev]]

    def assign_children_from_parents(self, fields=None) -> None:
        """Copy each new child's value from its refined parent
        (process-local on multi-process meshes)."""
        new = self._owned_subset(self._new_cells)
        if len(new) == 0:
            return
        parents = self.mapping.get_parent(new)
        for name in fields if fields is not None else self.fields:
            self.set(name, new, self.get_old_data(name, parents))

    def average_parents_from_children(self, fields=None) -> None:
        """Set each unrefined parent to the mean of its removed
        children (process-local on multi-process meshes)."""
        if len(self._removed_cells) == 0:
            return
        parents = self._owned_subset(self._unrefined_parents)
        if len(parents) == 0:
            return
        kids = self.mapping.get_all_children(parents)  # [n, 8]
        for name in fields if fields is not None else self.fields:
            vals = self.get_old_data(name, kids.reshape(-1))
            fshape = vals.shape[1:]
            vals = vals.reshape((len(parents), 8) + fshape).mean(axis=1)
            self.set(name, parents, vals)

    def load_cells(self, cells) -> None:
        """Replace the grid structure with an arbitrary valid cell set
        (the reference's load_cells, dccrg.hpp:3669-3738); data of all
        cells is reset."""
        from .neighbors import verify_tiling
        from .partition import partition_cells

        cells = np.sort(np.asarray(cells, dtype=np.uint64))
        verify_tiling(self.mapping, cells)
        with grid_transaction(self, op="load_cells"):
            owner = partition_cells(
                self.mapping, cells, self.n_dev, self._lb_method,
                pins=self._pins or None
            )
            self._cells_epoch = getattr(self, "_cells_epoch", 0) + 1
            self._ckpt_epoch = getattr(self, "_ckpt_epoch", 0) + 1
            self._build_plan(cells, owner)
            self._allocate_fields()
            if self._debug:
                from . import verify as _verify

                _verify.pin_requests_succeeded(self)

    # -- VTK output (dccrg.hpp:3320-3392) ------------------------------

    def write_vtk_file(self, filename: str, fields=None) -> None:
        from .utils.vtk import write_vtk_file

        write_vtk_file(self, filename, fields=fields)

    # -- checkpoint / restart (dccrg.hpp:1109-2426) --------------------

    def save_grid_data(self, filename: str, header: bytes = b"",
                       variable=None, *, sidecar: bool = False,
                       sidecar_chunk_bytes: int | None = None) -> None:
        """Write the pinned ``.dc`` bytes. On multi-process meshes the
        write is a TWO-PHASE COMMIT (slices into ``<file>.mp-tmp``,
        CRC exchange at a timeout-guarded barrier, verify + atomic
        rename by the committing rank); ``sidecar=True`` has that rank
        also write the resilience CRC32 sidecar with the per-rank
        slice table. Single-controller saves ignore the sidecar kwargs
        (use :meth:`save_checkpoint`)."""
        from .checkpoint import save_grid_data

        save_grid_data(self, filename, header, variable=variable,
                       sidecar=sidecar,
                       sidecar_chunk_bytes=sidecar_chunk_bytes)

    def load_grid_data(self, filename: str, header_size: int = 0,
                       variable=None) -> bytes:
        from .checkpoint import load_grid_data

        return load_grid_data(self, filename, header_size, variable=variable)

    @classmethod
    def from_file(cls, filename: str, cell_data, mesh: Mesh | None = None,
                  header_size: int = 0, variable=None):
        """Restart from nothing but a .dc file: reconstructs mapping,
        topology, geometry and the AMR cell set from the file metadata
        (the reference's load_grid_data, dccrg.hpp:1815-2105), then
        streams the payloads. Returns ``(grid, header)``."""
        from .checkpoint import load_grid

        return load_grid(filename, cell_data, mesh=mesh,
                         header_size=header_size, variable=variable)

    def save_checkpoint(self, filename: str, header: bytes = b"",
                        variable=None) -> str:
        """Atomic, checksummed checkpoint: the pinned ``.dc`` bytes
        (identical to :meth:`save_grid_data`) written via temp file +
        fsync + rename, with a per-chunk CRC32 sidecar ``<file>.crc``
        (see resilience.save_checkpoint)."""
        from . import resilience

        return resilience.save_checkpoint(self, filename, header=header,
                                          variable=variable)

    @classmethod
    def load_checkpoint(cls, filename: str, cell_data, mesh: Mesh | None = None,
                        header_size: int = 0, variable=None,
                        strict: bool = True):
        """Restart from a checkpoint with integrity verification:
        ``(grid, header, report)``; corrupt chunks raise (strict) or
        are salvaged (see resilience.load_checkpoint)."""
        from . import resilience

        return resilience.load_checkpoint(
            filename, cell_data, mesh=mesh, header_size=header_size,
            variable=variable, strict=strict)

    # -- misc parity ---------------------------------------------------

    def get_comm_size(self) -> int:
        """Device count (the reference's MPI communicator size)."""
        return self.n_dev

    def get_number_of_cells(self) -> int:
        return len(self.plan.cells)

    def get_existing_cell_from_indices(self, indices,
                                       minimum_refinement_level: int = 0,
                                       maximum_refinement_level: int | None = None):
        """Smallest existing cell containing the given smallest-cell
        indices within a refinement-level range (reference
        get_existing_cell(indices, min, max), dccrg.hpp:11414-11447)."""
        if maximum_refinement_level is None:
            maximum_refinement_level = self.mapping.max_refinement_level
        idx = np.asarray(indices, dtype=np.uint64)
        if np.any(idx >= self.mapping.get_index_length()):
            return ERROR_CELL
        for lvl in range(maximum_refinement_level,
                         minimum_refinement_level - 1, -1):
            c = self.mapping.get_cell_from_indices(idx, lvl)
            if c != ERROR_CELL and self._cell_pos(c) is not None:
                return np.uint64(c)
        return ERROR_CELL

    def get_existing_cell(self, coordinate):
        """Smallest existing cell containing a coordinate (reference
        get_existing_cell, dccrg.hpp:11414-11447)."""
        for lvl in range(self.mapping.max_refinement_level, -1, -1):
            c = self.geometry.get_cell(lvl, coordinate)
            if c != ERROR_CELL:
                pos = np.searchsorted(self.plan.cells, c)
                if pos < len(self.plan.cells) and self.plan.cells[pos] == c:
                    return np.uint64(c)
        return ERROR_CELL

    def get_maximum_refinement_level_difference(self) -> int:
        """Parity with dccrg.hpp:6752."""
        return 1

    def is_local(self, cell, device=None) -> bool:
        """Whether ``cell`` is owned by ``device``.

        The reference's ``is_local`` means "owned by *this* process"
        (its cell_process lookup against its own rank). Here host code
        is a single controller that sees every device, so there is no
        implicit "this device": with ``device=None`` the host-global
        view applies and every *existing* cell is local (False only for
        unknown ids). That is deliberate — the reference uses is_local
        to gate per-rank request APIs (refine_completely, pin, ...); on
        the single-controller model the host is allowed to request
        changes to any cell, so those guards only reject unknown ids.
        Pass an explicit ``device`` for the reference's owned-by-rank
        meaning."""
        pos = self._cell_pos(cell)
        if pos is None:
            return False
        if device is None:
            return True
        return int(self.plan.owner[pos]) == int(device)

    def get_process(self, cell) -> int:
        """Owning device of a cell (reference cell_process lookup)."""
        pos = self._cell_pos(cell)
        if pos is None:
            raise ValueError(f"unknown cell {cell}")
        return int(self.plan.owner[pos])
