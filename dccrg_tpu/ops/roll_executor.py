"""Roll-plan-driven Pallas bulk executor.

The roll decomposition (`_HoodPlan.roll_plan`, grid.py) reduces any
rectangular stencil on a closed-form uniform plan to S flat axis
shifts plus a sparse set of wrong rows — exactly the shape a tiled,
double-buffered, temporally-blocked Pallas kernel wants. This module
promotes the hand-written 512^3 benchmark kernel's structure
(ops/advection_kernel.py: manual HBM->VMEM DMAs with slot-parity
double buffering, in-VMEM shifted views, scalar-prefetched step
parameters) into a *generic* executor compiled from any grid's roll
plan + SlotwiseKernel flux function:

- every field's flat row array ``[L]`` (L a multiple of 1024) is
  viewed as ``[G, 8, 128]`` register-tile groups; tiles span ``TG``
  groups plus wrap-around halo groups sized by the shift reach, so
  every DMA slice is group-granular on the *major* (untiled) axis —
  always alignment-legal, mirroring the advection kernel's trick;
- inside the kernel each flat shift ``s = 128*q + r`` becomes a row
  slice (``q``) plus a lane rotate (``r``: a concat of two row-shifted
  views) of the VMEM window — no gather ops ever touch HBM;
- the slot validity mask is synthesized from the global flat index
  (the same arithmetic as grid._synth_col), so no [L, S] mask array
  exists on device;
- ``steps_per_pass`` > 1 applies the flux update that many times per
  HBM pass over a shrinking in-VMEM region (temporal blocking),
  dividing HBM traffic per cell-update accordingly;
- the sparse wrong rows (periodic wraps, capacity-padding reads) are
  repaired by a **fused scatter epilogue** in the same jitted program:
  a host-precomputed cascade of dilated row sets is re-run through the
  reference XLA slot loop with exact gathered neighbors, so fixup rows
  are bitwise identical to the XLA roll path at every step.

`compile_bulk_step_loop` plugs this into ``Grid.run_steps`` behind the
``DCCRG_BULK=pallas`` mode switch (grid.compile_step_loop consults it;
with DCCRG_BULK unset the pre-executor XLA program is compiled
bit-identically — the negative pin). `make_fleet_bulk_step` builds the
batched variant (an extra leading Pallas grid dimension over fleet
slots) that GridBatch buckets select through the fleet's bulk kernel
registry.

Eligibility (anything else falls back to the XLA roll path): a
single-device closed-form plan, scalar cell fields, a SlotwiseKernel,
``L % 1024 == 0``, and halos that fit the tiling. On CPU backends the
kernel runs under Pallas TPU interpret mode (CI's parity suite,
tests/test_bulk_executor.py); lane rotates (minor-dim concats) and
in-kernel integer div/mod are Mosaic-supported: the executor compiles
for a v5e at 512^3 (tests/test_chip_compile.py at 128^3) but has not
been timed on the chip.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_GROUP = _LANES * _SUBLANES  # flat cells per (8, 128) register tile


def bulk_mode() -> str:
    """The DCCRG_BULK mode switch: '' / 'xla' (default — the XLA roll
    path, bitwise the pre-executor program), 'pallas' (bulk executor
    where eligible, XLA fallback otherwise)."""
    return os.environ.get("DCCRG_BULK", "").strip().lower()


def bulk_steps_per_pass() -> int:
    """DCCRG_BULK_SPP: temporal blocking depth of the Pallas pass
    (sub-steps per HBM pass), clamped to 1..8 like the benchmark
    kernel's steps_per_pass."""
    try:
        k = int(os.environ.get("DCCRG_BULK_SPP", "1"))
    except ValueError:
        k = 1
    return max(1, min(k, 8))


# ---------------------------------------------------------------------
# static pass geometry
# ---------------------------------------------------------------------

class RollPassSpec:
    """Static geometry of one bulk pass, derived from the roll plan's
    flat shifts: the [G, 8, 128] group view, tile/halo extents and the
    per-sub-step shrinking compute regions."""

    def __init__(self, shifts, dims, periodic, offs_cells, n0, L, k,
                 tile_groups=None):
        self.shifts = tuple(int(s) for s in shifts)
        self.dims = tuple(int(d) for d in dims)
        self.periodic = tuple(bool(p) for p in periodic)
        self.offs_cells = tuple(tuple(int(v) for v in o)
                                for o in offs_cells)
        self.n0 = int(n0)
        self.L = int(L)
        self.k = int(k)
        if self.L % _GROUP:
            raise ValueError(f"L={L} not a multiple of {_GROUP}")
        self.G = self.L // _GROUP
        self.M = self.L // _LANES  # rows of 128 lanes
        # per slot: row shift q (floor) and lane rotate r in [0, 128)
        self.qr = [(s // _LANES, s % _LANES) for s in self.shifts]
        # per-sub-step row margins: slot j needs prev-region rows
        # [q_j, q_j + (r_j > 0)]
        self.a_r = max(0, max((-q for q, _r in self.qr), default=0))
        self.b_r = max(0, max((q + (1 if r else 0)
                               for q, r in self.qr), default=0))
        hm_rows, hp_rows = self.k * self.a_r, self.k * self.b_r
        self.Hm_g = -(-hm_rows // _SUBLANES)
        self.Hp_g = -(-hp_rows // _SUBLANES)
        if max(self.Hm_g, self.Hp_g) > self.G:
            raise ValueError("halo exceeds the grid (grid too small "
                             "for this steps_per_pass)")
        if tile_groups is None:
            env = os.environ.get("DCCRG_BULK_TILE_G")
            tile_groups = int(env) if env else None
        lo = max(self.Hm_g, self.Hp_g, 1)
        if tile_groups is not None:
            if (self.G % tile_groups) or tile_groups < lo:
                raise ValueError(
                    f"tile_groups={tile_groups} must divide G={self.G} "
                    f"and be >= {lo}")
            self.TG = int(tile_groups)
        else:
            self.TG = next(d for d in range(lo, self.G + 1)
                           if self.G % d == 0)
        self.n_tiles = self.G // self.TG
        self.WG = self.TG + self.Hm_g + self.Hp_g  # window groups
        self.WR = self.WG * _SUBLANES  # window rows

    def region(self, t):
        """Row bounds [lo, hi) of sub-step ``t``'s compute region
        within the window (t = 0 is the full input window)."""
        return t * self.a_r, self.WR - t * self.b_r


# ---------------------------------------------------------------------
# in-kernel helpers
# ---------------------------------------------------------------------

def _shifted_view(arr, base, length, q, r):
    """View of ``arr`` rows [base+q, ...) lane-rotated by ``r``: the
    flat-index shift ``128*q + r`` over the row-major [rows, 128]
    window — pure slices and one minor-dim concat."""
    a = arr[base + q: base + q + length]
    if r == 0:
        return a
    b = arr[base + q + 1: base + q + 1 + length]
    return jnp.concatenate([a[:, r:], b[:, :r]], axis=1)


def _mask_col(spec, i, base_valid, j):
    """Slot ``j`` validity over global flat indices ``i`` — the same
    closed-form arithmetic as grid._synth_col, evaluated per tile
    inside the kernel instead of per [L] column."""
    nx, ny, nz = spec.dims
    x = i % nx
    y = (i // nx) % ny
    z = i // (nx * ny)
    ox, oy, oz = spec.offs_cells[j]
    v = base_valid
    for coord, o, nd, per in ((x, ox, nx, spec.periodic[0]),
                              (y, oy, ny, spec.periodic[1]),
                              (z, oz, nz, spec.periodic[2])):
        if o != 0 and not per:
            t = coord + o
            v = v & (t >= 0) & (t < nd)
    return v


# ---------------------------------------------------------------------
# the bulk Pallas pass
# ---------------------------------------------------------------------

def make_bulk_pass(spec, kernel, fields_in, fields_out, dtypes,
                   offs_np, extra_dtypes, interpret, batch=None):
    """Compile one bulk pass: ``fn(extras_arr, *in_groups) -> outs``.

    ``in_groups`` are the fields_in arrays viewed as [G, 8, 128]
    ([B, G, 8, 128] when ``batch`` is an int — the fleet's slot axis
    becomes a leading Pallas grid dimension), ``extras_arr`` is the
    float32-packed per-pass scalars ([E] / [B, E]). Outputs are the
    fields_out group views after ``spec.k`` flux sub-steps, with wrap
    rows still un-fixed (the scatter epilogue repairs them)."""
    F = len(fields_in)
    n_out = len(fields_out)
    TG, WG, Hm_g, Hp_g, G = spec.TG, spec.WG, spec.Hm_g, spec.Hp_g, spec.G
    n_tiles, WR, M = spec.n_tiles, spec.WR, spec.M
    a_r, k = spec.a_r, spec.k
    carried = [f for f in fields_in if f in fields_out]

    def body(ex_ref, *refs):
        ins = refs[:F]
        outs = refs[F:F + n_out]
        bodies = refs[F + n_out:F + n_out + F]
        sems = refs[-1]
        if batch is None:
            b = None
            n = pl.program_id(0)
            lin = n
            total = n_tiles
        else:
            b = pl.program_id(0)
            n = pl.program_id(1)
            lin = b * n_tiles + n
            total = batch * n_tiles
        two = jnp.int32(2)  # keep int32 under jax_enable_x64
        slot = jax.lax.rem(lin, two)
        nxt = jax.lax.rem(lin + jnp.int32(1), two)

        def dmas(sl, li):
            if batch is None:
                bi, ni = None, li
            else:
                bi = li // jnp.int32(n_tiles)
                ni = li - bi * jnp.int32(n_tiles)
            t0 = pl.multiple_of(ni * TG, TG)
            cps = []
            for fi in range(F):
                src = ins[fi]

                def at(g0, cnt):
                    if batch is None:
                        return src.at[pl.ds(g0, cnt)]
                    return src.at[bi, pl.ds(g0, cnt)]

                cps.append(pltpu.make_async_copy(
                    at(t0, TG),
                    bodies[fi].at[sl, pl.ds(Hm_g, TG)],
                    sems.at[sl, 3 * fi],
                ))
                if Hm_g:
                    glo = jax.lax.rem(t0 - jnp.int32(Hm_g) + jnp.int32(G),
                                      jnp.int32(G))
                    cps.append(pltpu.make_async_copy(
                        at(glo, Hm_g),
                        bodies[fi].at[sl, pl.ds(0, Hm_g)],
                        sems.at[sl, 3 * fi + 1],
                    ))
                if Hp_g:
                    ghi = jax.lax.rem(t0 + jnp.int32(TG), jnp.int32(G))
                    cps.append(pltpu.make_async_copy(
                        at(ghi, Hp_g),
                        bodies[fi].at[sl, pl.ds(Hm_g + TG, Hp_g)],
                        sems.at[sl, 3 * fi + 2],
                    ))
            return cps

        @pl.when(lin == 0)
        def _():
            for c in dmas(jnp.int32(0), jnp.int32(0)):
                c.start()

        @pl.when(lin + 1 < total)
        def _():
            for c in dmas(nxt, lin + jnp.int32(1)):
                c.start()

        for c in dmas(slot, lin):
            c.wait()

        windows = {f: bodies[fi][slot].reshape(WR, _LANES)
                   for fi, f in enumerate(fields_in)}
        extras = tuple(
            (ex_ref[i] if batch is None else ex_ref[b, i]).astype(dt)
            for i, dt in enumerate(extra_dtypes))
        # global row index of window row 0 (mod M: the flat roll wraps
        # mod L, and L = M * 128 keeps the lane structure intact)
        row0 = (n * jnp.int32(TG) - jnp.int32(Hm_g)) * jnp.int32(_SUBLANES)

        carry = {}
        for t in range(1, k + 1):
            lo, hi = spec.region(t)
            length = hi - lo
            m_io = jax.lax.broadcasted_iota(jnp.int32, (length, _LANES), 0)
            c_io = jax.lax.broadcasted_iota(jnp.int32, (length, _LANES), 1)
            gr = jnp.remainder(row0 + jnp.int32(lo) + m_io, jnp.int32(M))
            i = gr * jnp.int32(_LANES) + c_io
            base_valid = i < spec.n0

            def src(f):
                # carried fields read sub-step t-1 values; statics read
                # the window — both with the region-local base offset
                if t > 1 and f in carried:
                    return carry[f], a_r
                return windows[f], lo

            cell = {}
            for f in fields_in:
                arr, base = src(f)
                cell[f] = arr[base: base + length]
            acc = kernel.init(cell, *extras)
            for j, (q, r) in enumerate(spec.qr):
                mj = _mask_col(spec, i, base_valid, j)
                nbr = {}
                for f in fields_in:
                    arr, base = src(f)
                    v = _shifted_view(arr, base, length, q, r)
                    nbr[f] = jnp.where(mj, v, jnp.zeros((), v.dtype))
                acc = kernel.slot(acc, cell, nbr, offs_np[j], mj, *extras)
            res = kernel.finish(acc, cell, *extras)
            carry = {f: res[f].astype(dtypes[f]) for f in fields_out}

        body_lo = Hm_g * _SUBLANES - spec.region(k)[0]
        for oi, f in enumerate(fields_out):
            out = carry[f][body_lo: body_lo + TG * _SUBLANES]
            out = out.reshape(TG, _SUBLANES, _LANES)
            if batch is None:
                outs[oi][...] = out
            else:
                outs[oi][0] = out

    if batch is None:
        grid = (n_tiles,)
        out_block = ((TG, _SUBLANES, _LANES),
                     lambda n, _ex: (n, 0, 0))
        out_shapes = [jax.ShapeDtypeStruct((G, _SUBLANES, _LANES),
                                           dtypes[f]) for f in fields_out]
    else:
        grid = (batch, n_tiles)
        out_block = ((1, TG, _SUBLANES, _LANES),
                     lambda b, n, _ex: (b, n, 0, 0))
        out_shapes = [jax.ShapeDtypeStruct((batch, G, _SUBLANES, _LANES),
                                           dtypes[f]) for f in fields_out]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * F,
        out_specs=[pl.BlockSpec(out_block[0], out_block[1],
                                memory_space=pltpu.VMEM)
                   for _ in fields_out],
        scratch_shapes=[pltpu.VMEM((2, WG, _SUBLANES, _LANES),
                                   dtypes[f]) for f in fields_in]
        + [pltpu.SemaphoreType.DMA((2, 3 * F))],
    )

    cells = spec.L * (batch or 1)
    call = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        interpret=pltpu.InterpretParams() if interpret else False,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            10 * len(spec.shifts) * k * cells,
            bytes_accessed=2 * sum(jnp.dtype(dtypes[f]).itemsize
                                   for f in fields_in) * cells,
            transcendentals=0,
        ),
    )

    def fn(extras_arr, *in_groups):
        out = call(extras_arr, *in_groups)
        return out if isinstance(out, (list, tuple)) else (out,)

    return fn


# ---------------------------------------------------------------------
# the fixup scatter epilogue
# ---------------------------------------------------------------------

def _flat_coords(rows, dims):
    nx, ny, _nz = dims
    return rows % nx, (rows // nx) % ny, rows // (nx * ny)


def _apply_offset(rows, off, dims, periodic, n0):
    """(valid, flat target) of stepping ``rows`` by cell offset
    ``off`` under the grid's periodicity — host-side mirror of the
    device mask/neighbor arithmetic."""
    rows = np.asarray(rows, dtype=np.int64)
    nx, ny, nz = dims
    x, y, z = _flat_coords(rows, dims)
    t = [x + off[0], y + off[1], z + off[2]]
    valid = rows < n0
    for d, nd in enumerate((nx, ny, nz)):
        if periodic[d]:
            t[d] = t[d] % nd
        else:
            valid = valid & (t[d] >= 0) & (t[d] < nd)
    tgt = t[0] + nx * (t[1] + ny * t[2])
    return valid, np.where(valid, tgt, 0)


def build_epilogue_sets(spec, wrong_rows_host):
    """Host tables of the fixup cascade for a ``spec.k``-deep pass.

    ``W`` = rows whose flat roll is wrong for some slot. After ``k``
    in-kernel sub-steps the wrongness has spread ``k-1`` stencil hops,
    and repairing it needs pass-input values ``k`` hops further out:
    ``need_k = W ∪ D(W) ∪ ... ∪ D^{k-1}(W)`` (D = inverse-neighbor
    dilation) re-run for k sub-steps over the nested supersets
    ``need_{t-1} = need_t ∪ N(need_t)`` (N = true neighbors), all
    gathers reading exact neighbor rows. Returns ``[(rows_t [Nt],
    nbr_rows_t [Nt, S], mask_t [Nt, S])]`` for t = 1..k (unpadded)."""
    L, k = spec.L, spec.k
    dims, periodic, n0 = spec.dims, spec.periodic, spec.n0
    offs = spec.offs_cells
    W = np.unique(np.asarray(wrong_rows_host, dtype=np.int64).ravel())
    W = W[W < L]

    def dilate_inverse(rows):
        parts = [rows]
        for o in offs:
            inv = (-o[0], -o[1], -o[2])
            valid, tgt = _apply_offset(rows, inv, dims, periodic, n0)
            # r' depends on rows via slot j iff r' + o_j lands on them
            # with a VALID mask at r'
            parts.append(tgt[valid])
        return np.unique(np.concatenate(parts))

    def dilate_forward(rows):
        parts = [rows]
        for o in offs:
            valid, tgt = _apply_offset(rows, o, dims, periodic, n0)
            parts.append(tgt[valid])
        return np.unique(np.concatenate(parts))

    wrong = W
    for _ in range(k - 1):
        wrong = np.union1d(W, dilate_inverse(wrong))
    need = [None] * (k + 1)
    need[k] = wrong
    for t in range(k - 1, 0, -1):
        need[t] = dilate_forward(need[t + 1])

    tables = []
    for t in range(1, k + 1):
        rows = need[t].astype(np.int64)
        S = len(offs)
        nbr = np.zeros((len(rows), S), dtype=np.int32)
        mask = np.zeros((len(rows), S), dtype=bool)
        for j, o in enumerate(offs):
            valid, tgt = _apply_offset(rows, o, dims, periodic, n0)
            nbr[:, j] = tgt.astype(np.int32)
            mask[:, j] = valid
        tables.append((rows.astype(np.int32), nbr, mask))
    return tables


def pad_epilogue_tables(tables, caps, L):
    """Pad the cascade tables to sticky row capacities (rows pad with
    ``L`` — gathers clamp, scatters drop) so the compiled program
    survives bucketed structure epochs."""
    out = []
    for (rows, nbr, mask), cap in zip(tables, caps):
        n = len(rows)
        rows_p = np.full(cap, L, dtype=np.int32)
        nbr_p = np.zeros((cap, nbr.shape[1]), dtype=np.int32)
        mask_p = np.zeros((cap, nbr.shape[1]), dtype=bool)
        rows_p[:n] = rows
        nbr_p[:n] = nbr
        mask_p[:n] = mask
        out.append((rows_p, nbr_p, mask_p))
    return out


def make_epilogue(kernel, fields_in, fields_out, dtypes, offs_const, L,
                  n_tables):
    """``fn(cur, tables_flat, extras) -> cur`` — the in-program fixup
    cascade: for each sub-step t, re-run the reference slot loop over
    the padded row set with exact gathered neighbors and scatter the
    results back, leaving fixup rows bitwise equal to the XLA roll
    path. ``cur`` maps every involved field to its [L] view. The slot
    loop is inlined (without the dense adapter's optimization_barrier
    — a scheduling hint with no effect on values, and vmap has no
    batching rule for it) so the fleet can vmap this over slots."""
    offs_dev = jnp.asarray(offs_const)
    S = len(offs_const)

    def fn(cur, tables_flat, extras):
        cur = dict(cur)
        for t in range(n_tables):
            rows, nbr, mask = tables_flat[3 * t: 3 * t + 3]
            rc = jnp.minimum(rows, L - 1)
            nc = jnp.minimum(nbr, L - 1)
            cell = {f: cur[f][rc] for f in fields_in}
            nbrv = {}
            for f in fields_in:
                g = cur[f][nc]
                nbrv[f] = jnp.where(
                    mask.reshape(mask.shape + (1,) * (g.ndim - 2)),
                    g, jnp.zeros((), g.dtype))
            offs = mask[..., None] * offs_dev[None, :, :]
            acc = kernel.init(cell, *extras)
            for j in range(S):
                nbr_j = {f: nbrv[f][:, j] for f in fields_in}
                acc = kernel.slot(acc, cell, nbr_j, offs[:, j],
                                  mask[:, j], *extras)
            res = kernel.finish(acc, cell, *extras)
            for f in fields_out:
                cur[f] = cur[f].at[rows].set(
                    res[f].astype(dtypes[f]), mode="drop")
        return cur

    return fn


# ---------------------------------------------------------------------
# Grid.run_steps integration
# ---------------------------------------------------------------------

def _grid_spec_for(grid, hood, k, neighborhood_id):
    """RollPassSpec for a grid's hood, or None when the bulk executor
    cannot express the plan (the caller falls back to XLA)."""
    cf = hood.closed_form
    if cf is None or cf.get("multi") or grid.n_dev != 1:
        return None
    roll = hood.roll_plan(grid.plan.L)
    if roll is None:
        return None
    L = int(grid.plan.L)
    if L % _GROUP:
        return None
    try:
        return RollPassSpec(roll[0], cf["dims"], cf["periodic"],
                            cf["offsets"], cf["n0"], L, k)
    except ValueError:
        return None


def _eligible_fields(grid, kernel, fields_in, fields_out):
    from ..grid import SlotwiseKernel

    if not isinstance(kernel, SlotwiseKernel):
        return False
    for f in set(fields_in) | set(fields_out):
        shape, _dt = grid.fields[f]
        if shape != ():
            return False
    return True


def compile_bulk_step_loop(grid, kernel, fields_in, fields_out,
                           exchange_fields, neighborhood_id, n_extra):
    """The DCCRG_BULK=pallas replacement for Grid.compile_step_loop on
    an eligible single-device closed-form plan: one jitted program
    running ``n_steps`` steps as temporally-blocked Pallas bulk passes
    with fused fixup epilogues. Same ``(fn, tables, static_in)``
    contract; returns None when ineligible (caller falls back to the
    XLA roll path)."""
    fields_in = tuple(fields_in)
    fields_out = tuple(fields_out)
    if not _eligible_fields(grid, kernel, fields_in, fields_out):
        return None
    hood = grid.plan.hoods[neighborhood_id]
    if hood.hard_nbr_rows is not None or hood.offs_const is None:
        return None
    k = bulk_steps_per_pass()
    spec_k = _grid_spec_for(grid, hood, k, neighborhood_id)
    if spec_k is None:
        return None
    spec_1 = spec_k if k == 1 else _grid_spec_for(
        grid, hood, 1, neighborhood_id)
    if spec_1 is None:
        return None
    L, R = grid.plan.L, grid.plan.R
    roll = hood.roll_plan(L)
    dtypes = {f: grid.fields[f][1] for f in set(fields_in) | set(fields_out)}
    offs_const = np.asarray(hood.offs_const)
    offs_np = [np.asarray(offs_const[j]) for j in range(len(offs_const))]
    static_in = tuple(f for f in fields_in if f not in fields_out)
    interpret = not grid._on_accelerator()
    if os.environ.get("DCCRG_BULK_INTERPRET") in ("0", "1"):
        interpret = os.environ.get("DCCRG_BULK_INTERPRET") == "1"

    # epilogue cascade tables (host, padded to sticky caps) for the
    # k-deep pass and — when k > 1 — the 1-deep remainder pass. The
    # numpy dilation cascade is O(wrong-set * S * k) — surface-sized
    # but ~10^6 rows at 512^3 — so it is memoized on the hood (one
    # structure epoch), like the roll plan itself; steady-state
    # run_steps calls only look up the cached program + tables.
    memo = getattr(hood, "_bulk_epilogue", None)
    if memo is None:
        memo = hood._bulk_epilogue = {}

    def padded(spec, tag):
        hit = memo.get(tag)
        if hit is not None:
            return hit
        raw = build_epilogue_sets(spec, roll[1])
        caps = [grid._sticky_cap(("bulkN", neighborhood_id, tag, t),
                                 max(1, len(r[0])))
                for t, r in enumerate(raw)]
        hit = (pad_epilogue_tables(raw, caps, L), tuple(caps))
        memo[tag] = hit
        return hit

    tab_k, caps_k = padded(spec_k, k)
    tab_1, caps_1 = (tab_k, caps_k) if k == 1 else padded(spec_1, 1)

    tables = []
    for t, (rows, nbr, mask) in enumerate(tab_k):
        cap = len(rows)
        tables.append(hood.dev(("bulk_rows", neighborhood_id, k, t, cap),
                               rows))
        tables.append(hood.dev(("bulk_nbr", neighborhood_id, k, t, cap),
                               nbr))
        tables.append(hood.dev(("bulk_mask", neighborhood_id, k, t, cap),
                               mask))
    n_tab_k = len(tab_k)
    if k > 1:
        for t, (rows, nbr, mask) in enumerate(tab_1):
            cap = len(rows)
            tables.append(hood.dev(
                ("bulk_rows", neighborhood_id, 1, t, cap), rows))
            tables.append(hood.dev(
                ("bulk_nbr", neighborhood_id, 1, t, cap), nbr))
            tables.append(hood.dev(
                ("bulk_mask", neighborhood_id, 1, t, cap), mask))
    n_tab_1 = len(tab_1)

    synth = (spec_k.dims, spec_k.periodic, spec_k.n0)
    key = ("bulksteploop", kernel, fields_in, fields_out, n_extra, L, R,
           spec_k.shifts, synth, k, spec_k.TG, spec_1.TG, caps_k, caps_1,
           interpret)
    fn = grid._program_cache.get(key)
    if fn is not None:
        return fn, tables, static_in

    n_static, n_out = len(static_in), len(fields_out)
    n_tabs_total = 3 * (n_tab_k + (n_tab_1 if k > 1 else 0))
    epi_k = make_epilogue(kernel, fields_in, fields_out, dtypes,
                          offs_const, L, n_tab_k)
    epi_1 = epi_k if k == 1 else make_epilogue(
        kernel, fields_in, fields_out, dtypes, offs_const, L, n_tab_1)
    f32 = jnp.float32

    def body(n_steps, *args):
        tabs = args[:n_tabs_total]
        tabs_k = tabs[: 3 * n_tab_k]
        tabs_1 = tabs_k if k == 1 else tabs[3 * n_tab_k:]
        args = args[n_tabs_total:]
        statics = {f: a[0][:L] for f, a in zip(static_in, args[:n_static])}
        outs_full = args[n_static: n_static + n_out]
        extra_dtypes = tuple(jnp.asarray(e).dtype
                             for e in args[n_static + n_out:])
        # extras ride the Pallas scalar-prefetch as float32; the
        # epilogue must see the SAME post-roundtrip values (a float64
        # extra under x64 would otherwise step fixup rows with more
        # dt bits than the bulk rows — a growing seam along the
        # wrong-row set)
        extras = tuple(
            jnp.asarray(e).astype(f32).astype(dt)
            for e, dt in zip(args[n_static + n_out:], extra_dtypes))
        ex_arr = (jnp.stack([e.astype(f32) for e in extras])
                  if extras else jnp.zeros((1,), f32))
        pass_k = make_bulk_pass(spec_k, kernel, fields_in, fields_out,
                                dtypes, offs_np, extra_dtypes, interpret)
        pass_1 = pass_k if k == 1 else make_bulk_pass(
            spec_1, kernel, fields_in, fields_out, dtypes, offs_np,
            extra_dtypes, interpret)

        def one_pass(state, pallas_fn, epi, tabs_t):
            full = dict(statics)
            full.update(zip(fields_out, state))
            ins = [full[f].reshape(spec_k.G, _SUBLANES, _LANES)
                   for f in fields_in]
            bulk_out = pallas_fn(ex_arr, *ins)
            bulk = {f: o.reshape(L)
                    for f, o in zip(fields_out, bulk_out)}
            cur = {f: full[f] for f in set(fields_in) | set(fields_out)}
            cur = epi(cur, tabs_t, extras)
            rows_last = tabs_t[-3]
            merged = []
            for f in fields_out:
                fixed = cur[f][jnp.minimum(rows_last, L - 1)]
                merged.append(bulk[f].at[rows_last].set(
                    fixed, mode="drop"))
            return tuple(merged)

        state0 = tuple(a[0][:L] for a in outs_full)
        kk = jnp.int32(k)
        passes = n_steps // kk
        state = jax.lax.fori_loop(
            0, passes,
            lambda _i, s: one_pass(s, pass_k, epi_k, tabs_k), state0)
        if k > 1:
            rem = n_steps - passes * kk
            state = jax.lax.fori_loop(
                0, rem,
                lambda _i, s: one_pass(s, pass_1, epi_1, tabs_1), state)
        return tuple(a.at[0, :L].set(s)
                     for a, s in zip(outs_full, state))

    fn = jax.jit(body)
    grid._program_cache[key] = fn
    return fn, tables, static_in


# ---------------------------------------------------------------------
# fleet (GridBatch) integration
# ---------------------------------------------------------------------

def make_fleet_bulk_step(grid, kernel, fields_in, fields_out, n_extra,
                         capacity):
    """Batched bulk step for a fleet bucket: ``step(state, extras)``
    over ``{field: [capacity, R, ...]}`` state with per-slot float32
    extras ``[capacity, E]`` — the Pallas grid gains a leading slot
    dimension and the fixup epilogue is vmapped. Returns None when the
    bucket's template grid or schema is ineligible (the caller keeps
    the table-gather vstep)."""
    fields_in = tuple(fields_in)
    fields_out = tuple(fields_out)
    if kernel is None:
        return None
    if not _eligible_fields(grid, kernel, fields_in, fields_out):
        return None
    from .. import grid as grid_mod

    hood = grid.plan.hoods[grid_mod.DEFAULT_NEIGHBORHOOD_ID]
    if hood.hard_nbr_rows is not None or hood.offs_const is None:
        return None
    spec = _grid_spec_for(grid, hood, 1,
                          grid_mod.DEFAULT_NEIGHBORHOOD_ID)
    if spec is None:
        return None
    L = int(grid.plan.L)
    roll = hood.roll_plan(L)
    dtypes = {f: grid.fields[f][1] for f in set(fields_in) | set(fields_out)}
    offs_const = np.asarray(hood.offs_const)
    offs_np = [np.asarray(offs_const[j]) for j in range(len(offs_const))]
    interpret = not grid._on_accelerator()
    raw = build_epilogue_sets(spec, roll[1])
    tabs = pad_epilogue_tables(
        raw, [max(1, len(r[0])) for r in raw], L)
    tabs_dev = []
    for rows, nbr, mask in tabs:
        tabs_dev.extend([jnp.asarray(rows), jnp.asarray(nbr),
                         jnp.asarray(mask)])
    epi = make_epilogue(kernel, fields_in, fields_out, dtypes,
                        offs_const, L, len(tabs))
    f32 = jnp.float32
    extra_dtypes = (f32,) * n_extra
    pallas_fn = make_bulk_pass(spec, kernel, fields_in, fields_out,
                               dtypes, offs_np, extra_dtypes, interpret,
                               batch=capacity)
    rows_last = tabs_dev[-3]

    def fix_one(bulk_row, full_row, ex_row):
        extras = tuple(ex_row[i] for i in range(n_extra))
        cur = epi(full_row, tabs_dev, extras)
        merged = {}
        for f in fields_out:
            fixed = cur[f][jnp.minimum(rows_last, L - 1)]
            merged[f] = bulk_row[f].at[rows_last].set(fixed, mode="drop")
        return merged

    def step(state, extras):
        full = {f: state[f][:, :L]
                for f in set(fields_in) | set(fields_out)}
        ins = [full[f].reshape(capacity, spec.G, _SUBLANES, _LANES)
               for f in fields_in]
        ex_arr = (extras.astype(f32) if n_extra
                  else jnp.zeros((capacity, 1), f32))
        bulk_out = pallas_fn(ex_arr, *ins)
        bulk = {f: o.reshape(capacity, L)
                for f, o in zip(fields_out, bulk_out)}
        merged = jax.vmap(fix_one)(bulk, full, extras)
        new = dict(state)
        for f in fields_out:
            new[f] = state[f].at[:, :L].set(
                merged[f].astype(state[f].dtype))
        return new

    return step
