"""Pallas TPU kernel for the advection benchmark hot loop.

The reference's per-cell flux loop (tests/advection/solve.hpp:44-266)
iterates cells and face neighbors through pointer-chasing neighbor
lists. Here the uniform-grid hot path is a tiled VMEM stencil:

- density lives unpadded in HBM; tiles span the full y extent and a
  (tx, Y, tz) brick of x/z, so the only halos needed are two x rows —
  and x is the *untiled* dimension of the (8, 128)-tiled memrefs, so
  their DMA slices are always alignment-legal. Periodic wraparound is
  applied to the DMA source indices; no padded copy of the state is
  ever materialized.
- y is the sublane dimension: the y-shifted operands come from in-VMEM
  concatenation (a VPU shuffle over data already on chip, with the
  periodic wrap falling out of the concat order) instead of HBM halos;
- input tiles are double-buffered (slot = tile parity) so the next
  tile's DMA overlaps the current tile's compute;
- the rotation velocity field of the benchmark is separable
  (vx depends only on y, vy only on x — solve.hpp:339-346), so face
  velocities enter as two 1-D arrays: ~zero HBM traffic beyond one
  density read + one write per step.

The result is an HBM-bandwidth-limited step: one read + one write of
the density per time step. The general variable-velocity variant lives
in models/advection.py (dense path) and pays three extra field reads.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def make_rotation_step(
    shape, dtype=jnp.float32, tile=(8, 128), cell_length=None, steps_per_pass=1,
    interpret=False,
):
    """Compile the 512^3-class benchmark step.

    shape: (X, Y, Z) interior extents; ``tile`` = (tx, tz) brick sizes
    for x and z (each tile covers the full y extent). X % tx == 0,
    Z % tz == 0, tz a multiple of 128 (or the full Z).
    Periodic in x and y (the 2d.cpp:237 configuration); vz == 0 so the
    z direction contributes no flux (and needs no halo).

    ``steps_per_pass``: temporal blocking depth — apply the upwind
    update that many times per HBM pass with a correspondingly wider x
    halo, dividing the HBM traffic per cell-update by the same factor.

    Returns ``step(rho, vx_face, vy_face, dt) -> rho'`` where
    ``vx_face`` is [1, Y] (vx at cell rows, constant along x) and
    ``vy_face`` is [X + 16, 1]: vy at cells (x - 8) % X, i.e. the cell
    values pre-extended by an 8-row wrap margin on each side so every
    dynamic slice offset stays sublane-aligned.

    ``interpret=True`` runs the kernel under Pallas's TPU interpret
    mode (pltpu.InterpretParams) so the DMA/semaphore logic and flux
    math execute on CPU — used by CI, which has no TPU.
    """
    X, Y, Z = shape
    tx, tz = tile
    tz = min(tz, Z)
    sp = int(steps_per_pass)
    if sp < 1 or sp > 8:
        raise ValueError("steps_per_pass must be in 1..8")
    if Z % 128:
        raise ValueError(
            f"pallas fast path needs Z a multiple of 128 (got {Z}); "
            "use the dense-path AdvectionSolver for small grids"
        )
    if X % tx or Z % tz:
        raise ValueError(f"shape {shape} not divisible by tile {(tx, tz)}")
    if tx % 8:
        raise ValueError("tile x extent must be a multiple of 8")
    gx, gz = X // tx, Z // tz
    n_tiles = gx * gz
    if cell_length is None:
        cell_length = (1.0 / X, 1.0 / Y, 1.0 / Z)
    # plain Python floats stay weakly typed so the flux arithmetic
    # keeps the kernel dtype (bfloat16 included) instead of promoting
    rdx = float(1.0 / cell_length[0])
    rdy = float(1.0 / cell_length[1])
    H = sp  # x-halo width on each side

    def tile_indices(n):
        return (n // gz) * tx, (n % gz) * tz

    def dmas(rho_hbm, body, sems, slot, n):
        """Body + two x-halo bands (x = untiled dim: always aligned).

        The wrapped halo band indices are contiguous because x0 is a
        multiple of tx >= H, so (x0 - H) mod X never splits a band."""
        x0, z0 = tile_indices(n)
        xm = (x0 - H + X) % X
        xp = (x0 + tx) % X
        zs = pl.ds(z0, tz)
        return [
            pltpu.make_async_copy(
                rho_hbm.at[pl.ds(x0, tx), :, zs],
                body.at[slot, pl.ds(H, tx), :, :],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                rho_hbm.at[pl.ds(xm, H), :, zs],
                body.at[slot, pl.ds(0, H), :, :],
                sems.at[slot, 1],
            ),
            pltpu.make_async_copy(
                rho_hbm.at[pl.ds(xp, H), :, zs],
                body.at[slot, pl.ds(tx + H, H), :, :],
                sems.at[slot, 2],
            ),
        ]

    def upwind(s, cx, cx_pos, cy_col, cy_pos):
        """One upwind update: input s of R rows -> output of R - 2 rows
        (the interior), with cy_col/cy_sign (R - 2 rows) aligned to the
        output.

        Because the benchmark's velocity field is separable (vx depends
        only on y, vy only on x — solve.hpp:339-346) the hi and lo face
        velocities of a cell are EQUAL, so the two per-face fluxes
        collapse algebraically:

            flux_lo - flux_hi = v * (up_lo - up_hi)
                              = v * where(v >= 0, r_m - rc, rc - r_p)

        and both one-sided differences along a dimension are slices of
        ONE difference array.  ``cx``/``cy_col`` carry ``v * dt / dlen``
        pre-folded (computed once per pass on [1,Y]/[tx+16,1] vectors;
        dt > 0 so their signs still select the upwind donor), so the
        inner loop is ~10 full-array VPU ops per sub-step instead of
        the naive 16."""
        R = s.shape[0]
        rc = s[1 : R - 1]
        # one-sided differences along x: both sides slice one array
        d_x = s[0 : R - 1] - s[1:R]  # d_x[i] = s[i] - s[i+1]
        dxt = cx * jnp.where(cx_pos, d_x[0 : R - 2], d_x[1 : R - 1])
        # y: d_y[j] = rc[j] - rc[(j+1) % Y]; the lo-side difference is
        # its +1 roll (periodic wrap falls out of the concat order)
        r_yp = jnp.concatenate([rc[:, 1:, :], rc[:, :1, :]], axis=1)
        d_y = rc - r_yp
        d_ym = jnp.concatenate([d_y[:, Y - 1 :, :], d_y[:, : Y - 1, :]], axis=1)
        dyt = cy_col * jnp.where(cy_pos, d_ym, d_y)
        return rc + dxt + dyt

    def kernel(dt_ref, rho_hbm, vxf_ref, vyf_ref, out_ref, body, sems):
        n = pl.program_id(0)
        two = jnp.int32(2)  # keep int32 under jax_enable_x64
        slot = jax.lax.rem(n, two)
        nxt = jax.lax.rem(n + jnp.int32(1), two)

        @pl.when(n == 0)
        def _():
            for c in dmas(rho_hbm, body, sems, 0, 0):
                c.start()

        @pl.when(n + 1 < n_tiles)
        def _():
            for c in dmas(rho_hbm, body, sems, nxt, n + 1):
                c.start()

        for c in dmas(rho_hbm, body, sems, slot, n):
            c.wait()

        x0, _z0 = tile_indices(n)
        x0 = pl.multiple_of(x0, tx)
        dt = dt_ref[0]
        # fold dt/dlen into the 1-D velocity vectors once per pass;
        # the minor-dim-inserting reshapes run in float32 (Mosaic only
        # supports them for 32-bit types) and are cast back below, so
        # everything downstream stays in the storage dtype
        f32 = jnp.float32
        cx = (vxf_ref[0, :].astype(f32).reshape(1, Y, 1)
              * (dt.astype(f32) * rdx))
        # extended vy: index i of vyf_ref holds vy[(i - 8) % X], so the
        # slice at x0 (sublane-aligned) covers global rows x0-8..x0+tx+7
        cy_wide = (vyf_ref[pl.ds(x0, tx + 16), 0].astype(f32)
                   .reshape(tx + 16, 1, 1)
                   * (dt.astype(f32) * rdy))
        # broadcast along the lanes and take the upwind signs while
        # still float32: Mosaic cannot broadcast a 16-bit vector in
        # sublanes and lanes at once, and v5e has no bfloat16 compare
        cx = jnp.broadcast_to(cx, (1, Y, tz))
        cy_wide = jnp.broadcast_to(cy_wide, (tx + 16, 1, tz))
        cx_pos, cy_pos = cx >= 0, cy_wide >= 0
        cx, cy_wide = cx.astype(dtype), cy_wide.astype(dtype)

        s = body[slot]  # rows cover global [x0 - H, x0 + tx + H)
        for k in range(sp):
            g = H - k - 1  # halo width remaining after this sub-step
            rows = slice(8 - g, 8 - g + tx + 2 * g)
            s = upwind(s, cx, cx_pos, cy_wide[rows], cy_pos[rows])
        out_ref[:] = s

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # rho stays in HBM
            pl.BlockSpec(memory_space=pltpu.VMEM),  # vx_face [1, Y]
            pl.BlockSpec(memory_space=pltpu.VMEM),  # vy_face [X, 1]
        ],
        out_specs=pl.BlockSpec(
            # (n, scalar_prefetch_ref) -> block indices
            (tx, Y, tz),
            lambda n, _dt: (n // gz, 0, n % gz),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, tx + 2 * H, Y, tz), dtype),  # body incl. x halos
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )

    flops_per_cell = 10 * sp
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        interpret=pltpu.InterpretParams() if interpret else False,
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), dtype),
        compiler_params=pltpu.CompilerParams(
            # deep temporal blocking holds several flux temporaries live;
            # let Mosaic use more than the 16 MiB default scoped VMEM
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops_per_cell * X * Y * Z,
            bytes_accessed=2 * jnp.dtype(dtype).itemsize * X * Y * Z,
            transcendentals=0,
        ),
    )

    @jax.jit
    def step(rho, vx_face, vy_face, dt):
        return call(
            jnp.asarray([dt], dtype=dtype),
            rho.astype(dtype),
            vx_face.astype(dtype),
            vy_face.astype(dtype),
        )

    return step
