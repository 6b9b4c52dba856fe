"""Pallas TPU kernel for the Poisson benchmark hot loop.

The reference's Poisson test spends its time in the per-iteration
matrix-vector product — a 7-point Laplacian applied through
pointer-chasing neighbor lists (tests/poisson/poisson_solve.hpp, the
``Solve`` class's per-cell neighbor loops). BASELINE.json names this
stencil loop as a Pallas target alongside the advection one.

Uniform-grid hot path, same structure as ops/advection_kernel.py:

- the operand lives unpadded in HBM; tiles span the full y AND z
  extents and a ``tx`` brick of x, so the only halos needed are two
  single x rows — and x is the *untiled* dimension of the
  (8, 128)-tiled memrefs, so their DMA slices are always
  alignment-legal. Periodic wraparound is applied to the DMA source
  indices.
- y and z neighbor terms come from in-VMEM concatenation (VPU
  shuffles over data already on chip, with the periodic wrap falling
  out of the concat order) — no y/z halos ever touch HBM;
- input tiles are double-buffered (slot = tile parity) so the next
  tile's DMA overlaps the current tile's compute;
- non-periodic boundaries drop the missing-neighbor terms
  (homogeneous Neumann), matching the masked stencil of
  models/poisson.py's general path and DensePoissonSolver.lap_kernel.

The result is an HBM-bandwidth-limited matvec: one read of the
operand + one write of the product per call — the memory-traffic
floor for one CG iteration's A·p.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def make_laplacian_matvec(shape, cell_length=None, periodic=(True, True, True),
                          dtype=jnp.float32, tx=8, interpret=False):
    """Compile the 7-point Laplacian matvec ``p -> A p``.

    shape: (X, Y, Z) extents; tiles are (tx, Y, Z) bricks, so Z must be
    a multiple of 128 (the lane tiling) and X a multiple of ``tx``. The
    sign convention matches DensePoissonSolver.lap_kernel: ``A p``
    sums ``rdd2 * (p[neighbor] - p[center])`` over present neighbors.

    ``interpret=True`` runs under Pallas's TPU interpret mode (CI has
    no TPU); the kernel logic is identical.
    """
    X, Y, Z = (int(v) for v in shape)
    if Z % 128:
        raise ValueError(
            f"pallas poisson path needs Z a multiple of 128 (got {Z}); "
            "use DensePoissonSolver for small grids"
        )
    if X % tx or tx % 8:
        raise ValueError(f"X {X} must divide into x tiles of {tx} (mult. of 8)")
    if cell_length is None:
        cell_length = (1.0 / X, 1.0 / Y, 1.0 / Z)
    rdx2 = float(1.0 / cell_length[0] ** 2)
    rdy2 = float(1.0 / cell_length[1] ** 2)
    rdz2 = float(1.0 / cell_length[2] ** 2)
    px, py, pz = (bool(b) for b in periodic)
    gx = X // tx
    H = 1  # one-cell halo in x

    def dmas(p_hbm, body, sems, slot, n):
        x0 = n * tx
        xm = (x0 - H + X) % X
        xp = (x0 + tx) % X
        return [
            pltpu.make_async_copy(
                p_hbm.at[pl.ds(x0, tx)], body.at[slot, pl.ds(H, tx)],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                p_hbm.at[pl.ds(xm, H)], body.at[slot, pl.ds(0, H)],
                sems.at[slot, 1],
            ),
            pltpu.make_async_copy(
                p_hbm.at[pl.ds(xp, H)], body.at[slot, pl.ds(tx + H, H)],
                sems.at[slot, 2],
            ),
        ]

    def kernel(p_hbm, out_ref, body, sems):
        n = pl.program_id(0)
        two = jnp.int32(2)
        slot = jax.lax.rem(n, two)
        nxt = jax.lax.rem(n + jnp.int32(1), two)

        @pl.when(n == 0)
        def _():
            for c in dmas(p_hbm, body, sems, 0, 0):
                c.start()

        @pl.when(n + 1 < gx)
        def _():
            for c in dmas(p_hbm, body, sems, nxt, n + 1):
                c.start()

        for c in dmas(p_hbm, body, sems, slot, n):
            c.wait()

        s = body[slot]  # rows cover global [x0 - 1, x0 + tx + 1)
        rc = s[1 : tx + 1]
        acc = jnp.zeros_like(rc)

        # x: halo rows from the DMA (wrapped indices); non-periodic
        # edges mask by the global row index
        t_lo = s[0:tx] - rc
        t_hi = s[2 : tx + 2] - rc
        if not px:
            x0 = pl.program_id(0) * tx
            gxr = x0 + jax.lax.broadcasted_iota(jnp.int32, rc.shape, 0)
            t_lo = jnp.where(gxr > 0, t_lo, 0.0)
            t_hi = jnp.where(gxr < X - 1, t_hi, 0.0)
        acc += rdx2 * (t_lo + t_hi)

        # y: in-VMEM concat rolls (wrap falls out of the concat order)
        y_hi = jnp.concatenate([rc[:, 1:, :], rc[:, :1, :]], axis=1)
        y_lo = jnp.concatenate([rc[:, Y - 1 :, :], rc[:, : Y - 1, :]], axis=1)
        t_lo = y_lo - rc
        t_hi = y_hi - rc
        if not py:
            gy = jax.lax.broadcasted_iota(jnp.int32, rc.shape, 1)
            t_lo = jnp.where(gy > 0, t_lo, 0.0)
            t_hi = jnp.where(gy < Y - 1, t_hi, 0.0)
        acc += rdy2 * (t_lo + t_hi)

        # z: same trick on the lane dimension
        z_hi = jnp.concatenate([rc[:, :, 1:], rc[:, :, :1]], axis=2)
        z_lo = jnp.concatenate([rc[:, :, Z - 1 :], rc[:, :, : Z - 1]], axis=2)
        t_lo = z_lo - rc
        t_hi = z_hi - rc
        if not pz:
            gz = jax.lax.broadcasted_iota(jnp.int32, rc.shape, 2)
            t_lo = jnp.where(gz > 0, t_lo, 0.0)
            t_hi = jnp.where(gz < Z - 1, t_hi, 0.0)
        acc += rdz2 * (t_lo + t_hi)

        out_ref[:] = acc

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(gx,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # p stays in HBM
        out_specs=pl.BlockSpec(
            (tx, Y, Z), lambda n: (n, 0, 0), memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, tx + 2 * H, Y, Z), jnp.dtype(dtype)),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )

    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        interpret=pltpu.InterpretParams() if interpret else False,
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), jnp.dtype(dtype)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            12 * X * Y * Z,
            bytes_accessed=2 * 4 * X * Y * Z,
            transcendentals=0,
        ),
    )

    def matvec(p):
        return call(jnp.asarray(p, dtype=dtype))

    return jax.jit(matvec)


class PallasPoissonSolver:
    """CG on the Pallas matvec: the single-chip fast path of the
    Poisson benchmark (uniform grids; cross-checked against
    DensePoissonSolver in tests under interpret mode). The CG vector
    updates run as fused XLA ops; the matvec — the HBM-bound op — is
    the kernel above."""

    def __init__(self, length, periodic=(True, True, True),
                 dtype=jnp.float32, tx=8, interpret=False):
        self.length = tuple(int(v) for v in length)
        self.periodic = tuple(bool(b) for b in periodic)
        self.dtype = jnp.dtype(dtype)
        self._matvec = make_laplacian_matvec(
            self.length, cell_length=tuple(1.0 / v for v in self.length),
            periodic=self.periodic, dtype=dtype, tx=tx, interpret=interpret,
        )

    def solve(self, rhs, rtol=1e-5, max_iterations=1000):
        from ..models.poisson import cg_solve

        return cg_solve(self._matvec, rhs, singular=all(self.periodic),
                        dtype=self.dtype, rtol=rtol,
                        max_iterations=max_iterations)
