"""Dense fast path for uniform (refinement-level-0) grids.

The reference treats a uniform grid as the special case of its general
machinery; on TPU the uniform case deserves the opposite: fields are
dense ``[nx, ny, nz, ...]`` arrays sharded over an up-to-3-D device
mesh, and halo exchange is six ``lax.ppermute`` slab sends inside
``shard_map`` — the pattern the BASELINE.json north star names for
``update_copies_of_remote_neighbors()``'s hot path. Per-cell stencil
loops (advection fluxes tests/advection/solve.hpp:44-266, game of life,
Poisson relaxation) become fused array code / Pallas kernels over the
padded local block.

Cell ids remain interoperable with ``Grid``/``Mapping``: the cell at
dense index (i, j, k) is level-0 cell ``1 + i + j*nx + k*nx*ny``
(dccrg_mapping.hpp:154-209), so a user can move between the paths.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

AXES = ("x", "y", "z")


def dense_mesh(devices=None, shape=None) -> Mesh:
    """3-D mesh over the given devices; defaults to all devices laid
    out along x (factor further with ``shape=(px, py, pz)``)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = (n, 1, 1)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} does not cover {n} devices")
    return Mesh(np.array(devices).reshape(shape), AXES)


class DenseGrid:
    """Uniform Cartesian grid with dense sharded storage.

    Parameters
    ----------
    length : (nx, ny, nz) level-0 cell counts; each must be divisible
        by the mesh extent along its axis.
    fields : dict name -> dtype (scalar per cell) or (shape, dtype).
    periodic : per-dimension wrap, as GridTopology.
    start / cell_length : Cartesian geometry parameters
        (dccrg_cartesian_geometry.hpp:51-88).
    """

    def __init__(
        self,
        length,
        fields,
        mesh: Mesh | None = None,
        periodic=(False, False, False),
        start=(0.0, 0.0, 0.0),
        cell_length=None,
    ):
        self.length = tuple(int(v) for v in length)
        self.periodic = tuple(bool(p) for p in periodic)
        self.mesh = mesh if mesh is not None else dense_mesh()
        if tuple(self.mesh.axis_names) != AXES:
            raise ValueError(f"DenseGrid needs a mesh with axes {AXES}")
        self.mesh_shape = tuple(self.mesh.shape[a] for a in AXES)
        for d in range(3):
            if self.length[d] % self.mesh_shape[d] != 0:
                raise ValueError(
                    f"grid length {self.length[d]} not divisible by mesh "
                    f"extent {self.mesh_shape[d]} along {AXES[d]}"
                )
        self.block = tuple(self.length[d] // self.mesh_shape[d] for d in range(3))
        self.start = np.asarray(start, dtype=np.float64)
        if cell_length is None:
            cell_length = tuple(1.0 / self.length[d] for d in range(3))
        self.cell_length = np.asarray(cell_length, dtype=np.float64)

        self.fields = {}
        self.arrays = {}
        for name, spec in fields.items():
            if isinstance(spec, tuple):
                shape, dtype = spec
            else:
                shape, dtype = (), spec
            self.fields[name] = (tuple(shape), jnp.dtype(dtype))
            self.arrays[name] = jnp.zeros(
                self.length + tuple(shape), dtype=dtype, device=self.sharding()
            )

    def sharding(self):
        return NamedSharding(self.mesh, P(*AXES))

    @property
    def n_cells(self) -> int:
        return self.length[0] * self.length[1] * self.length[2]

    # -- coordinates ---------------------------------------------------

    def cell_centers(self, dim: int) -> jnp.ndarray:
        """1-D array of cell-center coordinates along ``dim``."""
        return jnp.asarray(
            self.start[dim] + (np.arange(self.length[dim]) + 0.5) * self.cell_length[dim]
        )

    def init_fields(self, fn) -> None:
        """Set fields from ``fn(x, y, z) -> dict`` evaluated on cell
        centers (broadcast 3-D arrays), sharded evaluation."""
        x = self.cell_centers(0)[:, None, None]
        y = self.cell_centers(1)[None, :, None]
        z = self.cell_centers(2)[None, None, :]
        vals = fn(x, y, z)
        for name, v in vals.items():
            shape, dtype = self.fields[name]
            self.arrays[name] = jax.device_put(
                jnp.broadcast_to(v, self.length + shape).astype(dtype), self.sharding()
            )

    # -- halo padding (the ppermute ghost-slab exchange) ---------------

    def pad_with_halo(self, block: jnp.ndarray, halo: int, boundary: float = 0.0):
        """Inside shard_map: pad a local block with ``halo`` cells from
        the six mesh neighbors (lax.ppermute per direction); global
        non-periodic boundaries are filled with ``boundary``.

        This is the TPU lowering of update_copies_of_remote_neighbors()
        for uniform grids (dccrg.hpp:978, 10703-11209): one collective
        permute of face slabs per direction instead of per-peer
        MPI_Isend/Irecv of per-cell struct datatypes.
        """
        for d in range(3):
            n = self.mesh_shape[d]
            size = block.shape[d]
            hi_slab = lax.slice_in_dim(block, size - halo, size, axis=d)
            lo_slab = lax.slice_in_dim(block, 0, halo, axis=d)
            if n == 1:
                if self.periodic[d]:
                    from_lo, from_hi = hi_slab, lo_slab
                else:
                    from_lo = jnp.full_like(hi_slab, boundary)
                    from_hi = jnp.full_like(lo_slab, boundary)
            else:
                fwd = [(i, (i + 1) % n) for i in range(n if self.periodic[d] else n - 1)]
                bwd = [((i + 1) % n, i) for i in range(n if self.periodic[d] else n - 1)]
                from_lo = lax.ppermute(hi_slab, AXES[d], fwd)  # my low halo: left nbr's high slab
                from_hi = lax.ppermute(lo_slab, AXES[d], bwd)
                if not self.periodic[d]:
                    # edge devices received zeros; overwrite with boundary
                    pos = lax.axis_index(AXES[d])
                    from_lo = jnp.where(pos == 0, jnp.full_like(from_lo, boundary), from_lo)
                    from_hi = jnp.where(
                        pos == n - 1, jnp.full_like(from_hi, boundary), from_hi
                    )
            block = jnp.concatenate([from_lo, block, from_hi], axis=d)
        return block

    # -- stencil driver ------------------------------------------------

    def make_step(self, fn, fields_in, fields_out, halo: int = 1, boundary=0.0,
                  extra_specs=()):
        """Compile ``fn`` into a jitted distributed step.

        ``fn(blocks: dict, *extra) -> dict`` receives halo-padded local
        blocks ``[bx+2h, by+2h, bz+2h, ...]`` for every name in
        ``fields_in`` and must return interior updates ``[bx, by, bz, ...]``
        for every name in ``fields_out``. Runs under shard_map over the
        3-D mesh; returns ``step(arrays: dict, *extra) -> dict``.
        """
        fields_in = tuple(fields_in)
        fields_out = tuple(fields_out)
        mesh = self.mesh

        def body(*args):
            ins = args[: len(fields_in)]
            extra = args[len(fields_in):]
            padded = {
                n: self.pad_with_halo(b, halo, boundary) for n, b in zip(fields_in, ins)
            }
            out = fn(padded, *extra)
            return tuple(out[n] for n in fields_out)

        spec = P(*AXES)
        mapped = _shard_map(
            body,
            mesh=mesh,
            in_specs=(spec,) * len(fields_in) + tuple(extra_specs),
            out_specs=(spec,) * len(fields_out),
        )

        @jax.jit
        def step(arrays, *extra):
            res = mapped(*(arrays[n] for n in fields_in), *extra)
            out = dict(arrays)
            for n, v in zip(fields_out, res):
                out[n] = v
            return out

        return step

    # -- interop with the id-addressed world ---------------------------

    def cell_id_of_index(self, i, j, k):
        """Level-0 cell id at dense index (dccrg_mapping.hpp:154-209)."""
        nx, ny = self.length[0], self.length[1]
        return 1 + np.uint64(i) + np.uint64(j) * nx + np.uint64(k) * nx * ny

    def to_host(self, name: str) -> np.ndarray:
        return np.asarray(self.arrays[name])
