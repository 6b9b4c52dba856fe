"""Collective-communication wrappers.

TPU-native equivalents of the reference's MPI support layer
(dccrg_mpi_support.hpp): where dccrg wraps MPI_Allgatherv /
MPI_Allreduce / point-to-point neighbor reduces, this module wraps the
XLA collectives that ride the ICI mesh. The functions are meant to be
called *inside* ``shard_map``-mapped code (they need an axis name in
scope); each also has a ``host_*`` twin that runs the same collective
as a tiny jitted program over a mesh — the form application code uses
for occasional global reductions (e.g. the Poisson dot products,
tests/poisson/poisson_solve.hpp:278-360, use psum the same way).

- ``all_gather``  — All_Gather (dccrg_mpi_support.hpp:101-234)
- ``all_reduce``  — All_Reduce, sum (dccrg_mpi_support.hpp:240-269)
- ``all_finite``  — the resilience watchdog's probe: fused per-device
  ``all(isfinite)`` + min all-reduce, one scalar to the host
- ``some_reduce`` — Some_Reduce: reduce contributions only from a
  device's peer set (dccrg_mpi_support.hpp:285-380, which reduces
  values from neighbor processes via point-to-point messages; on TPU
  the peer sets are static masks and the exchange is one all_gather)
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map


def all_gather(x, axis_name: str):
    """Every device's ``x`` stacked along a new leading axis."""
    return lax.all_gather(x, axis_name)


def all_reduce(x, axis_name: str, op: str = "sum"):
    """Elementwise reduction across the mesh axis."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduction {op!r}")


def all_finite(xs, axis_name: str):
    """Watchdog reduction: 1 iff every element of every array in
    ``xs`` on every device is finite. Each device fuses its local
    ``all(isfinite)`` over the list, then one min all-reduce crosses
    the mesh — so the resilience watchdog (resilience.check_finite)
    pulls a single scalar to the host no matter how many fields it
    guards."""
    ok = jnp.ones((), jnp.int32)
    for x in xs:
        ok = ok * jnp.all(jnp.isfinite(x)).astype(jnp.int32)
    return all_reduce(ok, axis_name, "min")


def field_sums(xs, axis_name: str):
    """Integrity reduction: the global sum of every array in ``xs``,
    fused like :func:`all_finite` — each device reduces its local
    arrays to a ``[len(xs)]`` vector, then ONE psum crosses the mesh.
    The SDC defense (:mod:`dccrg_tpu.integrity`) uses it for
    conservation-sum invariants: the result is replicated, so every
    rank reads the identical value and the drift verdict needs no
    further consensus round."""
    parts = jnp.stack([jnp.sum(x).astype(jnp.float32) for x in xs])
    return all_reduce(parts, axis_name, "sum")


def some_reduce(x, peer_mask, axis_name: str):
    """Sum of ``x`` over each device's peer set only.

    ``peer_mask``: [n_dev, n_dev] bool, ``peer_mask[q, p]`` true when
    device q reduces device p's contribution (the reference reduces
    over processes it shares a boundary with). The device's own row is
    applied on the device, so the result differs per device.
    """
    gathered = lax.all_gather(x, axis_name)  # [n_dev, ...]
    me = lax.axis_index(axis_name)
    w = peer_mask[me].astype(x.dtype)  # [n_dev]
    return jnp.tensordot(w, gathered, axes=1)


# Compiled host-collective programs, cached per (collective key, mesh,
# arg count). The host_* wrappers run EVERY step on hot resilience
# paths (the watchdog probe, the per-step trip consensus of
# ResilientRunner, the checkpoint CRC gather) — rebuilding
# jit(shard_map(...)) per call re-traced the program each time; with a
# stable jitted callable, jax's own cache makes repeat calls
# dispatch-only. FIFO-bounded: unlike grid._program_cache (which dies
# with its grid), this dict outlives every grid, so a long-lived
# driver cycling through many distinct meshes must not accumulate
# executables forever (far above the handful any one process uses).
_MESH_PROGRAMS: dict = {}
_MESH_PROGRAMS_CAP = 64


def _mesh_map(mesh: Mesh, key, build, *args):
    """Run ``build(axis)``'s body as ``jit(shard_map(...))`` over
    ``mesh`` with every arg row-sharded along the mesh axis. ``key``
    names the collective for the program cache (closures have no
    stable identity)."""
    axis = mesh.axis_names[0]
    spec = NamedSharding(mesh, P(axis))
    ck = (key, mesh, len(args))
    fn = _MESH_PROGRAMS.get(ck)
    if fn is None:
        mapped = _shard_map(
            build(axis), mesh=mesh,
            in_specs=(P(axis),) * len(args),
            out_specs=P(axis),
            check_vma=False,
        )
        fn = jax.jit(mapped)
        while len(_MESH_PROGRAMS) >= _MESH_PROGRAMS_CAP:
            _MESH_PROGRAMS.pop(next(iter(_MESH_PROGRAMS)))
        _MESH_PROGRAMS[ck] = fn
    args = [jnp.asarray(a, device=spec) for a in args]
    return fn(*args)


def pull_replicated(arr) -> np.ndarray:
    """Host copy of a device array whose value is replicated — or whose
    per-device rows are identical (any all-gathered / all-reduced
    result). Fully-addressable arrays pull directly; on a multi-process
    mesh only this process's first addressable shard is read — the
    foreign shards hold the same bytes by construction, which is
    exactly what a plain ``np.asarray`` cannot know (it refuses
    non-addressable arrays)."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    block = np.asarray(arr.addressable_shards[0].data)
    if block.shape == tuple(arr.shape):  # replicated output (P())
        return block
    # row-sharded output with identical rows: replicate the local row
    return np.broadcast_to(block[0], tuple(arr.shape)).copy()


def host_all_gather(mesh: Mesh, x) -> np.ndarray:
    """Run all_gather over ``mesh``; ``x`` is [n_dev, ...] sharded rows.
    Returns [n_dev, n_dev, ...] (each device's view, replicated)."""
    out = _mesh_map(mesh, "all_gather",
                    lambda axis: lambda v: all_gather(v[0], axis)[None],
                    jnp.asarray(x))
    return pull_replicated(out)


def host_all_reduce(mesh: Mesh, x, op: str = "sum") -> np.ndarray:
    """Reduce [n_dev, ...] rows across the mesh axis; returns one row."""
    out = _mesh_map(mesh, ("all_reduce", op),
                    lambda axis: lambda v: all_reduce(v[0], axis, op)[None],
                    jnp.asarray(x))
    return pull_replicated(out)[0]


def host_some_reduce(mesh: Mesh, x, peer_mask) -> np.ndarray:
    """Per-device neighbor-set sum of [n_dev, ...] rows."""
    mask = np.asarray(peer_mask, dtype=bool)

    def build(axis):
        def body(v, mask_row):
            # the mask rides in row-sharded: this device's block IS its
            # peer row (peer_mask[me]), so the program stays cacheable
            # across different masks instead of baking one in
            gathered = all_gather(v[0], axis)  # [n_dev, ...]
            w = mask_row[0].astype(v.dtype)  # [n_dev]
            return jnp.tensordot(w, gathered, axes=1)[None]

        return body

    # per-device results differ — no replicated pull possible (host
    # introspection of some_reduce stays a single-controller API)
    return np.asarray(_mesh_map(mesh, "some_reduce", build,
                                jnp.asarray(x), mask))
