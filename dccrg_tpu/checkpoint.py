"""Parallel checkpoint / restart.

BYTE-COMPATIBLE implementation of the reference's .dc file format
(dccrg.hpp:1109-2426; layout documented at :1125-1142; conformance
pinned by tests/test_golden.py::test_reference_write_sequence_loads,
which replays the reference's write calls with struct.pack and loads
the result):

    [user header bytes]
    uint64 endianness magic 0x1234567890abcdef        (:1243)
    mapping record: 3 x uint64 level-0 lengths + int32 max_ref_lvl
    uint32 neighborhood length
    topology record: 3 x uint8 periodicity
    geometry record: int32 geometry id + parameters
    uint64 total cell count
    (uint64 cell id, uint64 data byte offset) pairs
    per-cell payloads

The reference writes with collective MPI-IO file views; here the host
owns the replicated structure and payloads stream through bounded
chunks: each chunk is gathered ON DEVICE for the chunk's cells and only
that slice crosses to the host (save, with a one-deep prefetch pipeline
overlapping chunk k+1's device pull with chunk k's file write), or is
scattered from a memory map that pages in on demand (load) — a >=64^3
multi-field grid never materializes the full interleaved payload
matrix. The format itself is pinned by a golden-file fixture
(tests/data/golden.dc + tests/test_golden.py: byte-identical re-save). The per-cell payload
is the grid's fields in sorted-name order — the same role as the
user's ``get_mpi_datatype()`` serialization boundary (sender/receiver
= -1 during save/load, dccrg.hpp:1106-1107).

**Restart needs nothing but the file**: :func:`load_grid` (and
``Grid.from_file``) reconstructs mapping, topology, geometry and the
AMR cell set from the metadata — the reference's
``start_loading_grid_data`` (dccrg.hpp:1815-2105: read metadata,
create_level_0_cells, load_cells refinement sweeps) — then streams the
payloads in. The legacy :func:`load_grid_data` keeps the
load-into-prepared-grid API, validating the file against the grid.

**Variable-size payloads** (two-pass, dccrg.hpp:2108-2123 and
tests/particles/cell.hpp:50-84): a field may be declared variable with
a count field: ``variable={"pos": "count"}`` stores only the first
``count`` rows of each cell's ``pos`` buffer. Loading reads the
fixed-size parts (including the counts) in pass one and the ragged
payloads in pass two, exactly the reference's size-fields-first
contract.
"""

from __future__ import annotations

import os
import struct
import zlib

import numpy as np

from . import faults

ENDIAN_MAGIC = 0x1234567890ABCDEF
CHUNK = 1 << 19  # cells per streamed payload chunk

# Integrity wrappers live in resilience.py: save_checkpoint writes
# these same bytes atomically (temp + fsync + rename) plus a CRC32
# sidecar <file>.crc; load_checkpoint verifies it. The .dc byte layout
# here stays pinned by the golden-file tests either way.


def _payload_spec_of(fields, variable=None):
    """Split a ``{name: (shape, dtype)}`` field spec into fixed and
    variable parts.

    Returns ``(fixed_spec, fixed_bytes, var_spec)`` where fixed_spec is
    [(name, shape, dtype, nbytes)] in sorted-name order, and var_spec
    is [(name, count_field, row_shape, dtype, row_bytes, capacity)]
    for fields declared variable (stored truncated to their per-cell
    count)."""
    variable = variable or {}
    fixed, var = [], []
    total = 0
    for n in sorted(fields):
        shape, dtype = fields[n]
        dtype = np.dtype(dtype)
        if n in variable:
            if not shape:
                raise ValueError(f"variable field {n!r} must have a row axis")
            row_shape = tuple(shape[1:])
            row_bytes = int(np.prod(row_shape, dtype=np.int64)) * dtype.itemsize if row_shape else dtype.itemsize
            var.append((n, variable[n], row_shape, dtype, row_bytes, int(shape[0])))
        else:
            nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape else dtype.itemsize
            fixed.append((n, tuple(shape), dtype, nbytes))
            total += nbytes
    for n, cf, *_ in var:
        if cf not in fields or fields[cf][0] != ():
            raise ValueError(f"count field {cf!r} of {n!r} must be a scalar field")
        if cf in variable:
            raise ValueError(f"count field {cf!r} cannot itself be variable")
    return fixed, total, var


def _payload_spec(grid, variable=None, names=None):
    """``names`` restricts the spec to a subset of the grid's fields —
    the delta-checkpoint path serializes only the dirty fields, in the
    same sorted-name interleave the full format uses (a delta file IS
    a valid .dc file of the sub-schema)."""
    if names is None:
        return _payload_spec_of(grid.fields, variable)
    fields = {n: grid.fields[n] for n in names}
    variable = {n: cf for n, cf in (variable or {}).items() if n in fields}
    return _payload_spec_of(fields, variable)


def parse_metadata(data, header_size: int = 0):
    """Parse a .dc file's metadata block (the format documented above):
    returns (mapping, hood_len, topology, geometry, cells, offsets,
    payload_start). Shared by load paths and dc_to_vtk. ``data`` is a
    bytes-like (a memory map works)."""
    from .geometry import geometry_from_buffer
    from .mapping import Mapping
    from .topology import GridTopology

    pos = header_size
    (magic,) = struct.unpack_from("<Q", data, pos)
    pos += 8
    if magic != ENDIAN_MAGIC:
        raise ValueError(
            f"bad endianness magic {magic:#x}: file written on an "
            "incompatible architecture or wrong header_size"
        )
    mapping = Mapping.from_bytes(bytes(data[pos : pos + 28]))
    pos += 28
    (hood_len,) = struct.unpack_from("<I", data, pos)
    pos += 4
    topology = GridTopology.from_bytes(bytes(data[pos : pos + 3]))
    pos += 3
    # the geometry record is self-describing via its id — no length
    # prefix, exactly the reference's layout (dccrg.hpp:1312-1323)
    try:
        geometry, geom_len = geometry_from_buffer(data, pos, mapping, topology)
    except (ValueError, struct.error):
        # struct.error covers a truncated record so the fallback (or
        # its 'unrecognized geometry record' diagnostic) still fires
        # legacy files from this repo before round 4 carried a u32
        # record-length prefix here; its value (>= 4) can never be a
        # valid geometry id, so falling back on that signature is
        # unambiguous
        try:
            (legacy_len,) = struct.unpack_from("<I", data, pos)
            (legacy_gid,) = struct.unpack_from("<i", data, pos + 4)
        except struct.error:
            raise ValueError(
                "unrecognized geometry record (file truncated mid-record)"
            ) from None
        if legacy_gid == 2:
            # legacy stretched records carried no coordinate counts;
            # sizes come from the mapping's level-0 lengths
            from .geometry import StretchedCartesianGeometry

            coords, off = [], pos + 8
            for d in range(3):
                n = int(mapping.length.get()[d]) + 1
                coords.append(np.frombuffer(
                    data, dtype=np.float64, count=n, offset=off).copy())
                off += 8 * n
            geometry = StretchedCartesianGeometry(mapping, topology, coords)
            geom_len = off - pos - 4
        else:
            try:
                geometry, geom_len = geometry_from_buffer(
                    data, pos + 4, mapping, topology)
            except (ValueError, struct.error):
                raise ValueError(
                    "unrecognized geometry record (neither the reference "
                    ".dc layout nor this repo's legacy length-prefixed "
                    "form)"
                )
        if geom_len != legacy_len:
            raise ValueError(
                f"legacy geometry length prefix {legacy_len} does not "
                f"match the parsed record ({geom_len} bytes)"
            )
        geom_len += 4
    pos += geom_len
    (n_cells,) = struct.unpack_from("<Q", data, pos)
    pos += 8
    pairs = np.frombuffer(data, dtype=np.uint64, count=2 * n_cells, offset=pos).reshape(-1, 2)
    cells = pairs[:, 0].copy()
    offsets = pairs[:, 1].copy()
    return mapping, hood_len, topology, geometry, cells, offsets, pos + 16 * n_cells


def payload_columns(raw, meta, fields, variable=None) -> dict:
    """Per-field fixed-column bytes of a parsed .dc buffer:
    ``{name: uint8[n_cells, nbytes]}`` gathered from each cell's
    offset-table position — the read-side mirror of
    :func:`_chunk_payload`'s interleave, used by the offline
    integrity audit (:func:`dccrg_tpu.integrity.file_fingerprint`) to
    re-derive a payload fingerprint without reconstructing a grid.
    Ragged (variable) fields are skipped: their per-cell extents sit
    between the fixed blocks and a corrupted count would make the
    walk ambiguous."""
    fixed_spec, _fixed_bytes, _var = _payload_spec_of(fields, variable)
    offs = meta[5].astype(np.int64)
    n = len(offs)
    out = {}
    col = 0
    buf = np.asarray(raw, dtype=np.uint8)
    for name, _shape, _dtype, nbytes in fixed_spec:
        span = np.arange(nbytes, dtype=np.int64)[None, :]
        idx = offs[:, None] + col + span
        if n and int(idx.max()) >= buf.size:
            raise ValueError(
                f"payload column {name!r} extends past the end of the "
                "buffer (truncated file?)")
        out[name] = buf[idx]
        col += nbytes
    return out


def _chunk_payload(grid, ids, fixed_spec, cell_bytes, reader=None):
    """The interleaved fixed-field payload for one chunk of cells,
    gathered on device so only the chunk crosses to the host.
    ``reader`` overrides the row source (the multi-process save passes
    grid._shard_read so reads stay on addressable shards)."""
    read = reader or (lambda n, d, r: np.asarray(grid.data[n][d, r]))
    dev, rows = grid._host_rows(ids)
    payload = np.empty((len(ids), cell_bytes), dtype=np.uint8)
    col = 0
    for name, shape, dtype, nbytes in fixed_spec:
        vals = np.ascontiguousarray(read(name, dev, rows))
        payload[:, col : col + nbytes] = vals.reshape(len(ids), -1).view(np.uint8)
        col += nbytes
    return payload


def _chunk_bytes(grid, cells, counts, start, fixed_spec, fixed_bytes,
                 var_spec, reader=None, idx=None):
    """Serialize one chunk of cells to bytes (device gather + host
    assembly) — runs on the prefetch thread so the NEXT chunk's device
    pull overlaps the file write of the current one. The multi-process
    save passes explicit cell positions (``idx``) and a shard-local
    ``reader`` so its slice writes share THIS byte-layout code — the
    two paths cannot drift apart."""
    idx = (np.arange(start, min(start + CHUNK, len(cells)))
           if idx is None else idx)
    ids = cells[idx]
    fixed = _chunk_payload(grid, ids, fixed_spec, fixed_bytes, reader)
    if not var_spec:
        return fixed.tobytes()
    read = reader or (lambda n, d, r: np.asarray(grid.data[n][d, r]))
    dev, rows = grid._host_rows(ids)
    var_host = {
        name: np.ascontiguousarray(read(name, dev, rows))
        for name, *_ in var_spec
    }
    nc = len(ids)
    var_nbytes = {
        name: counts[name][idx].astype(np.int64) * row_bytes
        for name, count_field, row_shape, dtype, row_bytes, cap in var_spec
    }
    return _interleave(nc, fixed, var_host, var_nbytes, fixed_bytes, var_spec)


def _interleave(nc, fixed, var_host, var_nbytes, fixed_bytes, var_spec):
    """Interleave fixed parts and ragged variable rows per cell —
    vectorized (repeat/cumsum scatter), no per-cell Python loop."""
    cell_total = np.full(nc, fixed_bytes, dtype=np.int64)
    for nb in var_nbytes.values():
        cell_total += nb
    out = np.empty(int(cell_total.sum()), dtype=np.uint8)
    cell_off = np.cumsum(cell_total) - cell_total
    out[cell_off[:, None] + np.arange(fixed_bytes, dtype=np.int64)] = fixed
    field_off = cell_off + fixed_bytes
    for name, *_ in var_spec:
        nb = var_nbytes[name]
        tot = int(nb.sum())
        if tot:
            vb = var_host[name].reshape(nc, -1).view(np.uint8)
            pos = np.arange(tot, dtype=np.int64) - np.repeat(
                np.cumsum(nb) - nb, nb
            )
            src_row = np.repeat(np.arange(nc, dtype=np.int64), nb)
            out[np.repeat(field_off, nb) + pos] = vb[src_row, pos]
        field_off = field_off + nb
    return out.tobytes()


def save_grid_data(grid, filename: str, header: bytes = b"",
                   variable=None, *, sidecar: bool = False,
                   sidecar_chunk_bytes: int | None = None,
                   fields=None, sidecar_extra=None) -> None:
    """Write the grid and all cell data (dccrg.hpp:1109-1736), payloads
    streamed in bounded chunks with the device pull of chunk k+1
    overlapping the file write of chunk k (the reference overlaps via
    collective MPI-IO file views, dccrg.hpp:1594-1659; here a one-deep
    prefetch pipeline gives the same pull/write concurrency on the
    single controller). ``variable={"field": "count_field"}`` stores
    that field truncated to each cell's count (two-pass loadable
    ragged payloads, dccrg.hpp:2108-2123).

    Multi-process meshes take the TWO-PHASE-COMMIT path
    (:func:`_save_process_slice`): slices land in ``<file>.mp-tmp``,
    per-rank CRCs are collected at a commit barrier, and the committing
    rank verifies + renames — atomic under rank death. ``sidecar=True``
    additionally has the committing rank write the resilience CRC32
    sidecar (with the per-rank slice table); on the single-controller
    path the sidecar is resilience.save_checkpoint's job and these
    kwargs are ignored.

    ``fields`` restricts the save to a subset of the grid's fields —
    the incremental-checkpoint (delta) path: the file is a valid .dc
    of the sub-schema, byte-layout shared with full saves.
    ``sidecar_extra`` (a dict) is merged into the committing rank's
    sidecar record (the delta parent link)."""
    from concurrent.futures import ThreadPoolExecutor

    cells = grid.get_cells()
    fixed_spec, fixed_bytes, var_spec = _payload_spec(grid, variable,
                                                      names=fields)

    meta = bytearray()
    meta += header
    meta += struct.pack("<Q", ENDIAN_MAGIC)
    meta += grid.mapping.to_bytes()
    meta += struct.pack("<I", grid._hood_len)
    meta += grid.topology.to_bytes()
    geom = grid.geometry.to_bytes()
    meta += geom  # self-describing record, no length prefix
    meta += struct.pack("<Q", len(cells))

    # per-cell byte sizes (variable fields contribute count * row).
    # Counts must be REPLICATED for the offset table; on multi-process
    # meshes the psum device gather with identical (plan-derived) args
    # on every process is globally consistent, unlike host get()
    sizes = np.full(len(cells), fixed_bytes, dtype=np.uint64)
    counts = {}
    for name, count_field, row_shape, dtype, row_bytes, cap in var_spec:
        c = _replicated_pull(grid, count_field, cells).astype(np.int64)
        if np.any(c < 0) or np.any(c > cap):
            raise ValueError(f"count field {count_field!r} out of range for {name!r}")
        counts[name] = c
        sizes += (c * row_bytes).astype(np.uint64)

    offset0 = len(meta) + 16 * len(cells)
    offsets = offset0 + np.concatenate(
        [[np.uint64(0)], np.cumsum(sizes)[:-1]]
    ).astype(np.uint64)

    if grid._multiproc:
        _save_process_slice(grid, filename, bytes(meta), cells, offsets,
                            sizes, counts, fixed_spec, fixed_bytes, var_spec,
                            header_size=len(header), sidecar=sidecar,
                            sidecar_chunk_bytes=sidecar_chunk_bytes,
                            sidecar_extra=sidecar_extra)
        return

    starts = list(range(0, len(cells), CHUNK))
    with open(filename, "wb") as f, ThreadPoolExecutor(1) as pool:
        f.write(bytes(meta))
        pairs = np.empty((len(cells), 2), dtype=np.uint64)
        pairs[:, 0] = cells
        pairs[:, 1] = offsets
        f.write(pairs.tobytes())
        fut = None
        for i, start in enumerate(starts):
            if fut is None:
                fut = pool.submit(_chunk_bytes, grid, cells, counts, start,
                                  fixed_spec, fixed_bytes, var_spec)
            buf = fut.result()
            fut = (pool.submit(_chunk_bytes, grid, cells, counts,
                               starts[i + 1], fixed_spec, fixed_bytes,
                               var_spec)
                   if i + 1 < len(starts) else None)
            # fault-injection site: a mid-stream write failure leaves a
            # torn file — resilience.save_checkpoint's atomic rename
            # guarantees it never carries the final checkpoint name
            faults.fire("checkpoint.chunk", chunk=i, path=filename)
            f.write(buf)


def _replicated_pull(grid, field, cells):
    """Per-cell host values with identical results on every process:
    single-controller grids read directly; multi-process grids use the
    chunked psum device gather, whose (replicated) index args make the
    collective consistent across processes — the role of the
    reference's allgathered cell lists (dccrg.hpp:1109-1736).

    A :func:`~dccrg_tpu.background.freeze_grid_mp` snapshot carries the
    pull PRE-COMPUTED (``_frozen_pulls``, taken on the caller thread at
    freeze time): the chunked gather is an XLA collective, and the
    async writer thread must never dispatch device work."""
    frozen = getattr(grid, "_frozen_pulls", None)
    if frozen is not None and field in frozen \
            and len(frozen[field]) == len(cells):
        return frozen[field]
    if not grid._multiproc:
        return grid.get(field, cells)
    out = []
    for start in range(0, len(cells), CHUNK):
        ids = cells[start : start + CHUNK]
        dev, rows = grid._host_rows(ids)
        out.append(grid._device_gather(field, dev, rows))
    return np.concatenate(out)


MP_TMP_SUFFIX = ".mp-tmp"


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(int(pid), 0)
    except (ProcessLookupError, OverflowError, ValueError):
        return False
    except PermissionError:  # pragma: no cover - exists, not ours
        return True
    return True


def stale_temp_files(dirpath: str) -> list:
    """Orphaned save/salvage temp files in ``dirpath``, left behind by
    a run that died or was preempted mid-save: ``<f>.mp-tmp`` (an
    unfinished two-phase multi-process save — the atomic rename never
    happened, so the bytes under the final name are still the previous
    intact checkpoint), and ``<f>.tmp.<pid>`` / ``<f>.salvage.<pid>`` /
    ``<f>.chain.<pid>`` (a delta-chain reconstruction scratch file)
    whose owning pid is no longer alive. Delta saves share the same
    temp discipline — ``<f>.dcd.tmp.<pid>`` and ``<f>.dcd.mp-tmp``
    match through the generic patterns. Never matches a finished
    checkpoint (keyframe or delta) or its sidecar. Only call between
    runs (or from the process that owns the saves): an ``.mp-tmp`` of
    a save in flight in ANOTHER process is indistinguishable from a
    stale one."""
    out = []
    try:
        names = sorted(os.listdir(dirpath))
    except OSError:
        return out
    for name in names:
        path = os.path.join(dirpath, name)
        if not os.path.isfile(path):
            continue
        if name.endswith(MP_TMP_SUFFIX):
            out.append(path)
            continue
        for marker in (".tmp.", ".salvage.", ".chain."):
            idx = name.rfind(marker)
            if idx < 0:
                continue
            pid = name[idx + len(marker):]
            if pid.isdigit() and not _pid_alive(int(pid)):
                out.append(path)
            break
    return out

def state_digest(grid, fields=None) -> str:
    """Deterministic SHA-256 over the grid's OWNED cell bytes — the
    exact payload rows a checkpoint serializes (per device, rows
    ``[0, n_local[d])``; ghost and pad rows excluded), field-name
    sorted with the name/shape/dtype folded in. Two grids with the
    same structure digest equal iff every owned field byte is equal,
    so the fleet isolation tests (and bench parity checks) compare
    'final field bytes identical' without writing checkpoint files.
    Process-local on multi-process meshes: each rank digests its own
    addressable shards (compare per rank, or gather host-side).

    Gather-mode independent BY CONSTRUCTION: the digest reads only the
    owned payload rows, which every gather mode (roll, tables) leaves
    in the same layout — pinned by the SDC suite
    (tests/test_integrity.py), because the shadow-audit comparator
    assumes a mode-dependent digest can never raise a false alarm."""
    import hashlib

    h = hashlib.sha256()
    names = sorted(fields if fields is not None else grid.fields)
    for name in names:
        shape, dtype = grid.fields[name]
        h.update(repr((name, tuple(shape), str(dtype))).encode())
        arr = grid.data[name]
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        for s in shards:
            d = s.index[0].start or 0
            n_own = int(grid.plan.n_local[d])
            h.update(np.ascontiguousarray(
                np.asarray(s.data)[0, :n_own]).tobytes())
    return h.hexdigest()


# Faked-split CRC staging: {tmp_path: {dev: (rank, [crc per run])}}.
# REAL multi-process meshes never touch this — their CRCs cross ranks
# through the device all-gather at the commit barrier; the table only
# bridges the SEQUENTIAL per-rank passes of the faked test protocol
# (tests/test_multiprocess.py runs rank 0's pass, then rank 1's, in
# one process). The meta-writing pass resets the entry, so an aborted
# earlier attempt can never leak stale CRCs into a later save.
_MP_CRC_STAGE: dict = {}


def _device_runs(n_dev, owner, offsets, sizes):
    """Per-device contiguous payload runs ``[(dev, positions, lo,
    hi)]`` in device order — derived from the replicated plan only, so
    every process computes the IDENTICAL run table (the shared frame of
    reference the commit-time CRC exchange needs; the reference gets
    the same from its allgathered cell lists, dccrg.hpp:1594-1659)."""
    runs = []
    offs = offsets.astype(np.int64)
    szs = sizes.astype(np.int64)
    for d in range(n_dev):
        pos = np.flatnonzero(owner == d)
        if not len(pos):
            continue
        brk = np.flatnonzero(np.diff(pos) != 1) + 1
        for seg in np.split(pos, brk):
            runs.append((d, seg, int(offs[seg[0]]),
                         int(offs[seg[-1]] + szs[seg[-1]])))
    return runs


def _crc_kv_key(base, rank):
    return f"dccrg_crc:{base}:{rank}"


def _post_run_crcs_kv(grid, runs, local_crcs, rank, base):
    """Writer-thread half of the commit-time CRC exchange: post this
    rank's per-run CRC32s to the coordination KV under the
    attempt-tagged key BEFORE the commit barrier, so once the barrier
    releases every posted record is visible to the committer. Pure
    gRPC — no XLA collective — which is what lets an
    :class:`~dccrg_tpu.background.AsyncSaver` writer thread run the
    whole two-phase save without dispatching device work
    (:func:`~dccrg_tpu.background.freeze_grid_mp`'s contract). The
    record is CRC-framed (:func:`~dccrg_tpu.coord.seal_record`): a
    rank that dies mid-post reads as a torn record, which the
    committer treats exactly like a dead rank."""
    import json

    from . import coord

    by_dev: dict = {}
    for gri, (d, _seg, _lo, _hi) in enumerate(runs):
        by_dev.setdefault(d, []).append(gri)
    payload = {str(d): [int(local_crcs[g]) & 0xFFFFFFFF for g in gris]
               for d, gris in by_dev.items() if grid._proc_local_dev[d]}
    rec = coord.seal_record(
        json.dumps({"rank": int(rank), "devs": payload}, sort_keys=True))
    client = coord._coordination_client()
    key = _crc_kv_key(base, rank)
    try:
        client.key_value_set(key, rec, allow_overwrite=True)
    except TypeError:  # older jaxlib without the kwarg
        client.key_value_set(key, rec)


def _read_run_crcs_kv(grid, by_dev, base):
    """Committer half of the KV CRC exchange: merge every rank's posted
    record into the ``{dev: (rank, [crc, ...])}`` table. A rank that
    never posted (died before the commit barrier) or posted a torn
    record simply leaves its devices absent — the committer's
    missing-slice check turns that into a
    :class:`~dccrg_tpu.coord.CheckpointCommitError` naming it."""
    import json

    import jax

    from . import coord

    client = coord._coordination_client()
    out: dict = {}
    for r in range(jax.process_count()):
        key = _crc_kv_key(base, r)
        try:
            rec = client.blocking_key_value_get(key, 10_000)
        except Exception:  # dead before posting: devices stay absent
            continue
        try:
            msg = json.loads(coord.unseal_record(rec, key=key))
        except coord.TornRecordError:
            continue  # torn post == dead rank to the committer
        for ds, crcs in msg["devs"].items():
            out[int(ds)] = (int(msg["rank"]), [int(c) for c in crcs])
    return out


def _gather_run_crcs(grid, runs, local_crcs, rank, tmp, real,
                     via_kv=False, base=""):
    """Collect every rank's per-run CRC32s into one replicated table
    ``{dev: (rank, [crc, ...])}``.

    Real multi-process meshes exchange through ``comm.host_all_gather``
    at the commit barrier: each process uploads a [n_dev, 1 + 2K]
    uint32 row block for its own devices — rank+1, then (present, crc)
    per run, so a never-written run is distinguishable from any
    legitimate CRC value — and the gather replicates the full table to
    every rank. uint32 on purpose: with ``jax_enable_x64`` off (JAX's
    default; the library never flips it) 64-bit dtypes are silently
    canonicalized to 32 bits inside the device put, which would wrap
    half of all CRC32 values and make healthy ranks look dead. Faked
    test splits merge the in-process stage table instead (their passes
    run sequentially — there is nothing to gather *from* yet when the
    first pass runs). ``via_kv`` (a freeze_grid_mp snapshot's async
    save) swaps the device all-gather for the coordination-KV records
    every rank posted before the commit barrier — no collective, so
    the exchange is legal on a writer thread."""
    by_dev: dict = {}
    for gri, (d, _seg, _lo, _hi) in enumerate(runs):
        by_dev.setdefault(d, []).append(gri)
    if real and via_kv:
        return _read_run_crcs_kv(grid, by_dev, base)
    if not real:
        stage = _MP_CRC_STAGE.setdefault(tmp, {})
        for d, gris in by_dev.items():
            if grid._proc_local_dev[d]:
                stage[d] = (rank, [local_crcs[g] for g in gris])
        return dict(stage)
    from . import comm

    K = max((len(v) for v in by_dev.values()), default=0)
    table = np.zeros((grid.n_dev, 1 + 2 * K), dtype=np.uint32)
    for d, gris in by_dev.items():
        if grid._proc_local_dev[d]:
            table[d, 0] = rank + 1
            for k, g in enumerate(gris):
                table[d, 1 + 2 * k] = 1  # presence marker
                table[d, 2 + 2 * k] = local_crcs[g]
    full = comm.host_all_gather(grid.mesh, table)[0]
    out = {}
    for d, gris in by_dev.items():
        if full[d, 0] > 0:
            out[d] = (int(full[d, 0]) - 1,
                      [int(full[d, 2 + 2 * k]) for k in range(len(gris))
                       if full[d, 1 + 2 * k] == 1])
    return out


def _save_process_slice(grid, filename, meta, cells, offsets, sizes, counts,
                        fixed_spec, fixed_bytes, var_spec, header_size=0,
                        sidecar=False, sidecar_chunk_bytes=None,
                        sidecar_extra=None):
    """Two-phase-commit multi-process save.

    Every process writes its OWN cells' payload runs — the reference's
    collective MPI-IO write with per-rank file views
    (dccrg.hpp:1594-1659) — but into ``<file>.mp-tmp``, never the final
    name, recording a CRC32 per run as it streams:

    1. **prepare** — the meta-writing rank lays down the (replicated)
       metadata + cell/offset table and pre-sizes the temp file; a
       timeout-guarded barrier releases the slice writers; every rank
       pwrites its runs (same one-deep prefetch pipeline as the
       single-controller path) and fsyncs.
    2. **commit** — a second barrier collects every rank's run CRCs
       (comm.host_all_gather on real meshes); the committing rank
       re-reads the temp file, verifies EVERY slice against its
       writer's CRC (raising :class:`~dccrg_tpu.coord
       .CheckpointCommitError` naming the dead/torn ranks on any
       mismatch or missing slice), fsyncs, and atomically renames.

    A rank death or I/O fault at ANY rank/phase therefore leaves
    either the old or the new checkpoint bitwise intact under the
    final name; a lost rank turns into a
    :class:`~dccrg_tpu.coord.BarrierTimeoutError` instead of a hang.
    With ``sidecar``, the committing rank also writes the resilience
    CRC32 sidecar extended with the per-rank slice table ``[dev, rank,
    lo, hi, crc]`` so a salvage load can name the dead rank's cells."""
    import jax

    from . import coord

    real = jax.process_count() > 1  # vs. a faked test split
    rank = coord.process_rank(grid)
    writes_meta = getattr(grid, "_ckpt_writes_meta", None)
    if writes_meta is None:
        writes_meta = (jax.process_index() == 0) if real else True
    commits = getattr(grid, "_ckpt_commits", None)
    if commits is None:
        commits = writes_meta
    tmp = filename + MP_TMP_SUFFIX
    # per-grid save-attempt epoch in every barrier tag: ranks ENTER the
    # save collectively even when a previous attempt failed at
    # different points on different ranks, so tagging by attempt
    # re-aligns the whole barrier sequence on a collective retry
    # (coord.barrier's per-tag counters cover everything else).
    # A freeze_grid_mp snapshot counts through its SOURCE grid
    # (_mp_epoch_src): bumping only the shallow copy would hand the
    # next save the same attempt number and collide its barrier tags
    attempt_src = getattr(grid, "_mp_epoch_src", None) or grid
    attempt = getattr(attempt_src, "_mp_save_epoch", 0) + 1
    attempt_src._mp_save_epoch = attempt
    grid._mp_save_epoch = attempt
    base = f"{os.path.basename(filename)}#{attempt}"
    end = int(offsets[-1] + sizes[-1]) if len(cells) else len(meta)
    runs = _device_runs(grid.n_dev, grid.plan.owner, offsets, sizes)

    # -- phase 1: prepare — meta + slice runs into the temp file ------
    faults.fire("checkpoint.mp", phase="meta", rank=rank, path=filename)
    if writes_meta:
        _MP_CRC_STAGE.pop(tmp, None)  # fresh attempt (faked protocol)
        with open(tmp, "wb") as f:
            f.write(meta)
            pairs = np.empty((len(cells), 2), dtype=np.uint64)
            pairs[:, 0] = cells
            pairs[:, 1] = offsets
            f.write(pairs.tobytes())
            f.truncate(end)  # pre-size so every process can pwrite
            f.flush()
            os.fsync(f.fileno())
    coord.barrier(f"save_prepare:{base}")

    from concurrent.futures import ThreadPoolExecutor

    mine = [g for g, r in enumerate(runs) if grid._proc_local_dev[r[0]]]
    local_crcs: dict = {g: 0 for g in mine}
    with open(tmp, "r+b") as f, ThreadPoolExecutor(1) as pool:
        # runs of consecutive local cells share one seek; the same
        # one-deep prefetch pipeline as the single-controller path, so
        # the shard pull of piece k+1 overlaps the file write of k
        pieces = [
            (g, s == 0, runs[g][1][s : s + CHUNK], runs[g][2])
            for g in mine
            for s in range(0, len(runs[g][1]), CHUNK)
        ]

        def assemble(piece):
            return _chunk_bytes(grid, cells, counts, 0, fixed_spec,
                                fixed_bytes, var_spec,
                                reader=grid._shard_read, idx=piece[2])

        fut = pool.submit(assemble, pieces[0]) if pieces else None
        for i, (g, is_run_start, _idx, lo) in enumerate(pieces):
            buf = fut.result()
            if i + 1 < len(pieces):
                fut = pool.submit(assemble, pieces[i + 1])
            faults.fire("checkpoint.mp", phase="slice", rank=rank,
                        piece=i, path=filename)
            if is_run_start:
                f.seek(lo)
            f.write(buf)
            local_crcs[g] = zlib.crc32(buf, local_crcs[g])
        f.flush()
        os.fsync(f.fileno())
    faults.fire("checkpoint.mp", phase="written", rank=rank, path=filename)

    # -- phase 2: commit barrier, CRC exchange, verify + publish ------
    via_kv = real and bool(getattr(grid, "_ckpt_crc_via_kv", False))
    if via_kv:
        # post BEFORE the barrier: once it releases, every surviving
        # rank's record is already readable (KV writes are ordered
        # before the poster's barrier arrival)
        _post_run_crcs_kv(grid, runs, local_crcs, rank, base)
    coord.barrier(f"save_commit:{base}")
    crc_table = _gather_run_crcs(grid, runs, local_crcs, rank, tmp, real,
                                 via_kv=via_kv, base=base)
    status_key = f"dccrg_commit:{base}"
    client = coord._coordination_client() if real else None
    if commits:
        # the metadata + offset table is REPLICATED state — the
        # committing rank recomputes its exact bytes locally, so a tear
        # in the meta region needs no CRC exchange to be caught
        pairs = np.empty((len(cells), 2), dtype=np.uint64)
        pairs[:, 0] = cells
        pairs[:, 1] = offsets
        # crc32 reads the buffer protocol directly: no tobytes() copy
        # of a table that is ~2 GB at the 512^3 scale
        meta_crc = zlib.crc32(pairs, zlib.crc32(meta))
        commit_err = None
        try:
            _commit_process_slices(grid, filename, tmp, runs, crc_table,
                                   header_size, sidecar,
                                   sidecar_chunk_bytes, rank,
                                   meta_crc & 0xFFFFFFFF,
                                   len(meta) + 16 * len(cells),
                                   sidecar_extra=sidecar_extra)
        except faults.InjectedRankDeath:
            raise  # a dead rank coordinates nothing
        except Exception as e:  # noqa: BLE001 - re-raised below
            commit_err = e
        _MP_CRC_STAGE.pop(tmp, None)
        if client is not None:
            # publish the outcome BEFORE the done barrier: peers read
            # it right after and learn of an abort immediately instead
            # of mistaking a live-but-aborted committer for a dead one.
            # allow_overwrite: a restarted job (fresh Grid, reset
            # attempt epoch) may legitimately reuse a key — a stale
            # value from a previous incarnation must not crash a save
            # that already published its rename
            status = ("ok" if commit_err is None
                      else f"commit aborted on rank {rank}: {commit_err}")
            try:
                client.key_value_set(status_key, status,
                                     allow_overwrite=True)
            except TypeError:  # older jaxlib without the kwarg
                try:
                    client.key_value_set(status_key, status)
                except Exception:  # pragma: no cover - key collision
                    pass
        if commit_err is not None:
            try:
                coord.barrier(f"save_done:{base}")
            except Exception:  # the abort outranks a straggling peer
                pass
            raise commit_err
    coord.barrier(f"save_done:{base}")
    if not commits and client is not None:
        try:
            status = client.blocking_key_value_get(status_key, 10_000)
        except Exception:  # committer gone: the barrier outcome governs
            status = None
        if status is not None and status != "ok":
            raise coord.CheckpointCommitError(
                f"{filename}: {status}; the previous checkpoint is "
                "untouched")


def _commit_process_slices(grid, filename, tmp, runs, crc_table,
                           header_size, sidecar, sidecar_chunk_bytes, rank,
                           meta_crc, payload_start, sidecar_extra=None):
    """The committing rank's half of the two-phase save: verify the
    replicated metadata block (against ``meta_crc``, recomputed
    locally) and every payload slice of the temp file against its
    writer's CRC, then atomically publish (old-sidecar drop, rename,
    dir fsync, new sidecar) — the same rename discipline as
    resilience.save_checkpoint's single-controller path."""
    from . import coord, resilience

    faults.fire("checkpoint.mp", phase="commit", rank=rank, path=filename)
    by_dev: dict = {}
    for gri, (d, _seg, lo, hi) in enumerate(runs):
        by_dev.setdefault(d, []).append((gri, lo, hi))
    missing = sorted(d for d in by_dev if d not in crc_table
                     or len(crc_table[d][1]) != len(by_dev[d]))
    if missing:
        raise coord.CheckpointCommitError(
            f"{filename}: commit aborted — no slice CRCs from device(s) "
            f"{missing} (their rank died before the commit barrier); the "
            "previous checkpoint is untouched",
            ranks=[crc_table[d][0] for d in missing if d in crc_table])
    # ONE sequential pass over the temp file yields all three CRC
    # layouts: the metadata block (= chunk 0 of the tiling), the
    # sidecar's chunk tiling, and the per-rank slice spans (globally
    # sorted for the streaming overlay, then unpermuted)
    entries = [(d, k, lo, hi)
               for d in sorted(by_dev)
               for k, (_gri, lo, hi) in enumerate(by_dev[d])]
    order = sorted(range(len(entries)), key=lambda i: entries[i][2])
    cb = sidecar_chunk_bytes or resilience.CRC_CHUNK
    file_bytes = os.path.getsize(tmp)
    chunk_ranges = resilience._chunk_ranges(payload_start, file_bytes, cb)
    chunk_crcs, sorted_crcs = resilience._stream_crcs(
        tmp, chunk_ranges, [(entries[i][2], entries[i][3]) for i in order],
        cb)
    got = [0] * len(entries)
    for k, i in enumerate(order):
        got[i] = sorted_crcs[k]
    if chunk_crcs[0] != meta_crc:
        raise coord.CheckpointCommitError(
            f"{filename}: commit aborted — the metadata/offset-table "
            "block of the temp file does not match its replicated bytes "
            "(torn prepare write); the previous checkpoint is untouched")
    slices = []  # [dev, rank, lo, hi, crc] rows for the sidecar
    torn = []
    for i, (d, k, lo, hi) in enumerate(entries):
        wrank, want = crc_table[d]
        if got[i] != (want[k] & 0xFFFFFFFF):
            torn.append((d, wrank))
        slices.append([int(d), int(wrank), int(lo), int(hi),
                       int(want[k] & 0xFFFFFFFF)])
    if torn:
        devs = sorted({d for d, _r in torn})
        ranks = sorted({r for _d, r in torn})
        raise coord.CheckpointCommitError(
            f"{filename}: commit aborted — slice(s) of device(s) {devs} "
            f"(written by rank(s) {ranks}) fail their CRC32 in the temp "
            "file (torn write / rank died mid-slice); the previous "
            "checkpoint is untouched", ranks=ranks)
    rec = None
    if sidecar:
        rec = {"format": resilience.SIDECAR_FORMAT, "chunk_bytes": cb,
               "file_bytes": file_bytes, "payload_start": payload_start,
               "header_size": header_size, "crc32": chunk_crcs,
               "slices": slices}
        if sidecar_extra:
            rec.update(sidecar_extra)
    # drop any previous sidecar BEFORE the rename (same reasoning as
    # resilience.save_checkpoint: never a new file under a stale
    # record), keeping its bytes to restore if the rename itself fails
    side = resilience.sidecar_path(filename)
    old_side = None
    if os.path.exists(side):
        with open(side, "rb") as sf:
            old_side = sf.read()
        os.unlink(side)
    try:
        os.replace(tmp, filename)
    except OSError:
        resilience._restore_sidecar(side, old_side)
        raise
    resilience._fsync_dir(os.path.dirname(os.path.abspath(filename)))
    faults.fire("checkpoint.mp", phase="publish", rank=rank, path=filename)
    if rec is not None:
        resilience._write_sidecar_record(side, rec)


def _grid_skeleton_matches(grid, mapping, hood_len, topology, geometry):
    if mapping != grid.mapping:
        raise ValueError(f"file grid {mapping} does not match {grid.mapping}")
    if topology != grid.topology:
        raise ValueError("file periodicity does not match the grid")
    if hood_len != grid._hood_len:
        raise ValueError(
            f"file neighborhood length {hood_len} != grid {grid._hood_len}"
        )
    if geometry.geometry_id != grid.geometry.geometry_id:
        raise ValueError("file geometry kind does not match the grid")
    if geometry.to_bytes() != grid.geometry.to_bytes():
        raise ValueError(
            "file geometry parameters do not match the grid (same kind, "
            "different start/cell lengths or coordinate arrays)"
        )


def _scatter_payloads(grid, raw, cells, offsets, fixed_spec, fixed_bytes,
                      var_spec):
    """Stream payloads from ``raw`` (memory map) into fresh device
    arrays. Two passes when variable fields exist: fixed parts (incl.
    counts) first, then the ragged rows (dccrg.hpp:2108-2123)."""
    from .grid import put_sharded

    hosts = {}
    for name, (shape, dtype) in grid.fields.items():
        hosts[name] = np.zeros((grid.n_dev, grid.plan.R) + shape, dtype=dtype)

    if grid._multiproc:
        # each process scatters only its own cells' payloads: the final
        # put_sharded serves only addressable shards, so foreign rows
        # in `hosts` are never consumed (per-rank collective read,
        # dccrg.hpp:2108-2390)
        keep = grid._proc_local_dev[grid.plan.owner[
            np.searchsorted(grid.plan.cells, cells)]]
        cells = cells[keep]
        offsets = offsets[keep]

    # pass 1: fixed-size parts at each cell's offset
    for start in range(0, len(cells), CHUNK):
        ids = cells[start : start + CHUNK]
        offs = offsets[start : start + CHUNK].astype(np.int64)
        dev, rows = grid._host_rows(ids)
        idx = offs[:, None] + np.arange(fixed_bytes, dtype=np.int64)[None, :]
        payload = raw[idx]
        col = 0
        for name, shape, dtype, nbytes in fixed_spec:
            vals = payload[:, col : col + nbytes].copy().view(dtype).reshape(
                (len(ids),) + shape
            )
            hosts[name][dev, rows] = vals
            col += nbytes

    # pass 2: ragged rows, sized by the counts read in pass 1
    for name, count_field, row_shape, dtype, row_bytes, cap in var_spec:
        for start in range(0, len(cells), CHUNK):
            ids = cells[start : start + CHUNK]
            offs = offsets[start : start + CHUNK].astype(np.int64)
            dev, rows = grid._host_rows(ids)
            c = hosts[count_field][dev, rows].astype(np.int64)
            if np.any(c < 0) or np.any(c > cap):
                raise ValueError(
                    f"corrupt counts for variable field {name!r} in file"
                )
            # variable fields follow the fixed block; earlier variable
            # fields (sorted order) of the same cell come first
            base = offs + fixed_bytes
            for vn, vcf, _rs, _dt, vrb, _cap in var_spec:
                if vn == name:
                    break
                base = base + hosts[vcf][dev, rows].astype(np.int64) * vrb
            # vectorized ragged read: fancy-index gathers over row
            # sub-blocks (repeat/cumsum, the save side's pattern) —
            # no per-cell Python (the reference's multi-pass collective
            # read has no serial tail either, dccrg.hpp:2108-2390).
            # The byte-index matrix costs index-dtype-size bytes per
            # payload byte, so it is built in bounded sub-blocks with
            # the narrowest index dtype the file size allows.
            total = int(c.sum())
            if total == 0:
                continue
            cell_of_row = np.repeat(np.arange(len(ids)), c)
            row_within = (np.arange(total, dtype=np.int64)
                          - np.repeat(np.cumsum(c) - c, c))
            starts = base[cell_of_row] + row_within * row_bytes
            idt = np.uint32 if raw.size < (1 << 32) else np.int64
            span = np.arange(row_bytes, dtype=idt)[None, :]
            blk = max(1, (8 << 20) // row_bytes)  # <=64 MB of u32 idx
            for s in range(0, total, blk):
                e = min(s + blk, total)
                idx = starts[s:e, None].astype(idt) + span
                vals = raw[idx].copy().view(dtype).reshape(
                    (e - s,) + row_shape)
                hosts[name][dev[cell_of_row[s:e]], rows[cell_of_row[s:e]],
                            row_within[s:e]] = vals

    for name in grid.fields:
        grid.data[name] = put_sharded(hosts[name], grid._sharding())
    # a wholesale load resets the delta-checkpoint baseline: every
    # field's saved bytes may now differ from the previous chain's
    grid._mark_ckpt_dirty()


def load_grid_data(grid, filename: str, header_size: int = 0,
                   variable=None) -> bytes:
    """Rebuild structure and data from a file written by
    save_grid_data into an ALREADY-CONSTRUCTED grid whose parameters
    are validated against the file (a mismatched restart fails loudly
    rather than corrupting). Returns the user header. For restart from
    nothing but the file, use :func:`load_grid` / ``Grid.from_file``."""
    raw = np.memmap(filename, dtype=np.uint8, mode="r")
    header = bytes(raw[:header_size])
    mapping, hood_len, topology, geometry, cells, offsets, _ = parse_metadata(
        raw, header_size
    )
    _grid_skeleton_matches(grid, mapping, hood_len, topology, geometry)
    fixed_spec, fixed_bytes, var_spec = _payload_spec(grid, variable)
    grid.load_cells(cells)
    _scatter_payloads(grid, raw, cells, offsets, fixed_spec, fixed_bytes, var_spec)
    _load_done_barrier()
    return header


def _load_done_barrier():
    """On real multi-process meshes, hold every rank until all have
    finished scattering their slices — a fast rank must not proceed to
    overwrite/replace the file while a peer is still reading it. A
    no-op (one process_count check) on a single controller; tagged
    without the filename because salvage loads read per-rank temp
    names that must not desynchronize the barrier sequence.

    Best effort by design: THIS rank's load already completed, so a
    peer that cannot answer (died mid-recovery — exactly when a
    survivor restores from checkpoint) must not turn a successful
    local load into a failure. The timeout is logged loudly instead."""
    import jax

    if jax.process_count() > 1:
        import logging

        from . import coord

        try:
            coord.barrier("load_done")
        except Exception as e:  # noqa: BLE001 - load is locally done
            logging.getLogger("dccrg_tpu.checkpoint").warning(
                "load_done barrier did not complete (%s); this rank's "
                "load IS complete — do not overwrite the file until "
                "the lost peers are accounted for", e)


def load_grid(filename: str, cell_data, mesh=None, header_size: int = 0,
              variable=None, load_balancing_method: str | None = None):
    """Restart from nothing but the file: reconstruct mapping,
    topology, geometry, neighborhood length and the AMR cell set from
    the metadata (the reference's start_loading_grid_data,
    dccrg.hpp:1815-2105), partition the cells, stream the payloads in.

    ``cell_data`` is the field spec (the user's side of the
    serialization contract, as with the reference's Cell_Data type);
    returns ``(grid, header)``."""
    from .grid import Grid

    raw = np.memmap(filename, dtype=np.uint8, mode="r")
    header = bytes(raw[:header_size])
    mapping, hood_len, topology, geometry, cells, offsets, _ = parse_metadata(
        raw, header_size
    )
    kind, params = geometry.spec()
    grid = (
        Grid(cell_data=cell_data)
        .set_initial_length(tuple(int(v) for v in mapping.length.get()))
        .set_maximum_refinement_level(mapping.max_refinement_level)
        .set_periodic(*(topology.is_periodic(d) for d in range(3)))
        .set_neighborhood_length(hood_len)
        .set_geometry(kind, **params)
    )
    if load_balancing_method is not None:
        grid.set_load_balancing_method(load_balancing_method)
    grid.initialize(mesh)
    fixed_spec, fixed_bytes, var_spec = _payload_spec(grid, variable)
    grid.load_cells(cells)
    _scatter_payloads(grid, raw, cells, offsets, fixed_spec, fixed_bytes, var_spec)
    _load_done_barrier()
    return grid, header
