"""Resilience layer: checkpoint integrity, numerics watchdog with
auto-rollback, and OOM-aware degradation.

dccrg is the grid layer of week-long production plasma runs (Vlasiator
survives node loss only through checkpoint/restart), so the framework
must detect, degrade and recover without a human watching. Four
pillars, each exercised end to end by the fault-injection suite
(tests/test_resilience.py, tests/test_checkpoint_integrity.py, driven
by :mod:`dccrg_tpu.faults`):

**Checkpoint integrity** — :func:`save_checkpoint` writes the pinned
``.dc`` byte format (unchanged — golden-file tests still pass)
*atomically*: temp file in the same directory, fsync, rename, with
bounded retries on transient I/O errors; a crash mid-save can never
destroy the previous checkpoint. A sidecar ``<file>.crc`` records a
CRC32 per fixed-size chunk of the final bytes; :func:`load_checkpoint`
verifies it and raises :class:`CheckpointCorruptionError` naming the
bad chunk, or — with ``strict=False`` — salvages every intact chunk
(corrupt cells come back zeroed and are listed in the
:class:`SalvageReport`).

**Numerics watchdog** — :func:`check_finite` runs a device-side
``isfinite`` reduction over the watched fields (one scalar crosses to
the host, a psum-style min via :mod:`dccrg_tpu.comm`);
:func:`assert_finite` turns a trip into a :class:`NumericsError`
naming the offending fields and cells (located host-side by
:func:`dccrg_tpu.verify.find_nonfinite_cells`). ``DCCRG_WATCHDOG=N``
makes ``Grid.run_steps`` self-check every ~N steps.

**Auto-rollback** — :class:`ResilientRunner` wraps a step loop:
checkpoint every C steps, watchdog-check every K; on a trip it dumps a
diagnostic bundle (step, fields, cell ids), rolls back to the last
good checkpoint and resumes, with bounded retries and exponential
backoff before surfacing :class:`ResilienceExhaustedError`.

**OOM degradation** — :func:`guarded_step` dispatches
``Grid.run_steps`` and, on XLA ``RESOURCE_EXHAUSTED`` (real or
injected), walks the fallback chain *current gather mode -> slot-wise
roll -> dense tables*, logging each downgrade; :func:`safe_devices`
probes the backend in a killable subprocess with retries/backoff, for
the CPU-only host benches (``python -m dccrg_tpu.resilience`` is its
CLI). The chip path calls ``jax.devices()`` in process: a chip belongs
to one process, so a probing child would hold it from its parent.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
import zlib
from contextlib import contextmanager
from dataclasses import dataclass, field as dataclass_field

import numpy as np

from . import background
from . import checkpoint as checkpoint_mod
from . import faults, telemetry

logger = logging.getLogger("dccrg_tpu.resilience")

CRC_CHUNK = 1 << 20  # bytes per sidecar checksum chunk
SIDECAR_FORMAT = "dccrg-dc-crc-v1"
SIDECAR_SUFFIX = ".crc"
#: Incremental (delta) checkpoints: a ``.dcd`` file is a valid ``.dc``
#: of the dirty-field sub-schema, chained to a parent save through its
#: sidecar's ``delta`` record (parent file + step + content digest).
DELTA_SUFFIX = ".dcd"
_MAX_CHAIN = 4096  # delta-chain depth bound (cycle backstop)


class CheckpointCorruptionError(ValueError):
    """A checkpoint failed integrity verification. ``bad_chunks`` holds
    the failing sidecar chunk indices (empty when the sidecar itself is
    missing/unreadable)."""

    def __init__(self, msg, bad_chunks=()):
        super().__init__(msg)
        self.bad_chunks = list(bad_chunks)


class DeltaChainError(CheckpointCorruptionError):
    """A delta checkpoint's keyframe+delta chain cannot be restored end
    to end. ``link`` names the broken file; ``chain`` lists the link
    paths resolved so far (keyframe first, when known). The typed
    salvage contract: :func:`dccrg_tpu.supervise.resume_latest` catches
    this and falls back to the last verifying prefix (an older delta or
    the keyframe) instead of failing the resume."""

    def __init__(self, msg, link=None, chain=()):
        super().__init__(msg)
        self.link = link
        self.chain = list(chain)


class NumericsError(RuntimeError):
    """The watchdog found non-finite values. ``details`` maps field
    name -> offending cell ids."""

    def __init__(self, msg, details=None):
        super().__init__(msg)
        self.details = details or {}


class ResilienceExhaustedError(RuntimeError):
    """Every bounded recovery attempt failed; the error is surfaced."""


class DeviceProbeError(RuntimeError):
    """The device backend did not answer within the probe budget."""


class RunInterrupted(RuntimeError):
    """The step loop stopped cleanly at a step boundary because the
    runner's ``interrupt_poll`` requested it — consensus-agreed across
    ranks, so EVERY rank raises this at the same boundary with the
    grid holding exactly ``step`` completed steps. Raised for the
    supervision layer (:mod:`dccrg_tpu.supervise`), which turns it
    into an emergency checkpoint plus a resumable exit."""

    def __init__(self, step: int):
        super().__init__(
            f"run interrupted at the boundary after step {step} "
            "(preemption requested; state is consistent on every rank)")
        self.step = int(step)


# ---------------------------------------------------------------------
# checkpoint integrity: CRC sidecar + atomic save + verifying load
# ---------------------------------------------------------------------

def sidecar_path(filename: str) -> str:
    return filename + SIDECAR_SUFFIX


def _chunk_ranges(payload_start, file_bytes, chunk_bytes, n=None):
    """Byte ranges of the sidecar chunks: chunk 0 is exactly the
    metadata block [0, payload_start) — mapping / geometry / offset
    table, whose corruption is never salvageable — and chunks >= 1 tile
    the payload in ``chunk_bytes`` pieces, so a bad payload chunk maps
    onto a bounded set of cells."""
    ranges = [(0, payload_start)]
    pos = payload_start
    while pos < file_bytes or (n is not None and len(ranges) < n):
        ranges.append((pos, min(pos + chunk_bytes, file_bytes)))
        pos += chunk_bytes
    return ranges


def _range_crcs(path: str, ranges, block: int = CRC_CHUNK) -> list:
    """CRC32 of each ``[lo, hi)`` byte range of ``path``, streamed
    ``block`` bytes at a time — ``zlib.crc32`` is incremental, so no
    range ever materializes in host RAM (at 512^3 the checkpoint is
    multi-GB and the save path already streams precisely to bound host
    memory; the checksum passes must too). A range truncated away
    checksums only the bytes that exist, so it mismatches — exactly
    what the caller needs it to do."""
    out = []
    with open(path, "rb") as f:
        for lo, hi in ranges:
            f.seek(int(lo))
            crc, left = 0, int(hi) - int(lo)
            while left > 0:
                buf = f.read(min(block, left))
                if not buf:
                    break
                crc = zlib.crc32(buf, crc)
                left -= len(buf)
            out.append(crc & 0xFFFFFFFF)
    return out


def _stream_crcs(path: str, chunk_ranges, spans, block: int = CRC_CHUNK):
    """ONE sequential streamed pass computing CRC32s of both the chunk
    tiling (``chunk_ranges``: contiguous, in order) and an overlay of
    ``spans`` (sorted by start, non-overlapping — the two-phase save's
    per-rank slice runs). Returns ``(chunk_crcs, span_crcs)``. The
    commit rank needs both layouts over the same bytes; reading the
    (multi-GB at 512^3) temp file once instead of twice halves the
    publish-path disk traffic."""
    chunk_crcs = []
    span_crcs = [0] * len(spans)
    si = 0
    with open(path, "rb") as f:
        for lo, hi in chunk_ranges:
            f.seek(int(lo))
            crc, pos, left = 0, int(lo), int(hi) - int(lo)
            while left > 0:
                buf = f.read(min(block, left))
                if not buf:
                    break
                crc = zlib.crc32(buf, crc)
                blo, bhi = pos, pos + len(buf)
                while si < len(spans) and spans[si][1] <= blo:
                    si += 1  # spans fully behind this block are done
                j = si
                while j < len(spans) and spans[j][0] < bhi:
                    s = max(int(spans[j][0]), blo)
                    e = min(int(spans[j][1]), bhi)
                    if s < e:
                        span_crcs[j] = zlib.crc32(buf[s - blo:e - blo],
                                                  span_crcs[j])
                    j += 1
                pos = bhi
                left -= len(buf)
            chunk_crcs.append(crc & 0xFFFFFFFF)
    return chunk_crcs, [c & 0xFFFFFFFF for c in span_crcs]


def _sidecar_record(path: str, header_size: int = 0,
                    chunk_bytes: int = CRC_CHUNK) -> dict:
    """The sidecar record for ``path``'s current bytes, checksummed in
    ``chunk_bytes`` streams (the metadata parse pages in only the head
    of a memory map — the payload never crosses to host RAM whole)."""
    file_bytes = os.path.getsize(path)
    raw = np.memmap(path, dtype=np.uint8, mode="r")
    payload_start = checkpoint_mod.parse_metadata(raw, header_size)[6]
    del raw
    ranges = _chunk_ranges(payload_start, file_bytes, chunk_bytes)
    crcs = _range_crcs(path, ranges, chunk_bytes)
    return {"format": SIDECAR_FORMAT, "chunk_bytes": chunk_bytes,
            "file_bytes": file_bytes, "payload_start": payload_start,
            "header_size": header_size, "crc32": crcs}


def _write_sidecar_record(side: str, rec: dict) -> None:
    tmp = side + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(rec, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, side)


def write_sidecar(filename: str, header_size: int = 0,
                  chunk_bytes: int = CRC_CHUNK) -> str:
    """Checksum ``filename`` into its ``.crc`` sidecar: CRC32 of the
    metadata block (chunk 0), then one CRC32 per ``chunk_bytes`` of
    payload. The ``.dc`` file itself is untouched (the golden byte
    format stays pinned)."""
    side = sidecar_path(filename)
    _write_sidecar_record(side, _sidecar_record(filename, header_size,
                                                chunk_bytes))
    return side


def read_sidecar(filename: str):
    """The parsed sidecar record, or None when none exists. An
    unparseable sidecar raises CheckpointCorruptionError (corruption
    hit the sidecar itself — the checkpoint cannot be trusted)."""
    side = sidecar_path(filename)
    if not os.path.exists(side):
        return None
    try:
        with open(side) as f:
            rec = json.load(f)
        if rec.get("format") != SIDECAR_FORMAT:
            raise ValueError(f"unknown sidecar format {rec.get('format')!r}")
        # a sidecar corrupted at rest can still parse as JSON — reject
        # implausible geometry here rather than hanging or crashing
        # the chunk-range math downstream
        cb = int(rec["chunk_bytes"])
        fb = int(rec["file_bytes"])
        ps = int(rec["payload_start"])
        crcs = rec["crc32"]
        if (cb <= 0 or fb < 0 or not 0 <= ps <= fb
                or not isinstance(crcs, list)
                or not all(isinstance(c, int) for c in crcs)):
            raise ValueError("implausible sidecar geometry")
        # the crc list must cover the whole recorded file: a sidecar
        # whose tail entries were lost (still valid JSON) would
        # otherwise leave trailing payload chunks silently unverified
        # (_bad_chunks zips against the shorter list)
        want_chunks = 1 + max(0, -(-(fb - ps) // cb))
        if len(crcs) != want_chunks:
            raise ValueError(
                f"sidecar records {len(crcs)} chunk crc(s), geometry "
                f"implies {want_chunks}")
        # two-phase multi-process saves extend the record with a
        # per-rank slice table [dev, rank, lo, hi, crc]; reject a
        # mangled one here like the rest of the geometry
        sl = rec.get("slices")
        if sl is not None and not (
                isinstance(sl, list)
                and all(isinstance(s, list) and len(s) == 5
                        and all(isinstance(v, int) for v in s)
                        and 0 <= s[2] <= s[3] <= fb
                        for s in sl)):
            raise ValueError("implausible per-rank slice table")
        # incremental saves extend the record with a delta subrecord
        # (dirty-field list + parent link); reject a mangled one here
        # so the chain walk never dereferences garbage
        d = rec.get("delta")
        if d is not None:
            p = d.get("parent") if isinstance(d, dict) else None
            if not (isinstance(d, dict)
                    and isinstance(d.get("fields"), list)
                    and all(isinstance(f, str) for f in d["fields"])
                    and isinstance(d.get("step"), int)
                    and isinstance(p, dict)
                    and isinstance(p.get("file"), str) and p["file"]
                    and os.path.basename(p["file"]) == p["file"]
                    and isinstance(p.get("step"), int)
                    and isinstance(p.get("digest"), int)):
                raise ValueError("implausible delta record")
        # SDC-audit saves extend the record with a payload fingerprint
        # ({field: [s1, s2, nbytes]}, see resilience.audit_checkpoint);
        # reject a mangled one like the rest of the geometry
        integ = rec.get("integrity")
        if integ is not None and not (
                isinstance(integ, dict)
                and all(isinstance(k, str) and isinstance(v, list)
                        and len(v) == 3
                        and all(isinstance(x, int) for x in v)
                        and v[2] > 0
                        for k, v in integ.items())):
            raise ValueError("implausible integrity record")
        return rec
    except (ValueError, KeyError, TypeError) as e:
        raise CheckpointCorruptionError(
            f"unreadable checksum sidecar {side}: {e}") from e


def _rec_ranges(rec) -> list:
    return _chunk_ranges(int(rec["payload_start"]), int(rec["file_bytes"]),
                         int(rec["chunk_bytes"]), n=len(rec["crc32"]))


def _chunk_name(i: int, ranges) -> str:
    if i >= len(ranges):  # the trailing-garbage sentinel
        return "trailing bytes past the recorded file size"
    lo, hi = ranges[i]
    what = "metadata block" if i == 0 else f"payload chunk {i}"
    return f"{what} (bytes {lo}-{max(lo, hi - 1)})"


def _bad_chunks(filename: str, rec) -> list:
    """Indices of sidecar chunks whose CRC32 no longer matches,
    streamed ``chunk_bytes`` at a time (never the whole file in RAM).
    Chunks truncated away count as bad; garbage appended past the
    recorded size is reported as the sentinel index one past the last
    chunk — the recorded range may still be fully intact, so salvage
    just trims the tail instead of zeroing good cells."""
    want = rec["crc32"]
    got = _range_crcs(filename, _rec_ranges(rec), int(rec["chunk_bytes"]))
    bad = [i for i, (g, w) in enumerate(zip(got, want))
           if g != (w & 0xFFFFFFFF)]
    if os.path.getsize(filename) > int(rec["file_bytes"]):
        bad.append(len(want))
    return bad


def _bad_slices(filename: str, rec) -> list:
    """Indices of per-rank slice entries — two-phase multi-process
    saves record ``[dev, rank, lo, hi, crc]`` per written run — whose
    bytes no longer match. The attribution layer over the chunk CRCs:
    a bad chunk says WHERE the corruption is, a bad slice says WHOSE
    write it was (the dead/torn rank a salvage report names)."""
    sl = rec.get("slices") or []
    if not sl:
        return []
    got = _range_crcs(filename, [(int(s[2]), int(s[3])) for s in sl])
    return [i for i, s in enumerate(sl)
            if got[i] != (int(s[4]) & 0xFFFFFFFF)]


def verify_checkpoint(filename: str, require_sidecar: bool = True) -> list:
    """Verify ``filename`` against its sidecar. Returns the bad chunk
    indices (empty = intact). Raises CheckpointCorruptionError when the
    sidecar is missing and ``require_sidecar``."""
    rec = read_sidecar(filename)
    if rec is None:
        if require_sidecar:
            raise CheckpointCorruptionError(
                f"{filename}: no checksum sidecar ({sidecar_path(filename)}); "
                "wrote with a pre-resilience save, or the sidecar was lost. "
                "Load with strict=False to proceed unverified."
            )
        return []
    return _bad_chunks(filename, rec)


# ---------------------------------------------------------------------
# incremental (delta) checkpoints: dirty-field saves chained to a
# keyframe through sidecar parent links
# ---------------------------------------------------------------------

def record_digest(rec) -> int:
    """Content digest of a sidecar record — CRC32 over the per-chunk
    CRC list + file size, chained with the parent's digest for delta
    records. Derived (never stored), so a tampered sidecar changes the
    digest and breaks its children's recorded parent links; together
    with per-link byte verification this pins a chain to the exact
    saves that produced it: a parent *replaced* by a different save
    under the same name is detected even though its own CRCs verify."""
    import struct

    crcs = np.asarray([int(c) & 0xFFFFFFFF for c in rec["crc32"]],
                      dtype=np.uint32)
    d = zlib.crc32(crcs.tobytes(),
                   zlib.crc32(struct.pack("<Q", int(rec["file_bytes"]))))
    delta = rec.get("delta")
    if delta:
        d = zlib.crc32(
            struct.pack("<I", int(delta["parent"]["digest"]) & 0xFFFFFFFF),
            d)
    return d & 0xFFFFFFFF


def is_delta_checkpoint(filename: str, rec=None) -> bool:
    """True when ``filename`` is an incremental (delta) save — by its
    ``.dcd`` suffix or its sidecar's delta record."""
    if filename.endswith(DELTA_SUFFIX):
        return True
    if rec is None:
        try:
            rec = read_sidecar(filename)
        except CheckpointCorruptionError:
            return False
    return bool(rec and rec.get("delta"))


def chain_links(filename: str) -> list:
    """Resolve ``filename``'s keyframe+delta chain from sidecar parent
    links: ``[(path, record)]`` KEYFRAME FIRST (a plain full
    checkpoint is its own one-link chain). Structural resolution only
    — byte verification is :func:`verify_chain`'s job — but every
    parent's recorded content digest is checked against the child's
    link here, so a replaced ancestor is named. Raises
    :class:`DeltaChainError` naming the broken link on a missing
    file/sidecar, a digest mismatch, or a cycle."""
    links, seen = [], set()
    cur = os.path.abspath(filename)
    dirpath = os.path.dirname(cur)
    expect = None  # the child's recorded parent digest
    while True:
        done = [p for p, _r in reversed(links)]
        if cur in seen or len(links) >= _MAX_CHAIN:
            raise DeltaChainError(
                f"{filename}: delta parent links form a cycle at {cur}",
                link=cur, chain=done)
        seen.add(cur)
        if not os.path.exists(cur):
            raise DeltaChainError(
                f"{filename}: chain link {cur} is missing (its keyframe "
                "or an intermediate delta was deleted)", link=cur,
                chain=done)
        try:
            rec = read_sidecar(cur)
        except CheckpointCorruptionError as e:
            raise DeltaChainError(
                f"{filename}: chain link {cur} has an unreadable "
                f"sidecar ({e})", link=cur, chain=done) from e
        if rec is None:
            raise DeltaChainError(
                f"{filename}: chain link {cur} has no sidecar — a delta "
                "chain cannot be interpreted without one (the "
                "dirty-field list and parent link live there)",
                link=cur, chain=done)
        if expect is not None and record_digest(rec) != expect:
            raise DeltaChainError(
                f"{filename}: chain link {cur} does not match its "
                f"child's recorded parent digest {expect:#010x} — the "
                "parent was overwritten by a different save", link=cur,
                chain=done)
        links.append((cur, rec))
        delta = rec.get("delta")
        if not delta:
            break
        expect = int(delta["parent"]["digest"]) & 0xFFFFFFFF
        cur = os.path.join(dirpath, delta["parent"]["file"])
    links.reverse()
    return links


def verify_chain(filename: str, assume_ok=(), _memo=None) -> list:
    """Verify every link of ``filename``'s chain — bytes against each
    sidecar's chunk CRCs plus the parent digest links — and return the
    link paths, keyframe first. Raises :class:`DeltaChainError` naming
    the FIRST broken link in chain order (a broken ancestor
    invalidates every later delta). ``assume_ok`` paths skip the byte
    pass (the process that just saved and verified them can vouch);
    ``_memo`` caches per-file results across calls in one sweep."""
    links = chain_links(filename)
    memo = _memo if _memo is not None else {}
    vouched = {os.path.abspath(p) for p in assume_ok}
    for path, rec in links:
        if path in vouched:
            continue
        bad = memo.get(path)
        if bad is None:
            bad = memo[path] = _bad_chunks(path, rec)
        if bad:
            names = ", ".join(_chunk_name(i, _rec_ranges(rec))
                              for i in bad)
            raise DeltaChainError(
                f"{filename}: chain link {path} fails verification "
                f"({names})", link=path, chain=[p for p, _r in links])
    return [p for p, _r in links]


def _chain_scratch(path: str) -> str:
    """Writable scratch path for a chain materialization: next to the
    checkpoint when its directory is writable (same filesystem — a
    multi-GB reconstruction never lands on a small tmpfs — and an
    orphan is swept by ``checkpoint.stale_temp_files``), else the
    system temp dir: a READ-ONLY checkpoint directory (archived
    snapshot, RO-mounted shared volume) must stay resumable, exactly
    like full ``.dc`` saves which load in place."""
    dirpath = os.path.dirname(os.path.abspath(path))
    if os.access(dirpath, os.W_OK):
        return path + f".chain.{os.getpid()}"
    import tempfile

    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".chain.")
    os.close(fd)
    return tmp


def _cell_data_fields(cell_data) -> dict:
    """Normalize a user ``cell_data`` spec (or ``Grid.fields``) into
    ``{name: (shape tuple, np.dtype)}`` — the serialization contract
    the chain materializer computes field column offsets from."""
    out = {}
    for name, spec in cell_data.items():
        if isinstance(spec, tuple):
            shape, dtype = spec
        else:
            shape, dtype = (), spec
        out[name] = (tuple(shape), np.dtype(dtype))
    return out


def materialize_chain(filename: str, out_path: str, cell_data,
                      variable=None, verify: bool = True,
                      _memo=None) -> list:
    """Reconstruct the full checkpoint bytes of delta ``filename`` into
    ``out_path``: copy the keyframe, then overlay each delta's
    dirty-field columns in chain order (each cell's fixed-field block
    lives at its offset-table position, so the overlay is a strided
    byte scatter — vectorized, chunked, never the whole payload in
    RAM). The result is bitwise identical to the full save an
    uninterrupted run would have written at the delta's step (pinned by
    the chain tests and the fuzz oracle). ``cell_data`` is the caller's
    field schema (``Grid.fields`` works too); returns the chain's link
    paths. On multi-process meshes every rank reconstructs its own
    scratch copy (``out_path`` must be per-process, e.g. pid-suffixed)
    and the collective load barrier downstream keeps them aligned."""
    import shutil

    links = chain_links(filename)
    if verify:
        verify_chain(filename, _memo=_memo)
    key_path, key_rec = links[0]
    fields = _cell_data_fields(cell_data)
    fixed_spec, fixed_bytes, _var = checkpoint_mod._payload_spec_of(
        fields, variable)
    col_of = {}
    col = 0
    for name, _shape, _dtype, nbytes in fixed_spec:
        col_of[name] = col
        col += nbytes

    shutil.copyfile(key_path, out_path)
    header_size = int(key_rec.get("header_size", 0))
    raw_out = np.memmap(out_path, dtype=np.uint8, mode="r+")
    try:
        meta = checkpoint_mod.parse_metadata(raw_out, header_size)
        cells_full, offs_full = meta[4], meta[5].astype(np.int64)
        for dpath, drec in links[1:]:
            dnames = list(drec["delta"]["fields"])
            if not dnames:
                continue
            raw_d = np.memmap(dpath, dtype=np.uint8, mode="r")
            dmeta = checkpoint_mod.parse_metadata(
                raw_d, int(drec.get("header_size", 0)))
            dcells, doffs = dmeta[4], dmeta[5].astype(np.int64)
            if not np.array_equal(dcells, cells_full):
                raise DeltaChainError(
                    f"{filename}: delta {dpath} records a different "
                    "cell list than its keyframe (a structural change "
                    "without a keyframe — the chain is inconsistent)",
                    link=dpath, chain=[p for p, _r in links])
            try:
                dspec, _db, _dv = checkpoint_mod._payload_spec_of(
                    {n: fields[n] for n in dnames}, None)
            except KeyError as e:
                raise DeltaChainError(
                    f"{filename}: delta {dpath} stores field {e} not in "
                    "the caller's schema", link=dpath,
                    chain=[p for p, _r in links]) from e
            src_col = 0
            for name, _shape, _dtype, nbytes in dspec:
                dst = offs_full + col_of[name]
                src = doffs + src_col
                span = np.arange(nbytes, dtype=np.int64)[None, :]
                blk = max(1, (8 << 20) // max(nbytes, 1))
                for s in range(0, len(cells_full), blk):
                    e = min(s + blk, len(cells_full))
                    raw_out[dst[s:e, None] + span] = \
                        raw_d[src[s:e, None] + span]
                src_col += nbytes
            del raw_d
        raw_out.flush()
    finally:
        del raw_out
    return [p for p, _r in links]


@telemetry.traced("ckpt.save")
def save_checkpoint(grid, filename: str, header: bytes = b"",
                    variable=None, sidecar: bool = True, retries: int = 2,
                    backoff: float = 0.1, chunk_bytes: int = CRC_CHUNK,
                    *, fields=None, sidecar_extra=None) -> str:
    """Atomic checkpoint save: the pinned ``.dc`` bytes stream into a
    temp file in the target directory, fsync, then one rename — a crash
    at any point leaves either the old or the new checkpoint complete,
    never a torn file under the final name. Transient I/O errors retry
    with exponential backoff. With ``sidecar`` (default) the per-chunk
    CRC32 sidecar is written after the rename.

    ``fields`` restricts the save to a field subset and
    ``sidecar_extra`` merges extra keys (the delta parent link) into
    the sidecar record — the incremental-save plumbing; use
    :func:`save_delta_checkpoint` rather than passing them directly."""
    kind = ("delta" if sidecar_extra and "delta" in sidecar_extra
            else "keyframe")
    telemetry.inc("dccrg_saves_total", kind=kind)
    # measured save cost is a first-class controller input
    # (dccrg_ckpt_save_seconds{kind}): the autopilot prices checkpoint
    # cadence with it, and operators read the same histogram
    t_save = time.perf_counter()
    if grid._multiproc:
        # multi-process meshes take the TWO-PHASE-COMMIT save
        # (checkpoint._save_process_slice): every rank streams its
        # slice runs into <file>.mp-tmp, a timeout-guarded commit
        # barrier collects per-run CRC32s across ranks, and the
        # committing rank verifies every slice before the atomic
        # rename — with the sidecar (extended by the per-rank slice
        # table) written by that rank. No retry loop here: replaying
        # the save on ONE rank would desynchronize the ranks' barrier
        # sequence, so transient-I/O retry on this path belongs to the
        # caller (who can re-enter collectively on every rank).
        faults.fire("checkpoint.write", path=filename, attempt=0)
        checkpoint_mod.save_grid_data(
            grid, filename, header=header, variable=variable,
            sidecar=sidecar, sidecar_chunk_bytes=chunk_bytes,
            fields=fields, sidecar_extra=sidecar_extra)
        faults.corrupt_file(filename)
        telemetry.observe("dccrg_ckpt_save_seconds",
                          time.perf_counter() - t_save, kind=kind)
        return filename

    tmp = filename + f".tmp.{os.getpid()}"
    side = sidecar_path(filename)
    rec = None
    for attempt in range(retries + 1):
        try:
            checkpoint_mod.save_grid_data(grid, tmp, header=header,
                                          variable=variable, fields=fields)
            faults.fire("checkpoint.write", path=filename, attempt=attempt)
            with open(tmp, "rb+") as f:
                f.flush()
                os.fsync(f.fileno())
            if sidecar:
                # checksum the TEMP bytes so the record always matches
                # the file the rename publishes
                rec = _sidecar_record(tmp, header_size=len(header),
                                      chunk_bytes=chunk_bytes)
                if sidecar_extra:
                    rec.update(sidecar_extra)
                integ = _integrity_record(grid, fields, variable)
                if integ:
                    rec["integrity"] = integ
            # drop any previous sidecar BEFORE the rename: a crash in
            # this window leaves the new file with no sidecar — which
            # strict load refuses conservatively — never a new file
            # paired with a stale record (which would reject or
            # destructively 'salvage' an intact checkpoint). Keep the
            # old record's bytes: if the rename itself fails, the OLD
            # checkpoint is still the intact one under the final name
            # and must stay verifiable for rollback.
            old_side = None
            if os.path.exists(side):
                with open(side, "rb") as f:
                    old_side = f.read()
                os.unlink(side)
            try:
                os.replace(tmp, filename)
            except OSError:
                _restore_sidecar(side, old_side)
                raise
            _fsync_dir(os.path.dirname(os.path.abspath(filename)))
            break
        except OSError as e:
            if os.path.exists(tmp):
                os.unlink(tmp)
            if attempt >= retries:
                raise
            delay = backoff * (2 ** attempt)
            logger.warning(
                "checkpoint save of %s failed (%s); retry %d/%d in %.2fs",
                filename, e, attempt + 1, retries, delay)
            time.sleep(delay)
    if rec is not None:
        _write_sidecar_record(side, rec)
    # post-write corruption injection happens AFTER the sidecar records
    # the good bytes — exactly the at-rest corruption CRCs exist for
    faults.corrupt_file(filename)
    telemetry.observe("dccrg_ckpt_save_seconds",
                      time.perf_counter() - t_save, kind=kind)
    return filename


@telemetry.traced("ckpt.delta")
def save_delta_checkpoint(grid, filename: str, *, parent_path: str,
                          parent_step: int, step: int, fields,
                          header: bytes = b"", variable=None,
                          retries: int = 2, backoff: float = 0.1,
                          chunk_bytes: int = CRC_CHUNK) -> str:
    """Incremental checkpoint: save only ``fields`` (the dirty set
    since ``parent_path``) as a ``.dcd`` file — a valid ``.dc`` of the
    sub-schema, same atomic temp+fsync+rename (or two-phase
    multi-process commit) discipline as a full save — whose sidecar
    records the parent link ``{file, step, digest}``. The chain is only
    valid within one structure epoch and with fixed-size fields (the
    caller — :meth:`dccrg_tpu.supervise.CheckpointStore.save` — forces
    a keyframe otherwise). Restore via the chain-aware
    :func:`load_checkpoint` / ``resume_latest``; the reconstruction is
    bitwise identical to an uninterrupted full save."""
    extra = delta_sidecar_extra(parent_path, parent_step=parent_step,
                                step=step, fields=fields,
                                variable=variable)
    return save_checkpoint(grid, filename, header=header,
                           variable=variable, retries=retries,
                           backoff=backoff, chunk_bytes=chunk_bytes,
                           fields=extra["delta"]["fields"],
                           sidecar_extra=extra)


def delta_sidecar_extra(parent_path: str, *, parent_step: int, step: int,
                        fields, variable=None) -> dict:
    """The delta save's ``sidecar_extra`` record: the sorted dirty
    field list plus the parent link ``{file, step, digest}`` (digest
    derived from the parent's CURRENT sidecar, so a replaced parent is
    detected at load). Split out of :func:`save_delta_checkpoint` so
    the async-save path (``DCCRG_ASYNC_SAVE``) can resolve the link
    synchronously — while the drained parent is provably durable —
    before handing the write to the background thread. Raises
    :class:`CheckpointCorruptionError` when the parent has no sidecar
    (the caller falls back to a keyframe)."""
    fields = sorted(fields)
    var = variable or {}
    ragged = set(var) | set(var.values())
    if ragged & set(fields):
        raise ValueError(
            f"delta fields {sorted(ragged & set(fields))} are ragged "
            "(or ragged counts): their per-cell byte sizes move the "
            "offset table — only a full keyframe may capture that")
    parent_rec = read_sidecar(parent_path)
    if parent_rec is None:
        raise CheckpointCorruptionError(
            f"{parent_path}: delta parent has no sidecar; save a "
            "keyframe instead")
    digest = record_digest(parent_rec)
    if faults.take_delta_parent_corrupt():
        digest ^= 0x5A5A5A5A  # injected parent-link corruption
    return {"delta": {
        "fields": fields, "step": int(step),
        "parent": {"file": os.path.basename(parent_path),
                   "step": int(parent_step),
                   "digest": int(digest)}}}


def _integrity_record(grid, fields, variable) -> dict:
    """The sidecar ``integrity`` record: a payload fingerprint
    ``{field: [s1, s2, nbytes]}`` computed from the grid's LIVE
    device state (not the written bytes) via
    :func:`dccrg_tpu.integrity.grid_fingerprint`. Because the
    fingerprint is order-independent and exact, ``audit_checkpoint``
    can later re-derive it from the file's payload columns alone:
    bytes that rotted between device memory and the published file —
    or at rest afterwards, even under a plausible-looking CRC epoch —
    no longer match. Ragged (variable) fields are excluded (the file
    stores them truncated to their counts; the live rows differ).
    Empty when ``DCCRG_INTEGRITY=0`` or on multi-process grids (the
    two-phase commit path owns those sidecars)."""
    from . import integrity

    if not integrity.integrity_enabled():
        return {}
    var = variable or {}
    names = [n for n in sorted(fields if fields is not None
                               else grid.fields) if n not in var]
    if not names:
        return {}
    out = {}
    fp = integrity.grid_fingerprint(grid, names)
    for n in names:
        shape, dtype = grid.fields[n]
        nbytes = int(np.prod(shape, dtype=np.int64) or 1) * \
            np.dtype(dtype).itemsize
        out[n] = [int(fp[n][0]), int(fp[n][1]), nbytes]
    return out


def audit_checkpoint(filename: str) -> "dict | None":
    """Offline at-rest SDC audit: re-derive the payload fingerprint of
    ``filename`` from its bytes and compare against the ``integrity``
    record its sidecar captured from live device state at save time.
    Returns ``{field: (ok, got_pair, want_pair)}``, or None when the
    sidecar carries no integrity record (pre-SDC save, or
    ``DCCRG_INTEGRITY=0``). Complements the CRC chunk pass: CRCs
    verify the file matches what was WRITTEN; the fingerprint verifies
    what was written matches what the simulation actually HELD —
    corruption on the serialization path, or bit rot under a
    regenerated/intact-looking CRC epoch, fails here and only here.
    The ``python -m dccrg_tpu.resilience audit`` subcommand prints
    this."""
    from . import checkpoint as checkpoint_mod
    from . import integrity

    rec = read_sidecar(filename)
    if rec is None:
        raise CheckpointCorruptionError(
            f"{filename}: no checksum sidecar; nothing to audit "
            "against")
    integ = rec.get("integrity")
    if not integ:
        return None
    # synthesize a bytes-only schema: the column walk needs each
    # fixed field's serialized width and the sorted-name order, both
    # of which the record carries — the audit needs no grid schema
    fields = {n: ((int(v[2]),), np.uint8) for n, v in integ.items()}
    raw = np.memmap(filename, dtype=np.uint8, mode="r")
    try:
        meta = checkpoint_mod.parse_metadata(
            raw, int(rec.get("header_size", 0)))
        cols = checkpoint_mod.payload_columns(raw, meta, fields)
        out = {}
        for n, v in integ.items():
            got = integrity.fingerprint_rows(cols[n])
            want = (int(v[0]) & 0xFFFFFFFF, int(v[1]) & 0xFFFFFFFF)
            out[n] = (got == want, got, want)
        return out
    finally:
        del raw


def _restore_sidecar(side: str, old_side) -> None:
    """Put a displaced sidecar's bytes back after a failed rename —
    atomic (same tmp+fsync+rename discipline as _write_sidecar_record)
    and best effort: a torn restore must not shadow the original
    failure, and a missing sidecar is the conservative state. Shared by
    the single-controller save and the multi-process commit rank."""
    if old_side is None:
        return
    try:
        rtmp = side + f".tmp.{os.getpid()}"
        with open(rtmp, "wb") as f:
            f.write(old_side)
            f.flush()
            os.fsync(f.fileno())
        os.replace(rtmp, side)
    except OSError:  # pragma: no cover - double fault
        pass


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover - exotic filesystems
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover
        pass
    finally:
        os.close(fd)


@dataclass
class SalvageReport:
    """What a non-strict load had to work around. ``bad_slices`` /
    ``dead_ranks`` attribute the damage when the sidecar carries a
    two-phase multi-process slice table: which writer ranks' slices
    fail their CRC (the dead rank whose cells came back zeroed)."""

    bad_chunks: list = dataclass_field(default_factory=list)
    corrupt_cells: np.ndarray = dataclass_field(
        default_factory=lambda: np.empty(0, np.uint64))
    sidecar_missing: bool = False
    bad_slices: list = dataclass_field(default_factory=list)
    dead_ranks: list = dataclass_field(default_factory=list)
    # the keyframe+delta link paths a chain-aware load replayed
    # (keyframe first; empty for plain full checkpoints)
    chain: list = dataclass_field(default_factory=list)

    @property
    def clean(self) -> bool:
        return not self.bad_chunks and not self.sidecar_missing


@telemetry.traced("ckpt.load", counter="dccrg_loads_total")
def load_checkpoint_into(grid, filename: str, *, header_size: int = 0,
                         variable=None, verify: bool = True) -> None:
    """Load a checkpoint's exact bytes into an ALREADY-CONSTRUCTED
    grid of matching structure — the rollback/per-slot-restore
    primitive shared by :class:`ResilientRunner` and the fleet layer
    (:mod:`dccrg_tpu.fleet`, which restores ONE batch member into a
    scratch grid). CHAIN-AWARE: a delta checkpoint verifies and
    materializes its whole keyframe+delta chain into a scratch file
    first (a broken chain raises :class:`DeltaChainError`); a full
    checkpoint is CRC-verified against its sidecar (``verify=False``
    skips that for bytes the caller just wrote and verified). Ghost
    copies are refreshed afterwards, so static never-re-exchanged
    fields read exactly the checkpointed state."""
    if is_delta_checkpoint(filename):
        tmp = _chain_scratch(filename)
        try:
            materialize_chain(filename, tmp, grid.fields,
                              variable=variable, verify=verify)
            checkpoint_mod.load_grid_data(grid, tmp,
                                          header_size=header_size,
                                          variable=variable)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    else:
        if verify:
            bad = verify_checkpoint(filename)
            if bad:
                raise CheckpointCorruptionError(
                    f"rollback target {filename} is itself "
                    f"corrupt (chunks {bad})", bad_chunks=bad)
        checkpoint_mod.load_grid_data(grid, filename,
                                      header_size=header_size,
                                      variable=variable)
    # the load scatters LOCAL rows only; ghost copies of fields the
    # step loop treats as static (never re-exchanged) would stay
    # zero — refresh every field's ghosts so the resumed run sees
    # exactly the checkpointed state
    grid.update_copies_of_remote_neighbors()


@telemetry.traced("ckpt.load", counter="dccrg_loads_total")
def load_checkpoint(filename: str, cell_data, mesh=None,
                    header_size: int = 0, variable=None, strict: bool = True,
                    load_balancing_method=None):
    """Restart from a checkpoint with integrity verification.

    Returns ``(grid, header, report)``. With ``strict`` (default) any
    checksum mismatch — or a missing sidecar — raises
    :class:`CheckpointCorruptionError` naming the bad chunk. With
    ``strict=False`` intact chunks are salvaged: corrupt byte ranges
    are zeroed before the load, so affected cells come back with
    default (zero) values — variable-size fields read a zero count —
    and are listed in ``report.corrupt_cells``. Corruption inside the
    metadata block (mapping/geometry/offset table) is never salvageable
    and raises in both modes.

    An incremental (delta) checkpoint loads CHAIN-AWARE: the whole
    keyframe+delta chain is verified, materialized into a scratch file
    (``<file>.chain.<pid>`` next to it, or in the system temp dir
    when the checkpoint directory is read-only; removed afterwards)
    and loaded — bitwise
    identical to the full save an uninterrupted run would have
    written. A broken chain raises :class:`DeltaChainError` naming the
    broken link in BOTH modes (zero-salvage cannot repair a missing
    ancestor); the fallback to the last verifying prefix is
    ``resume_latest``'s job, which walks to older entries."""
    rec = read_sidecar(filename)
    if is_delta_checkpoint(filename, rec):
        if rec is None:
            raise DeltaChainError(
                f"{filename}: a delta checkpoint without its sidecar "
                "cannot be interpreted (the dirty-field list and parent "
                "link live there); resume from an older link instead",
                link=filename)
        tmp = _chain_scratch(filename)
        try:
            chain = materialize_chain(filename, tmp, cell_data,
                                      variable=variable)
            grid, header = checkpoint_mod.load_grid(
                tmp, cell_data, mesh=mesh, header_size=header_size,
                variable=variable,
                load_balancing_method=load_balancing_method)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return grid, header, SalvageReport(chain=chain)
    if rec is None:
        if strict:
            raise CheckpointCorruptionError(
                f"{filename}: no checksum sidecar; load with strict=False "
                "to proceed unverified")
        logger.warning("%s: loading without checksum verification "
                       "(sidecar missing)", filename)
        grid, header = checkpoint_mod.load_grid(
            filename, cell_data, mesh=mesh, header_size=header_size,
            variable=variable, load_balancing_method=load_balancing_method)
        return grid, header, SalvageReport(sidecar_missing=True)

    bad = _bad_chunks(filename, rec)
    if not bad:
        # chunk CRCs tile every recorded byte, so clean chunks imply
        # clean per-rank slices — no second verification pass needed
        grid, header = checkpoint_mod.load_grid(
            filename, cell_data, mesh=mesh, header_size=header_size,
            variable=variable, load_balancing_method=load_balancing_method)
        return grid, header, SalvageReport()

    # attribution: which ranks' two-phase slices cover the damage
    bad_sl = _bad_slices(filename, rec)
    dead = sorted({int(rec["slices"][i][1]) for i in bad_sl})
    all_ranges = _rec_ranges(rec)
    names = ", ".join(_chunk_name(i, all_ranges) for i in bad)
    if dead:
        names += (f"; slice(s) written by rank(s) {dead} fail their "
                  "CRC32")
    if strict:
        raise CheckpointCorruptionError(
            f"{filename}: checksum mismatch in {names}", bad_chunks=bad)

    # -- salvage: zero the corrupt ranges, load, report the cells -----
    if 0 in bad:
        raise CheckpointCorruptionError(
            f"{filename}: corruption in the {names}; the metadata block "
            "(mapping/geometry/offset table) cannot be trusted — not "
            "salvageable", bad_chunks=bad)
    file_bytes = int(rec["file_bytes"])
    with open(filename, "rb") as f:
        raw = bytearray(f.read())
    # a truncated file is padded back to the recorded size with zeros
    # (the missing tail is inside a corrupt range anyway)
    if len(raw) < file_bytes:
        raw += bytes(file_bytes - len(raw))
    del raw[file_bytes:]

    # the trailing-garbage sentinel has no in-range bytes to zero —
    # `del raw[file_bytes:]` below already trims it
    ranges = [all_ranges[i] for i in bad if i < len(all_ranges)]
    try:
        meta = checkpoint_mod.parse_metadata(bytes(raw), header_size)
    except Exception as e:  # metadata CRC passed but parse still failed
        raise CheckpointCorruptionError(
            f"{filename}: metadata unreadable ({e}); corruption in {names} "
            "is not salvageable", bad_chunks=bad) from e
    cells, offsets = meta[4], meta[5]

    for lo, hi in ranges:
        raw[lo:hi] = bytes(hi - lo)

    # per-cell payload extents from the (intact) offset table
    offs = offsets.astype(np.int64)
    ends = np.empty_like(offs)
    ends[:-1] = offs[1:]
    if len(ends):
        ends[-1] = file_bytes
    hit = np.zeros(len(cells), dtype=bool)
    for lo, hi in ranges:
        hit |= (offs < hi) & (ends > lo)
    corrupt_cells = cells[hit].copy()

    tmp = filename + f".salvage.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(bytes(raw))
        grid, header = checkpoint_mod.load_grid(
            tmp, cell_data, mesh=mesh, header_size=header_size,
            variable=variable, load_balancing_method=load_balancing_method)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    logger.warning(
        "%s: salvaged around %s — %d cell(s) restored with default "
        "values: %s", filename, names, len(corrupt_cells),
        corrupt_cells[:16].tolist())
    return grid, header, SalvageReport(bad_chunks=bad,
                                       corrupt_cells=corrupt_cells,
                                       bad_slices=bad_sl,
                                       dead_ranks=dead)


# ---------------------------------------------------------------------
# numerics watchdog
# ---------------------------------------------------------------------

def _inexact_fields(grid, fields=None):
    import jax.numpy as jnp

    names = list(fields) if fields is not None else list(grid.fields)
    return [n for n in names
            if jnp.issubdtype(grid.fields[n][1], jnp.inexact)]


def check_finite(grid, fields=None) -> bool:
    """Device-side watchdog probe: every element of the watched fields
    isfinite, reduced to ONE scalar crossing to the host (per-device
    ``all`` then a psum-style min over the mesh via comm.py). Cheap
    enough to run every few steps; locate the offenders with
    :func:`assert_finite` / verify.find_nonfinite_cells only on a
    trip."""
    import jax
    from jax.sharding import PartitionSpec as P

    from . import comm
    from jax import shard_map

    names = _inexact_fields(grid, fields)
    if not names:
        return True
    key = ("finite", tuple(names),
           tuple(tuple(grid.fields[n][0]) for n in names))
    fn = grid._program_cache.get(key)
    if fn is None:
        axis, mesh = grid.axis, grid.mesh

        def body(*arrs):
            return comm.all_finite([a[0] for a in arrs], axis)[None]

        mapped = shard_map(
            body, mesh=mesh, in_specs=(P(axis),) * len(names),
            out_specs=P(axis), check_vma=False)
        fn = jax.jit(mapped)
        grid._program_cache[key] = fn
    out = fn(*(grid.data[n] for n in names))
    # the min all-reduce leaves identical rows on every device; pull
    # through comm so real multi-process meshes (where row 0 may not
    # be addressable) read their local shard instead
    return bool(int(comm.pull_replicated(out).ravel()[0]))


def assert_finite(grid, fields=None, step=None) -> None:
    """Raise :class:`NumericsError` (naming fields and cell ids, found
    host-side via verify.py) when the watchdog probe trips."""
    if check_finite(grid, fields):
        return
    from . import verify

    details = verify.find_nonfinite_cells(grid, fields)
    where = "" if step is None else f" at step {step}"
    names = {n: ids[:8].tolist() for n, ids in details.items()}
    raise NumericsError(
        f"non-finite values{where} in {names or 'ghost/pad rows only'}",
        details=details)


# ---------------------------------------------------------------------
# OOM-aware step dispatch: the gather-mode fallback chain
# ---------------------------------------------------------------------

_GATHER_ENV = ("DCCRG_ROLL_STENCIL", "DCCRG_FORCE_TABLES", "DCCRG_BULK")
FALLBACK_CHAIN = ("current", "roll", "tables")


def _is_resource_exhausted(e: BaseException) -> bool:
    return ("RESOURCE_EXHAUSTED" in str(e)
            or isinstance(e, faults.SimulatedResourceExhausted))


# the env each forced gather mode pins (None = unset). DCCRG_FORCE_TABLES
# is read at PLAN BUILD time (uniform.py), DCCRG_ROLL_STENCIL at program
# build — forcing a mode therefore needs a plan rebuild. Both fallback
# modes also drop out of the DCCRG_BULK=pallas executor: an OOM under
# the bulk program (its VMEM windows + epilogue tables cost more than
# the bare roll path) degrades to plain XLA gathers before dense
# tables are tried.
_MODE_ENV = {
    "roll": {"DCCRG_FORCE_TABLES": None, "DCCRG_ROLL_STENCIL": "1",
             "DCCRG_BULK": None},
    "tables": {"DCCRG_FORCE_TABLES": "1", "DCCRG_ROLL_STENCIL": "0",
               "DCCRG_BULK": None},
}


def _apply_mode(grid, mode: str) -> None:
    """Pin the gather env for ``mode`` and rebuild the plan if it was
    last built under a different forced mode. Cells/owners (and the
    sticky capacity memo) are unchanged by the rebuild, so the row
    layout — and with it every field array — stays valid."""
    if mode == "current":
        return
    for v, val in _MODE_ENV[mode].items():
        if val is None:
            os.environ.pop(v, None)
        else:
            os.environ[v] = val
    # _build_plan clears the marker, so any external rebuild (AMR
    # commit, load balance) correctly invalidates it
    if getattr(grid, "_plan_gather_mode", None) != mode:
        grid._build_plan(grid.plan.cells, grid.plan.owner)
        grid._plan_gather_mode = mode


@contextmanager
def _restore_env():
    saved = {v: os.environ.get(v) for v in _GATHER_ENV}
    try:
        yield saved
    finally:
        for v, val in saved.items():
            if val is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = val


def guarded_step(grid, kernel, fields_in, fields_out, n_steps=1, *,
                 exchange_fields=None, neighborhood_id=None,
                 extra_args=()) -> str:
    """Dispatch ``Grid.run_steps`` with graceful OOM degradation.

    On XLA ``RESOURCE_EXHAUSTED`` (real, or injected through
    faults.resource_exhausted) the dispatch walks the fallback chain
    *current mode -> slot-wise roll -> dense tables*, logging each
    downgrade, and returns the mode that completed. Fallback entries
    whose forced env equals the caller's current env are skipped
    (retrying the identical configuration would just re-OOM), and a
    successful downgrade is remembered on the grid: later guarded
    dispatches start from the working mode even after a structural
    rebuild reverted the plan. When every mode exhausts HBM,
    :class:`ResilienceExhaustedError` surfaces with the last error
    chained. The caller's env vars are restored either way."""
    from .grid import DEFAULT_NEIGHBORHOOD_ID

    hood = (DEFAULT_NEIGHBORHOOD_ID if neighborhood_id is None
            else neighborhood_id)
    failed = []
    with _restore_env() as saved:
        sticky = getattr(grid, "_sticky_gather_mode", None)
        if sticky is not None:
            chain = [m for m in FALLBACK_CHAIN[1:]
                     if FALLBACK_CHAIN.index(m) >= FALLBACK_CHAIN.index(sticky)]
        else:
            chain = ["current"] + [m for m in FALLBACK_CHAIN[1:]
                                   if _MODE_ENV[m] != saved]
        for mode in chain:
            try:
                _apply_mode(grid, mode)
                faults.fire("step.dispatch", mode=mode)
                grid.run_steps(kernel, fields_in, fields_out, n_steps,
                               exchange_fields=exchange_fields,
                               neighborhood_id=hood, extra_args=extra_args)
                if mode != "current":
                    grid._sticky_gather_mode = mode
                if failed:
                    logger.warning(
                        "step completed in fallback gather mode %r "
                        "(exhausted: %s); the downgrade sticks for "
                        "later guarded dispatches", mode,
                        [m for m, _ in failed])
                return mode
            except Exception as e:  # noqa: BLE001 - filtered just below
                if not _is_resource_exhausted(e):
                    raise
                logger.warning(
                    "RESOURCE_EXHAUSTED dispatching step in gather mode "
                    "%r; falling back (%s)", mode, e)
                failed.append((mode, e))
    raise ResilienceExhaustedError(
        f"every gather mode in {[m for m, _ in failed]} exhausted device "
        "memory") from failed[-1][1]


# ---------------------------------------------------------------------
# the resilient step loop: watchdog + checkpoint + rollback
# ---------------------------------------------------------------------

# trip codes the per-step consensus all-reduces (max wins), ordered by
# priority: _TRIP_INTERRUPT is a consensus-agreed step-boundary
# interrupt (a preemption signal observed by dccrg_tpu.supervise) that
# any REAL trip outranks — a rank that tripped rolls everyone back
# first and the still-set preempt flag is re-polled at the next
# boundary; _TRIP_ROLLBACK.._TRIP_OOM are recoverable (mutation /
# numerics / silent corruption / OOM -> every rank rolls back
# together; _TRIP_CORRUPT is an integrity-invariant verdict, see
# dccrg_tpu.integrity — finite wrong bits the numerics code cannot
# see); >= _TRIP_FATAL means a rank hit a non-recoverable error and
# every OTHER rank raises in sync instead of hanging in the dead
# rank's abandoned collectives
_TRIP_INTERRUPT = 1
_TRIP_ROLLBACK = 2   # MutationAbortedError
_TRIP_NUMERICS = 3
_TRIP_CORRUPT = 4    # integrity invariant (SDC) verdict
_TRIP_OOM = 5
_TRIP_FATAL = 6


def watchdog_interval(default: int = 0) -> int:
    """The DCCRG_WATCHDOG env knob: check every ~N steps (0 = off)."""
    try:
        return int(os.environ.get("DCCRG_WATCHDOG", "") or default)
    except ValueError:
        return default


class ResilientRunner:
    """Run a step loop that survives numerical blow-ups.

    ``step_fn(grid, step_index)`` advances the simulation by one step
    (typically a ``run_steps``/:func:`guarded_step` call). Every
    ``checkpoint_every`` steps the state is checkpointed atomically
    (CRC sidecar included); every ``check_every`` steps the watchdog
    probes for non-finite values. On a trip the runner

    1. dumps a diagnostic bundle (step, offending fields, cell ids)
       into ``diagnostics_dir``,
    2. rolls the grid back to the last *verified* checkpoint,
    3. backs off exponentially and resumes.

    Retries are bounded: ``max_retries`` consecutive trips without
    passing the previous trip point raise
    :class:`ResilienceExhaustedError`. Because the checkpoint holds
    exact field bytes and the step programs are deterministic, a
    recovered run reconverges to the bitwise-identical state of an
    undisturbed one (pinned by tests/test_resilience.py).
    """

    def __init__(self, grid, step_fn, checkpoint_path, *, fields=None,
                 check_every=None, checkpoint_every=10,
                 checkpoint_seconds=0.0, max_retries=3,
                 backoff=0.05, header=b"", variable=None,
                 diagnostics_dir=None, interrupt_poll=None,
                 conserved_fields=None):
        self.grid = grid
        self.step_fn = step_fn
        # SDC defense (dccrg_tpu.integrity): fields whose global sum
        # the caller's step kernel provably conserves. At every
        # watchdog boundary the runner recomputes the device-side
        # collective sums and compares them against the values
        # recorded at the last checkpoint; a drift beyond
        # integrity.sum_tolerance — finite, plausible bits the
        # numerics watchdog cannot see — is a _TRIP_CORRUPT verdict
        # put through coord.trip_consensus so EVERY rank rolls back
        # together. Off (None/empty, or DCCRG_INTEGRITY=0): zero
        # overhead, no extra program.
        self.conserved_fields = tuple(conserved_fields or ())
        self._integrity_base = None  # sums at the rollback target
        # optional step-boundary interrupt hook (the supervision
        # layer's preemption poll): truthy -> the _TRIP_INTERRUPT code
        # joins this step's trip consensus, and when it wins on every
        # rank the loop raises RunInterrupted instead of stepping on
        self.interrupt_poll = interrupt_poll
        self.checkpoint_path = checkpoint_path
        self.fields = fields
        self.check_every = (check_every if check_every is not None
                            else (watchdog_interval(0) or 1))
        self.checkpoint_every = checkpoint_every
        # wall-clock cadence (monotonic clock, evaluated only at step
        # boundaries — a save can never land mid-step): a checkpoint
        # becomes due once this many seconds passed since the last
        # one, whatever the step count. 0 disables; step-count cadence
        # may be disabled independently with checkpoint_every=0. On
        # multi-process meshes the per-rank clocks drift, so due-ness
        # goes through an any-rank consensus before acting — every
        # rank enters the collective save together.
        self.checkpoint_seconds = float(checkpoint_seconds or 0.0)
        self._last_save_t = None
        self.max_retries = max_retries
        self.backoff = backoff
        self.header = header
        self.variable = variable
        self.diagnostics_dir = (diagnostics_dir
                                or os.path.dirname(os.path.abspath(
                                    checkpoint_path)))
        self.step = 0
        self.trips = []  # diagnostic bundles, newest last
        self.rollbacks = 0
        self.checkpoints = 0
        self._ckpt_step = None
        self._retry_streak = 0
        self._streak_step = -1

    # -- checkpoint plumbing ------------------------------------------

    def _write_checkpoint(self) -> str:
        """Write the periodic checkpoint; returns the path written.
        The supervision layer's store-backed runner overrides this to
        route through :meth:`dccrg_tpu.supervise.CheckpointStore.save`
        (numbered files, dirty-field delta saves).

        With ``DCCRG_ASYNC_SAVE=1`` the write runs on a background
        thread against a :func:`dccrg_tpu.background.freeze_grid`
        snapshot (multi-process meshes through
        :func:`dccrg_tpu.background.freeze_grid_mp`, whose two-phase
        barriers rendezvous on the ranks' writer threads), overlapped
        with the following steps' dispatch — bitwise identical bytes,
        published atomically; :meth:`_drain_saves` is the barrier every
        store reader (rollback, run end) takes first."""
        if background.async_save_enabled():
            saver = self._active_saver(create=True)
            saver.drain()  # one in flight; an earlier failure raises here
            frozen = (background.freeze_grid_mp(self.grid,
                                                variable=self.variable)
                      if self.grid._multiproc
                      else background.freeze_grid(self.grid))
            path = self.checkpoint_path
            saver.submit(
                lambda: save_checkpoint(frozen, path, header=self.header,
                                        variable=self.variable),
                label=path)
            return path
        save_checkpoint(self.grid, self.checkpoint_path,
                        header=self.header, variable=self.variable)
        return self.checkpoint_path

    def _active_saver(self, create: bool = False):
        """The :class:`~dccrg_tpu.background.AsyncSaver` carrying this
        runner's in-flight periodic write, or None. The store-backed
        runner overrides this with its store's saver."""
        if create and getattr(self, "_saver", None) is None:
            self._saver = background.AsyncSaver()
        return getattr(self, "_saver", None)

    def _drain_saves(self, swallow: bool = False) -> None:
        """Async-save barrier: block until no periodic write is in
        flight. ``swallow=True`` (the rollback/emergency paths, where
        resumability outranks the report) logs a writer failure
        instead of raising — its ``on_fail`` hooks have already
        re-pointed the rollback target at the last durable save."""
        saver = self._active_saver()
        if saver is None:
            return
        try:
            saver.drain()
        except Exception as e:  # noqa: BLE001 - policy filter below
            if not swallow:
                raise
            logger.error("async checkpoint write failed (%s); the last "
                         "durable checkpoint is the rollback target", e)

    def _save(self) -> None:
        prev = (self.checkpoint_path, self._ckpt_step, self._last_save_t,
                self._integrity_base)
        self.checkpoint_path = self._write_checkpoint()
        self._ckpt_step = self.step
        self._last_save_t = time.monotonic()
        self.checkpoints += 1
        if self._integrity_on():
            # the conservation baseline the boundary drift check
            # compares against — recorded at the rollback target, so
            # a corrupt verdict always rolls back to state whose
            # invariants were verified clean
            self._integrity_base = self._conservation_sums()
        saver = self._active_saver()
        if saver is not None and saver.pending():
            # the bookkeeping above is speculative while the write is
            # in flight: a writer failure reverts the rollback target
            # to the last DURABLE checkpoint at the drain barrier
            def _restore(_err, prev=prev):
                (self.checkpoint_path, self._ckpt_step,
                 self._last_save_t, self._integrity_base) = prev
                self.checkpoints -= 1

            saver.add_on_fail(_restore)

    def _integrity_on(self) -> bool:
        from . import integrity

        return bool(self.conserved_fields) and integrity.integrity_enabled()

    def _conservation_sums(self):
        from . import integrity

        return integrity.conservation_sums(self.grid,
                                           self.conserved_fields)

    def _integrity_drift(self):
        """The boundary SDC check: None when clean, else a details
        dict naming each conserved field whose device-side global sum
        drifted beyond tolerance since the last checkpoint. The sums
        are a replicated collective (comm.field_sums), so every rank
        computes the identical verdict."""
        from . import integrity

        if not self._integrity_on() or self._integrity_base is None:
            return None
        telemetry.inc("dccrg_integrity_checks_total", where="runner")
        with telemetry.span("integrity.check"):
            now = self._conservation_sums()
        steps = max(1, self.step - (self._ckpt_step or 0))
        details = {}
        for i, name in enumerate(self.conserved_fields):
            shape, _dt = self.grid.fields[name]
            n_el = len(self.grid.plan.cells) * int(
                np.prod(shape, dtype=int) or 1)
            tol = integrity.sum_tolerance(self._integrity_base[i],
                                          n_el, steps)
            drift = abs(float(now[i]) - float(self._integrity_base[i]))
            if drift > tol:
                details[name] = np.empty(0, np.uint64)
                logger.warning(
                    "integrity drift in %r: conservation sum moved "
                    "%g (tolerance %g) since the step-%s checkpoint "
                    "— silent corruption", name, drift, tol,
                    self._ckpt_step)
        return details or None

    def _rollback(self) -> None:
        # chain-aware when the target is a delta: the shared primitive
        # verifies + materializes the keyframe+delta chain (a broken
        # chain surfaces as DeltaChainError — a corrupt rollback
        # target either way)
        t0 = time.perf_counter()
        # drain barrier: never read a store an async write is still
        # publishing into (a failed write re-points checkpoint_path at
        # the last durable save before the load below)
        self._drain_saves(swallow=True)
        with telemetry.span("runner.rollback"):
            load_checkpoint_into(self.grid, self.checkpoint_path,
                                 header_size=len(self.header),
                                 variable=self.variable)
        self.step = self._ckpt_step
        self.rollbacks += 1
        telemetry.inc("dccrg_rollbacks_total")
        # rollback cost is a controller input (with the trip rate it
        # prices the replay window a checkpoint cadence implies)
        telemetry.observe("dccrg_rollback_seconds",
                          time.perf_counter() - t0)

    # -- trip handling ------------------------------------------------

    def _dump_diagnostics(self, details) -> dict:
        bundle = {
            "step": self.step,
            "rollback_to": self._ckpt_step,
            "retry": self._retry_streak,
            "fields": {n: ids[:64].tolist() for n, ids in details.items()},
            "checkpoint": self.checkpoint_path,
            "wall_time": time.time(),
        }
        path = os.path.join(
            self.diagnostics_dir,
            f"dccrg_diag_step{self.step}_try{self._retry_streak}.json")
        try:
            with open(path, "w") as f:
                json.dump(bundle, f, indent=1)
            bundle["path"] = path
        except OSError as e:  # diagnostics must never kill recovery
            logger.warning("could not write diagnostic bundle: %s", e)
        self.trips.append(bundle)
        return bundle

    def _trip(self, details=None, kind="numerics") -> None:
        from . import verify

        if details is None:
            details = verify.find_nonfinite_cells(self.grid, self.fields)
        if self.step > self._streak_step:
            self._retry_streak = 0  # progress since the last trip
        self._streak_step = self.step
        self._retry_streak += 1
        telemetry.inc("dccrg_trips_total", kind=kind)
        bundle = self._dump_diagnostics(details)
        logger.warning(
            "watchdog trip (%s) at step %d (fields %s); rolling back "
            "to step %s (retry %d/%d)", kind, self.step,
            list(details) or "<ghost rows>", self._ckpt_step,
            self._retry_streak, self.max_retries)
        if self._retry_streak > self.max_retries:
            msg = (f"watchdog tripped {self._retry_streak} times at "
                   f"step {self.step} without progress; diagnostics: "
                   f"{bundle.get('path', '<unwritten>')}")
            if kind == "corrupt":
                # persistent SDC: the typed subclass names the class
                # of failure (likely a defective device, not a
                # transient upset) while generic handlers catching
                # ResilienceExhaustedError keep working
                from . import integrity

                raise integrity.IntegrityError(
                    "integrity invariants failed on every retry — "
                    "persistent silent corruption; " + msg,
                    details={n: "invariant drift" for n in details})
            raise ResilienceExhaustedError(msg)
        if self.backoff:
            time.sleep(self.backoff * (2 ** (self._retry_streak - 1)))
        self._rollback()

    # -- the loop -----------------------------------------------------

    def run(self, n_steps: int) -> "ResilientRunner":
        """Advance to ``n_steps`` total steps, recovering as needed.
        Returns self (``.step``, ``.trips``, ``.rollbacks``,
        ``.checkpoints`` carry the story).

        On multi-process meshes every trip decision is put through
        :func:`dccrg_tpu.coord.trip_consensus` (a max all-reduce of a
        per-rank trip code) BEFORE acting on it: a
        ``MutationAbortedError``, an OOM, or a watchdog-hook
        ``NumericsError`` raised host-side on ONE rank makes EVERY
        rank roll back to the same checkpoint together, instead of the
        tripped rank abandoning a barrier its peers then hang in. The
        device-side ``check_finite`` probe is a global collective and
        agrees by construction."""
        from . import coord
        from .txn import MutationAbortedError

        if self._ckpt_step is None:
            self._save()  # rollback target always exists
        membership = coord.get_membership()
        while self.step < n_steps:
            if membership is not None:
                # elastic-fleet liveness: renew this rank's heartbeat
                # lease at step boundaries (throttled to the heartbeat
                # cadence), so peers classify a healthy-but-busy rank
                # live instead of reclaiming its work — and a rank
                # that stops beating surfaces to THEM as a typed
                # PeerDeadError naming it, not a barrier-tag timeout
                membership.heartbeat()
            code, details = 0, None
            try:
                self.step_fn(self.grid, self.step)
            except MutationAbortedError as e:
                # a structural mutation inside the step (adapt /
                # balance) failed and already rolled itself back;
                # recover like a watchdog trip: diagnostics, rollback
                # to the last checkpoint, bounded retry
                logger.warning("step %d: %s", self.step, e)
                code, details = _TRIP_ROLLBACK, {"mutation": np.asarray(
                    e.cells, dtype=np.uint64)}
            except NumericsError as e:
                # the DCCRG_WATCHDOG hook inside run_steps tripped
                # mid-step: same recovery as the runner's own check
                # (it already names the offending fields and cells)
                logger.warning("step %d: %s", self.step, e)
                code, details = _TRIP_NUMERICS, (e.details if e.details
                                                 else None)
            except Exception as e:  # noqa: BLE001 - filtered just below
                if not _is_resource_exhausted(e):
                    # non-recoverable: tell the peers before dying —
                    # they are (or soon will be) blocked in this
                    # step's consensus reduce, which unlike
                    # coord.barrier has no timeout of its own; a
                    # FATAL code makes every rank raise in sync
                    # instead of N-1 ranks hanging in a collective.
                    # Deadline-bounded: the mesh may be the very thing
                    # that broke (a wedged collective is what
                    # StepTimeoutError reports), and telling the peers
                    # must never keep the dying rank alive.
                    coord.broadcast_fatal(self.grid, _TRIP_FATAL)
                    raise
                # a device OOM that escaped the step (no guarded_step
                # in the loop, or an injected one): recover like a
                # trip — rollback frees the live buffers and the
                # bounded retry surfaces a persistent OOM as
                # ResilienceExhaustedError
                logger.warning("step %d: %s", self.step, e)
                code, details = _TRIP_OOM, {"resource_exhausted":
                                            np.empty(0, np.uint64)}
            if (code == 0 and self.interrupt_poll is not None
                    and self.interrupt_poll()):
                # the step completed cleanly but an interrupt (a
                # preemption signal) is pending on this rank; offer
                # the LOWEST-priority code so a real trip elsewhere
                # still wins (the flag stays set — the next boundary
                # re-polls it after the collective rollback)
                code = _TRIP_INTERRUPT
            agreed = coord.trip_consensus(self.grid, code)
            if agreed >= _TRIP_FATAL:
                raise ResilienceExhaustedError(
                    f"a peer rank failed fatally at step {self.step} "
                    "(non-recoverable exception on another rank; see "
                    "its log) — stopping in sync instead of hanging "
                    "in its abandoned collectives")
            if agreed >= _TRIP_ROLLBACK:
                if code in (0, _TRIP_INTERRUPT):
                    # another rank tripped; this one rolls back with it
                    details = {"remote_rank_trip": np.empty(0, np.uint64)}
                self._trip(details=details)
                continue
            if agreed == _TRIP_INTERRUPT:
                # every rank completed this step cleanly and agreed to
                # stop: the grid holds step+1 completed steps on all of
                # them — exactly the state the supervision layer's
                # emergency checkpoint captures
                self.step += 1
                if not check_finite(self.grid, self.fields):
                    # the rollback-target invariant holds for the
                    # emergency checkpoint too: NEVER hand poisoned
                    # state to a save (CRCs cannot see NaNs). Recover
                    # first — check_finite is a global collective, so
                    # every rank takes this branch together — and the
                    # still-pending interrupt stops the run at the
                    # first clean boundary after the rollback.
                    self._trip()
                    continue
                raise RunInterrupted(self.step)
            self.step += 1
            faults.poison_step(self.grid, self.step)
            faults.flip_step(self.grid, self.step)
            ckpt_due = (bool(self.checkpoint_every)
                        and self.step % self.checkpoint_every == 0)
            if not ckpt_due and self.checkpoint_seconds > 0:
                due = (self._last_save_t is not None
                       and time.monotonic() - self._last_save_t
                       >= self.checkpoint_seconds)
                # clocks drift across ranks: agree (any rank due ->
                # all save) before entering the collective save path
                ckpt_due = bool(coord.trip_consensus(self.grid, int(due)))
            # a checkpoint step ALWAYS checks first — the rollback
            # target must never capture unverified (poisoned OR
            # silently corrupted) state, whatever the check/checkpoint
            # cadence ratio
            if (ckpt_due or self.step % self.check_every == 0
                    or self.step == n_steps):
                if not check_finite(self.grid, self.fields):
                    self._trip()
                    continue
                # SDC boundary check (conserved_fields opt-in): the
                # drift verdict is computed from a replicated
                # collective, but the trip still goes through the
                # consensus all-reduce — any rank's CORRUPT verdict
                # (however asymmetric a future detector might be)
                # rolls every rank back together, and the mp harness
                # pins that all ranks agree on the verdict
                drift = self._integrity_drift()
                if self._integrity_on() and int(coord.trip_consensus(
                        self.grid,
                        _TRIP_CORRUPT if drift else 0)) >= _TRIP_CORRUPT:
                    self._trip(details=drift or {
                        "remote_rank_corrupt": np.empty(0, np.uint64)},
                        kind="corrupt")
                    continue
            if ckpt_due:
                self._save()
        # a write still in flight when the loop finishes must be
        # durable before the caller reads the store (resume, digest
        # comparisons); a failure surfaces here like a sync save's
        self._drain_saves()
        return self


# ---------------------------------------------------------------------
# device probing that cannot hang
# ---------------------------------------------------------------------

def safe_devices(timeout: float = 90.0, retries: int = 2,
                 backoff: float = 2.0, platform=None):
    """``jax.devices()`` that cannot hang the caller: the backend is
    probed first in a SUBPROCESS (killed hard on timeout) with bounded
    retries and exponential backoff; only a successful probe lets the
    in-process call proceed. Raises :class:`DeviceProbeError` when the
    budget is spent. ``platform`` routes both the probe and the
    in-process jax through ``jax.config.update('jax_platforms', ...)``
    (env vars are too late once jax is imported). For CPU-only host
    benches: on a chip the probing child would hold the device."""
    code = "import jax; "
    if platform:
        code += f"jax.config.update('jax_platforms', {platform!r}); "
    code += "print(len(jax.devices()))"
    last = "no probe attempted"
    for attempt in range(retries + 1):
        try:
            faults.fire("device.probe", attempt=attempt)
            out = subprocess.run(
                [sys.executable, "-c", code], timeout=timeout,
                capture_output=True, text=True)
            if out.returncode == 0:
                import jax

                if platform:
                    jax.config.update("jax_platforms", platform)
                return jax.devices()
            last = (out.stderr or out.stdout).strip()[-200:]
        except (subprocess.TimeoutExpired, faults.InjectedProbeHang) as e:
            last = f"probe timed out after {timeout}s ({type(e).__name__})"
        if attempt < retries:
            delay = backoff * (2 ** attempt)
            logger.warning("device probe failed (%s); retry %d/%d in %.1fs",
                           last, attempt + 1, retries, delay)
            time.sleep(delay)
    raise DeviceProbeError(
        f"device backend unreachable after {retries + 1} probe(s): {last}")


_PROBED_DEVICES: dict = {}


def probed_devices(timeout: float = 120.0, retries: int = 1,
                   backoff: float = 2.0, platform=None) -> list:
    """Memoized :func:`safe_devices`: ONE subprocess probe per process
    AND requested platform, however many benches ask (a probe costs a
    subprocess spawn nobody wants per construction). The cache is
    keyed by ``platform`` — it
    changes what the result MEANS, unlike the budget parameters,
    where the first caller's values win."""
    if platform not in _PROBED_DEVICES:
        _PROBED_DEVICES[platform] = list(safe_devices(
            timeout=timeout, retries=retries, backoff=backoff,
            platform=platform))
    return _PROBED_DEVICES[platform]


def _tool_main(argv) -> int:
    """Checkpoint maintenance subcommands, callable without a live
    accelerator: ``verify <file>`` re-checksums one checkpoint against
    its sidecar; ``gc <dir> --keep-last K --keep-every N`` applies the
    supervision layer's retention policy (DRY-RUN by default —
    ``--apply`` actually prunes; the GC can never delete the only
    checkpoint that passes verification)."""
    import argparse

    ap = argparse.ArgumentParser(prog="python -m dccrg_tpu.resilience",
                                 description=_tool_main.__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    v = sub.add_parser("verify", help="verify a checkpoint's CRC "
                                      "sidecar (a delta checkpoint "
                                      "verifies its WHOLE chain)")
    v.add_argument("file")
    c = sub.add_parser("chain", help="print every keyframe->delta "
                                     "chain in a checkpoint directory "
                                     "with per-link verification "
                                     "status")
    c.add_argument("dir")
    c.add_argument("--stem", default=None,
                   help="only checkpoints named <stem>_<step>.dc[d]")
    a = sub.add_parser("audit", help="at-rest SDC audit: recompute a "
                                     "checkpoint's payload integrity "
                                     "fingerprint and compare against "
                                     "the record its sidecar captured "
                                     "from live device state at save "
                                     "time (catches corruption the "
                                     "CRC pass cannot: serialization-"
                                     "path damage, rot under an "
                                     "intact-looking CRC epoch)")
    a.add_argument("file")
    g = sub.add_parser("gc", help="prune a checkpoint directory by the "
                                  "keep-last-K / keep-every-N retention "
                                  "policy — chain-aware: whole chains "
                                  "only, never orphans a delta "
                                  "(dry-run unless --apply)")
    g.add_argument("dir")
    g.add_argument("--keep-last", type=int, default=3)
    g.add_argument("--keep-every", type=int, default=0)
    g.add_argument("--stem", default=None,
                   help="only checkpoints named <stem>_<step>.dc[d]")
    g.add_argument("--apply", action="store_true",
                   help="actually delete (default: report only)")
    args = ap.parse_args(argv)

    if args.cmd == "audit":
        # CRC pass first: a file that fails its chunk CRCs is plain
        # detectable corruption, not the silent class
        try:
            bad = verify_checkpoint(args.file)
        except CheckpointCorruptionError as e:
            print(f"CORRUPT {args.file}: {e}")
            return 1
        if bad:
            print(f"CORRUPT {args.file}: chunk CRC mismatch "
                  f"(chunks {bad}) — detectable corruption, not SDC")
            return 1
        try:
            rep = audit_checkpoint(args.file)
        except CheckpointCorruptionError as e:
            print(f"CORRUPT {args.file}: {e}")
            return 1
        if rep is None:
            print(f"NO-RECORD {args.file}: sidecar carries no "
                  "integrity fingerprint (pre-SDC save or "
                  "DCCRG_INTEGRITY=0)")
            return 2
        rc = 0
        for name in sorted(rep):
            ok, got, want = rep[name]
            if ok:
                print(f"OK {args.file}: field {name} fingerprint "
                      f"({got[0]:#010x}, {got[1]:#010x})")
            else:
                rc = 1
                print(f"SDC {args.file}: field {name} payload "
                      f"fingerprint ({got[0]:#010x}, {got[1]:#010x}) "
                      f"!= device-state record ({want[0]:#010x}, "
                      f"{want[1]:#010x}) — the CRCs sealed corrupted "
                      "bytes")
        return rc

    if args.cmd == "verify":
        if is_delta_checkpoint(args.file):
            # a delta is only as good as its chain: verify every link
            try:
                links = verify_chain(args.file)
            except CheckpointCorruptionError as e:
                print(f"CORRUPT {args.file}: {e}")
                return 1
            print(f"OK {args.file} (chain of {len(links)}: "
                  + " -> ".join(os.path.basename(p) for p in links) + ")")
            return 0
        try:
            bad = verify_checkpoint(args.file)
        except CheckpointCorruptionError as e:
            print(f"CORRUPT {args.file}: {e}")
            return 1
        if bad:
            rec = read_sidecar(args.file)
            ranges = _rec_ranges(rec)
            names = ", ".join(_chunk_name(i, ranges) for i in bad)
            print(f"CORRUPT {args.file}: checksum mismatch in {names}")
            return 1
        print(f"OK {args.file}")
        return 0

    from . import supervise  # lazy: resilience must import standalone

    if args.cmd == "chain":
        chains = supervise.chain_report(args.dir, stem=args.stem)
        bad = 0
        for stem_name, links in chains:
            head = links[-1][0]
            print(f"chain {stem_name} @ step {head} "
                  f"({len(links)} link(s)):")
            for step, path, kind, status in links:
                if status != "OK":
                    bad += 1
                print(f"  {kind:<8} step {step:>8}  {status:<12} "
                      f"{os.path.basename(path)}")
        if not chains:
            print(f"no numbered checkpoints in {args.dir}")
        return 1 if bad else 0

    rep = supervise.gc_checkpoints(
        args.dir, keep_last=args.keep_last, keep_every=args.keep_every,
        stem=args.stem, apply=args.apply)
    verb = "pruned" if args.apply else "would prune"
    for step, path in rep.dropped:
        print(f"{verb} step {step}: {path}")
    for path in rep.stale_temps:
        print(f"{verb} stale temp file: {path}")
    if rep.rescued is not None:
        print(f"kept step {rep.rescued} beyond policy: it is the only "
              "checkpoint that passes verification")
    if rep.refused:
        print(f"REFUSED: {rep.refused}")
    print(f"{'applied' if rep.applied else 'dry-run'}: "
          f"{len(rep.kept)} kept, {len(rep.dropped)} "
          f"{'pruned' if rep.applied else 'prunable'}, "
          f"{len(rep.stale_temps)} stale temp file(s)"
          + ("" if args.apply else " — pass --apply to prune"))
    return 0


def _main(argv=None) -> int:
    """CLI probe for shell scripts: ``python -m dccrg_tpu.resilience
    [--timeout S] [--retries N] [--platform P]`` exits 0 and prints the
    devices when the backend answers, 1 otherwise — never hangs. The
    checkpoint-maintenance subcommands ``verify <file>``, ``audit
    <file>`` (at-rest SDC fingerprint audit), ``chain <dir>`` and
    ``gc <dir> [--keep-last K] [--keep-every N] [--apply]`` run
    without touching the accelerator at all (see
    :func:`_tool_main`)."""
    import argparse

    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("verify", "gc", "chain", "audit"):
        return _tool_main(argv)
    ap = argparse.ArgumentParser(description=_main.__doc__)
    ap.add_argument("--timeout", type=float, default=90.0)
    ap.add_argument("--retries", type=int, default=0)
    ap.add_argument("--backoff", type=float, default=2.0)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args(argv)
    try:
        devs = safe_devices(timeout=args.timeout, retries=args.retries,
                            backoff=args.backoff, platform=args.platform)
        print("OK", devs)
        return 0
    except DeviceProbeError as e:
        print("DOWN", e)
        return 1


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(_main())
