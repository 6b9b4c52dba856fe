"""Distributed coordination: timeout-guarded barriers, guarded
``jax.distributed`` bring-up, and cross-rank trip consensus.

The reference dccrg leans on MPI's collective semantics: a rank that
dies makes the next collective fail *somewhere*, and the job scheduler
reaps the rest. JAX multi-controller gives no such courtesy —
``sync_global_devices`` simply never returns if a participant is gone,
and a checkpoint save that died on one rank leaves every other rank
blocked forever with a half-written file on disk. This module is the
coordination layer the multi-process paths (checkpoint two-phase
commit, :class:`~dccrg_tpu.resilience.ResilientRunner`) thread their
rank synchronization through:

- :func:`barrier` — a tagged, timeout-guarded barrier. Real meshes go
  through the ``jax.distributed`` coordination-service barrier (which
  has a deadline) when available, else ``sync_global_devices`` under a
  watchdog thread. Either way a lost rank surfaces as a typed
  :class:`BarrierTimeoutError` *naming the tag* within the configured
  bound (``DCCRG_BARRIER_TIMEOUT``, default 120 s) instead of hanging
  the job. Fault injection (:meth:`~dccrg_tpu.faults.FaultPlan
  .barrier_hang`) exercises the watchdog deterministically on a single
  controller.
- :func:`distributed_init` — ``jax.distributed.initialize`` with
  bounded retry + exponential backoff for the transient failures of
  real cluster bring-up (coordination service not listening yet, port
  races), raising :class:`DistributedInitError` when the budget is
  spent.
- :func:`trip_consensus` — all-reduces a per-rank trip code over the
  mesh (max), so rollback decisions that originate on ONE host (a
  ``MutationAbortedError``, an OOM, a watchdog hook) are taken by
  EVERY rank together: all ranks roll back to the same checkpoint
  instead of deadlocking in a barrier half of them never reach.
  :func:`broadcast_fatal` is its deadline-bounded best-effort variant
  for a rank that is about to die and must not hang while saying so.
- :class:`CheckpointCommitError` — the abort signal of the two-phase
  multi-process checkpoint commit (checkpoint._save_process_slice):
  raised by the committing rank when a slice is missing or fails its
  CRC, with the previous checkpoint still intact under the final name.
- :func:`seal_record` / :func:`unseal_record` / :func:`kv_barrier` —
  the primitives the distributed-AMR commit (dccrg_tpu/distamr.py)
  rides: CRC-framed KV records (a torn write convicts as
  :class:`TornRecordError`, never acts), and a presence-key barrier
  with an EXPLICIT participant set that doubles as a small all-gather,
  watches an epoch fence (:class:`StaleFenceError` — a SIGSTOP zombie
  that wakes after the fleet moved on must lose) and a peer abort
  marker (:class:`RemoteAbortError` — distributed rollback propagates
  faster than a timeout), and upgrades expiry to
  :class:`PeerDeadError` under a membership lease view.
- :class:`Membership` — elastic fleet membership: every rank writes a
  heartbeat lease into the coordination KV store
  (``DCCRG_HEARTBEAT_S`` cadence), and peers classify each other
  live/suspect/dead from the OBSERVED lease age (the observer's own
  clock ages a value it saw stop changing — no cross-host clock
  comparison, ``DCCRG_LEASE_S`` is the death bound).
  :meth:`Membership.poll` / :meth:`Membership.detect_dead_ranks` are
  deadline-bounded through :func:`run_with_deadline` so a wedged KV
  read can never block the step loop — on expiry the caller keeps the
  last view. A :class:`Membership` registered via
  :func:`set_membership` upgrades barrier timeouts: a barrier whose
  peer is DEAD by lease raises :class:`PeerDeadError` *naming the
  rank* (a :class:`BarrierTimeoutError` subclass, so every existing
  handler keeps working) instead of timing out and blaming a tag.
- :class:`InMemoryKV` / :class:`CoordKV` — the KV store the
  membership leases and the scheduler's job leases ride.
  ``create()`` is first-writer-wins (the coordination service's
  ``allow_overwrite=False`` IS a compare-and-set), which is what
  makes a double-reclaim race resolve to exactly one winner.

Everything degrades to a no-op on a single controller, so
single-process code pays one ``process_count()`` check per call.
"""

from __future__ import annotations

import logging
import os
import threading
import time

import numpy as np

from . import faults

logger = logging.getLogger("dccrg_tpu.coord")

DEFAULT_BARRIER_TIMEOUT = 120.0

# Barrier ids must be unique AND align across ranks. A PER-TAG counter
# (not one global sequence) keeps them aligned even when ranks' barrier
# histories diverge on OTHER tags — e.g. a save that failed mid-protocol
# on one rank consumed that save's tags only, so an unrelated barrier
# still matches. Within one tag the contract is: every rank calls it the
# same number of times; protocols that can fail asymmetrically BETWEEN
# calls of the same tag must fold an attempt epoch into the tag itself
# (the two-phase checkpoint save tags carry `#<attempt>` for exactly
# this — a collective retry re-aligns by construction).
_tag_seq: dict = {}


def _next_seq(tag: str) -> int:
    seq = _tag_seq.get(tag, 0)
    _tag_seq[tag] = seq + 1
    return seq


class BarrierTimeoutError(RuntimeError):
    """A tagged barrier did not complete within its bound: a
    participating rank is gone (process death, hung collective, hung
    device). ``tag``/``timeout`` carry the details."""

    def __init__(self, tag: str, timeout: float):
        super().__init__(
            f"barrier {tag!r} did not complete within {timeout:g}s: a "
            "participating rank is unreachable (process death, hung "
            "collective, or hung device)")
        self.tag = tag
        self.timeout = timeout


class DistributedInitError(RuntimeError):
    """``jax.distributed.initialize`` failed after every bounded
    retry."""


class CheckpointCommitError(RuntimeError):
    """The two-phase multi-process checkpoint commit aborted: one or
    more ranks' slices are missing or fail their CRC32, so the new file
    was NOT published and the previous checkpoint stays bitwise intact
    under the final name. ``ranks`` names the writers whose slices
    failed (the dead/torn ranks)."""

    def __init__(self, msg, ranks=()):
        super().__init__(msg)
        self.ranks = sorted({int(r) for r in ranks})


class TornRecordError(RuntimeError):
    """A sealed coordination record (:func:`seal_record`) failed its
    CRC32 frame — the half-written KV record of a writer that died (or
    was SIGKILLed) mid-write. The reader must treat the record as
    absent-and-poisoned: abort the protocol round, never act on the
    payload. ``key`` names the record when known."""

    def __init__(self, key: str = "", detail: str = ""):
        super().__init__(
            f"coordination record {key!r} is torn (CRC mismatch"
            f"{': ' + detail if detail else ''})")
        self.key = key


class StaleFenceError(RuntimeError):
    """An epoch-fenced coordination point observed the fence move past
    the epoch this participant entered under: this process is a ZOMBIE
    — it was stopped (SIGSTOP, GC pause, swapped host) while the
    surviving ranks completed (or re-formed) the protocol round and
    advanced the fence. The only safe move is a full local rollback to
    the pre-round state; rejoining happens at the NEW fence through the
    fleet layer, never by finishing the stale round."""

    def __init__(self, tag: str, expected, observed):
        super().__init__(
            f"fenced point {tag!r}: fence moved {expected!r} -> "
            f"{observed!r} while this rank was inside the round — this "
            "rank is a zombie; rolling back to the pre-round state")
        self.tag = tag
        self.expected = expected
        self.observed = observed


class RemoteAbortError(RuntimeError):
    """A PEER rank aborted the distributed transaction this rank is
    inside and posted an abort marker — the distributed-rollback fast
    path: every waiting participant raises this immediately instead of
    burning its barrier timeout. ``rank`` names the aborter (-1 when
    the marker was unreadable), ``reason`` its message."""

    def __init__(self, tag: str, rank: int = -1, reason: str = ""):
        super().__init__(
            f"distributed commit {tag!r}: peer rank {rank} aborted"
            f"{' (' + reason + ')' if reason else ''} — rolling back")
        self.tag = tag
        self.rank = int(rank)
        self.reason = reason


class PeerDeadError(BarrierTimeoutError):
    """A coordination point failed because one or more PEER RANKS are
    dead by membership lease (no heartbeat within ``DCCRG_LEASE_S``) —
    the detecting side of a host failure. Subclasses
    :class:`BarrierTimeoutError` so every existing timeout handler
    keeps working, but ``ranks`` names the culprits instead of the
    barrier tag having to take the blame."""

    def __init__(self, tag: str, timeout: float, ranks, lease_s=None):
        ranks = sorted({int(r) for r in ranks})
        lease = "" if lease_s is None else f" within {lease_s:g}s"
        RuntimeError.__init__(
            self,
            f"barrier {tag!r}: peer rank(s) {ranks} are DEAD by "
            f"membership lease (no heartbeat observed{lease}); their "
            "jobs are reclaimable by the survivors")
        self.tag = tag
        self.timeout = timeout
        self.ranks = ranks


def barrier_timeout(default: float = DEFAULT_BARRIER_TIMEOUT) -> float:
    """The ``DCCRG_BARRIER_TIMEOUT`` env knob: seconds before a
    coordination barrier gives up on its peers."""
    try:
        return float(os.environ.get("DCCRG_BARRIER_TIMEOUT", "") or default)
    except ValueError:
        return default


def run_with_deadline(fn, timeout: float, name: str = "deadline"):
    """Run ``fn()`` on a daemon worker thread bounded by ``timeout``
    seconds — the shared watchdog primitive behind the barrier sync,
    the fatal-trip broadcast and the supervision layer's step/save
    deadlines. Returns ``(finished, result, error)``; on expiry the
    worker is abandoned (``finished=False``) — a wedged callee cannot
    be cancelled, only reported — and the caller decides whether that
    is a typed error or a logged shrug."""
    box, err = [], []
    done = threading.Event()

    def _work():
        try:
            box.append(fn())
        except BaseException as e:  # noqa: BLE001 - caller's to re-raise
            err.append(e)
        finally:
            done.set()

    t = threading.Thread(target=_work, daemon=True, name=f"dccrg-{name}")
    t.start()
    if not done.wait(float(timeout)):
        return False, None, None
    return True, (box[0] if box else None), (err[0] if err else None)


def _coordination_client():
    """The jax.distributed coordination-service client, or None (not
    initialized, or jax internals drifted)."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    except Exception:  # pragma: no cover - jax internals drift
        return None


def barrier(tag: str, timeout: float | None = None) -> None:
    """Synchronize every process at a tagged point, or raise
    :class:`BarrierTimeoutError` naming the tag within ``timeout``
    seconds (default: :func:`barrier_timeout`).

    Single-controller meshes return immediately. Real multi-process
    meshes prefer the coordination-service barrier (deadline built in);
    when only ``sync_global_devices`` is available it runs on a daemon
    watchdog thread so the caller can never block past the bound (the
    hung thread is abandoned — a barrier that lost a rank is not
    recoverable anyway, only reportable). An injected
    :meth:`~dccrg_tpu.faults.FaultPlan.barrier_hang` replaces the sync
    with a sleep, exercising the watchdog machinery deterministically
    without a cluster."""
    timeout = barrier_timeout() if timeout is None else float(timeout)
    faults.fire("coord.barrier", tag=tag)
    hang = faults.take_barrier_hang(tag)
    import jax

    # the membership fast path: a peer the heartbeat leases already
    # declared dead will never reach this barrier — raise the typed
    # error NAMING the rank now instead of burning the full timeout
    # (in-process fleets register a membership too, so the check
    # precedes the single-controller early return)
    _raise_if_peer_dead(tag, timeout, poll=False)
    real = jax.process_count() > 1
    if not real and hang is None:
        return
    seq = _next_seq(tag)
    if hang is None:
        client = _coordination_client()
        if client is not None:
            try:
                client.wait_at_barrier(f"dccrg:{tag}:{seq}",
                                       int(timeout * 1000))
                return
            except Exception as e:
                # the service reports a lost rank either as our
                # deadline expiring or as the peer's task failing its
                # heartbeat — both mean the same thing to the caller
                msg = str(e)
                if ("DEADLINE_EXCEEDED" in msg or "Barrier failed" in msg
                        or "heartbeat timeout" in msg):
                    _raise_if_peer_dead(tag, timeout, poll=True)
                    raise BarrierTimeoutError(tag, timeout) from e
                raise

    # watchdog-thread path: sync_global_devices has no deadline of its
    # own, and the injected hang must exercise this same machinery
    def _sync():
        if hang is not None:
            # a simulated lost rank: the sync never happens; a
            # finite hang_s below the timeout models a slow-but-
            # alive peer the barrier should survive
            time.sleep(min(hang, timeout + 30.0))
        elif real:  # pragma: no cover - needs a real cluster
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"dccrg:{tag}:{seq}")

    finished, _res, err = run_with_deadline(_sync, timeout,
                                            f"barrier:{tag}")
    if not finished:
        _raise_if_peer_dead(tag, timeout, poll=True)
        raise BarrierTimeoutError(tag, timeout)
    if err is not None:
        raise err


def distributed_init(coordinator_address=None, num_processes=None,
                     process_id=None, *, retries: int = 3,
                     backoff: float = 0.5, **kwargs) -> None:
    """``jax.distributed.initialize`` with bounded retry + exponential
    backoff: real cluster bring-up fails transiently (the coordinator
    is not listening yet, a port race, a slow DNS answer) and the raw
    call just dies. Raises :class:`DistributedInitError` with the last
    failure chained once the budget is spent."""
    import jax

    last: Exception | None = None
    for attempt in range(retries + 1):
        try:
            faults.fire("coord.init", attempt=attempt)
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                **kwargs)
            return
        except Exception as e:  # noqa: BLE001 - retried, then surfaced
            last = e
            if attempt < retries:
                delay = backoff * (2 ** attempt)
                logger.warning(
                    "distributed init failed (%s); retry %d/%d in %.1fs",
                    e, attempt + 1, retries, delay)
                time.sleep(delay)
    raise DistributedInitError(
        f"jax.distributed.initialize failed after {retries + 1} "
        f"attempt(s): {last}") from last


def process_rank(grid) -> int:
    """This controller's rank for checkpoint coordination:
    ``jax.process_index()``, or the per-pass rank a faked test split
    pinned on the grid (``grid._ckpt_rank``)."""
    r = getattr(grid, "_ckpt_rank", None)
    if r is not None:
        return int(r)
    import jax

    return int(jax.process_index())


def trip_consensus(grid, code: int) -> int:
    """All-reduce (max) a per-rank trip code across the mesh.

    :class:`~dccrg_tpu.resilience.ResilientRunner` calls this every
    step so trip/rollback decisions that originate host-side on ONE
    rank (``MutationAbortedError`` from a failed adapt, an OOM, the
    watchdog hook inside ``run_steps``) are taken by EVERY rank: all
    ranks roll back to the same checkpoint together instead of the
    tripped rank abandoning a collective its peers are still waiting
    in. Codes are small ints ordered by priority (0 = no trip;
    resilience._TRIP_INTERRUPT = a consensus-agreed step-boundary
    interrupt, e.g. a preemption signal — outranked by any real trip;
    recoverable trips — every rank rolls back together; >=
    resilience._TRIP_FATAL marks a non-recoverable failure — every
    rank raises in sync); the max across ranks wins.
    Single-controller grids return ``code`` unchanged — the reduction
    (a cached compiled collective, see comm._mesh_map) only runs on
    multi-process meshes."""
    code = int(code)
    if not grid._multiproc:
        return code
    from . import comm

    flags = np.zeros(grid.n_dev, dtype=np.int32)
    flags[grid._proc_local_dev] = np.int32(code)
    return int(comm.host_all_reduce(grid.mesh, flags, "max"))


def broadcast_fatal(grid, code: int, timeout: float | None = None) -> None:
    """Best-effort, deadline-bounded :func:`trip_consensus` broadcast
    for a rank on its way out of a non-recoverable error. The mesh may
    be the very thing that is broken (a wedged collective is exactly
    what :class:`~dccrg_tpu.supervise.StepTimeoutError` reports), so
    the courtesy broadcast runs on a daemon watchdog thread and is
    abandoned after ``timeout`` seconds (default:
    :func:`barrier_timeout`) — telling the peers must never keep the
    dying rank alive. Exceptions are swallowed: the caller is about to
    re-raise the error that actually matters."""
    timeout = barrier_timeout() if timeout is None else float(timeout)

    def _send():
        try:
            trip_consensus(grid, code)
        except Exception:  # noqa: BLE001 - the original error outranks it
            pass

    finished, _res, _err = run_with_deadline(_send, timeout,
                                             "fatal-broadcast")
    if not finished:  # pragma: no cover - needs a wedged mesh
        logger.warning(
            "fatal trip code %d could not be broadcast within %.0fs "
            "(the mesh itself is unreachable); peers must rely on "
            "their own barrier timeouts", code, timeout)


# ---------------------------------------------------------------------
# elastic membership: heartbeat leases over the coordination KV store
# ---------------------------------------------------------------------

DEFAULT_HEARTBEAT_S = 2.0
DEFAULT_LEASE_S = 8.0


def heartbeat_seconds(default: float = DEFAULT_HEARTBEAT_S) -> float:
    """The ``DCCRG_HEARTBEAT_S`` env knob: seconds between a rank's
    heartbeat-lease renewals in the coordination KV store."""
    try:
        v = float(os.environ.get("DCCRG_HEARTBEAT_S", "") or default)
    except ValueError:
        v = default
    return max(0.01, v)


def lease_seconds(default: float | None = None) -> float:
    """The ``DCCRG_LEASE_S`` env knob: seconds without an observed
    heartbeat before a peer rank is declared DEAD (and its job leases
    reclaimable). Clamped to at least two heartbeats — a lease shorter
    than that would flap on ordinary scheduling jitter."""
    hb = heartbeat_seconds()
    fallback = DEFAULT_LEASE_S if default is None else float(default)
    try:
        v = float(os.environ.get("DCCRG_LEASE_S", "") or fallback)
    except ValueError:
        v = fallback
    return max(2.0 * hb, v)


class InMemoryKV:
    """Process-local KV store with the coordination service's
    compare-and-set semantics (:meth:`create` is first-writer-wins).
    The single-process default, and the store the fake-clock
    lease/fencing tests share between in-process 'ranks'."""

    def __init__(self):
        self._data: dict = {}
        self._lock = threading.Lock()

    def set(self, key: str, value: str) -> None:
        with self._lock:
            self._data[str(key)] = str(value)

    def create(self, key: str, value: str) -> bool:
        """Create ``key`` iff absent; False when another writer won
        the race (THE compare-and-set the lease fencing rides)."""
        with self._lock:
            if str(key) in self._data:
                return False
            self._data[str(key)] = str(value)
            return True

    def get(self, key: str):
        with self._lock:
            return self._data.get(str(key))

    def dir_get(self, prefix: str):
        """Every ``(key, value)`` under ``prefix`` as a dict — the
        one-call census the lease machinery prefers over per-key
        reads (an ABSENT key costs a full blocking-get timeout on the
        real service; a prefix listing only returns what exists)."""
        prefix = str(prefix)
        with self._lock:
            return {k: v for k, v in self._data.items()
                    if k.startswith(prefix)}

    def delete(self, key: str) -> None:
        with self._lock:
            self._data.pop(str(key), None)


class CoordKV:
    """The real ``jax.distributed`` coordination-service KV store.
    ``create()`` maps to ``key_value_set`` WITHOUT overwrite — the
    service rejects an existing key, which is the first-writer-wins
    compare-and-set exactly one reclaimer may win. Reads use a short
    blocking get (this jaxlib has no try-get); every operation
    swallows service errors into None/False — a dying coordination
    service must degrade into observed staleness (the failure mode
    the lease machinery already handles), never a crash."""

    #: how long a read waits for a key that may simply not exist yet
    GET_TIMEOUT_MS = 100

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str) -> None:
        try:
            self._client.key_value_set(str(key), str(value),
                                       allow_overwrite=True)
        except TypeError:  # pragma: no cover - older jaxlib signature
            try:
                self._client.key_value_delete(str(key))
            except Exception:  # noqa: BLE001 - best effort
                pass
            try:
                self._client.key_value_set(str(key), str(value))
            except Exception:  # noqa: BLE001 - best effort
                pass
        except Exception:  # noqa: BLE001 - degrade to staleness
            pass

    def create(self, key: str, value: str) -> bool:
        try:
            # no allow_overwrite: the service refuses an existing key
            self._client.key_value_set(str(key), str(value))
            return True
        except Exception:  # noqa: BLE001 - lost the CAS (or no service)
            return False

    def get(self, key: str):
        try:
            return self._client.blocking_key_value_get(
                str(key), self.GET_TIMEOUT_MS)
        except Exception:  # noqa: BLE001 - absent key / dead service
            return None

    def dir_get(self, prefix: str):
        """One prefix listing instead of N blocking gets (an absent
        key costs the full GET_TIMEOUT_MS; a listing returns only
        what exists). None on service error — the caller falls back
        to per-key reads."""
        try:
            return dict(self._client.key_value_dir_get(str(prefix)))
        except Exception:  # noqa: BLE001 - degrade to per-key reads
            return None

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(str(key))
        except Exception:  # noqa: BLE001 - best effort
            pass


_LOCAL_KV: "InMemoryKV | None" = None


def default_kv():
    """The KV store leases ride: the coordination service's when
    ``jax.distributed`` is initialized, else one process-global
    :class:`InMemoryKV` (single-host serving needs no coordination,
    but the code paths stay identical)."""
    client = _coordination_client()
    if client is not None:
        return CoordKV(client)
    global _LOCAL_KV
    if _LOCAL_KV is None:
        _LOCAL_KV = InMemoryKV()
    return _LOCAL_KV


def prefix_census(kv, prefix: str):
    """One-call ``{full_key: value}`` snapshot of every key under
    ``prefix``, or None when the KV cannot list (callers then fall
    back to per-key reads). On the real coordination service an
    ABSENT key costs a full blocking-get timeout, so every tick-path
    consumer (job leases, the streaming-intake front door) reads one
    census instead of per-key; the service may list RELATIVE child
    names, which are normalized back to full keys so lookups are
    uniform across KV implementations."""
    dir_get = getattr(kv, "dir_get", None)
    if dir_get is None:
        return None
    raw = dir_get(str(prefix))
    if raw is None:
        return None
    p = str(prefix).rstrip("/") + "/"
    return {(str(k) if str(k).startswith(p) else p + str(k)): v
            for k, v in raw.items()}


# ---------------------------------------------------------------------
# sealed records + fenced KV barrier (the distributed-AMR commit rides
# these; see dccrg_tpu/distamr.py)
# ---------------------------------------------------------------------

def seal_record(payload: str) -> str:
    """Frame ``payload`` with its CRC32 (``crc:length:payload``) for a
    KV write that may be observed half-done: the coordination service
    itself writes atomically, but a writer can die BETWEEN composing a
    record and meaning it, and fault injection deliberately stores torn
    tails — the frame lets every reader convict a damaged record
    instead of acting on it."""
    import zlib

    data = str(payload)
    raw = data.encode("utf-8")
    return f"{zlib.crc32(raw) & 0xFFFFFFFF:08x}:{len(raw)}:{data}"


def unseal_record(record: str, key: str = "") -> str:
    """Verify and strip a :func:`seal_record` frame; raises
    :class:`TornRecordError` naming ``key`` when the CRC or length
    does not match the payload."""
    import zlib

    try:
        crc_hex, length, data = str(record).split(":", 2)
        want_crc = int(crc_hex, 16)
        want_len = int(length)
    except (ValueError, AttributeError):
        raise TornRecordError(key, "unparseable frame") from None
    raw = data.encode("utf-8")
    if len(raw) != want_len:
        raise TornRecordError(key, f"length {len(raw)} != {want_len}")
    if (zlib.crc32(raw) & 0xFFFFFFFF) != want_crc:
        raise TornRecordError(key, "payload CRC mismatch")
    return data


def atomic_file_write(path: str, data: str, *, tmp_dir=None) -> str:
    """Durably land a small file: write to a temp sibling (or
    ``tmp_dir``), fsync, then atomically ``os.replace`` onto ``path``
    — the intake-spool discipline, shared by every small on-disk
    record in the package (a crashed writer leaves either the old
    complete file or an invisible temp, never a torn visible one).
    The temp name carries the writer's pid so crash litter is
    attributable (swept by the stale-temp GC patterns)."""
    d = tmp_dir if tmp_dir is not None else (os.path.dirname(path)
                                             or ".")
    tmp = os.path.join(
        str(d), f".{os.path.basename(path)}.tmp.{os.getpid()}")
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def write_sealed_file(path: str, payload: str, *, tmp_dir=None) -> str:
    """:func:`seal_record` + :func:`atomic_file_write`: a CRC-framed
    durable small-file record any reader can convict instead of
    trusting (the warm-start manifest entries ride this)."""
    return atomic_file_write(path, seal_record(payload),
                             tmp_dir=tmp_dir)


def read_sealed_file(path: str, key: str = "") -> str:
    """Read and verify a :func:`write_sealed_file` record; raises
    :class:`TornRecordError` (naming ``key``, default the path) on a
    damaged frame. OSErrors propagate — absent and unreadable are the
    caller's distinction to make."""
    with open(path) as f:
        raw = f.read()
    return unseal_record(raw, key or str(path))


def kv_barrier(kv, tag: str, rank: int, ranks, timeout=None, *,
               value: str = "1", poll_s: float = 0.02, fence=None,
               abort_key=None, membership=None) -> dict:
    """Presence-key barrier over the coordination KV: each participant
    writes ``<tag>/<rank> = value`` and polls until every rank in
    ``ranks`` has arrived, then returns ``{rank: value}`` — the barrier
    doubles as an all-gather of one small record per rank (the
    distributed-AMR commit meets at it with structure digests as the
    values, so agreement checking costs no extra round).

    Unlike the coordination-service barrier this one takes an EXPLICIT
    participant set, so a collective that lost a rank can re-form over
    the survivors, and in-process fake ranks (tests) can meet at it.
    While polling it watches for the two conditions that must abort a
    distributed round faster than a timeout:

    - ``fence=(key, expected)``: raises :class:`StaleFenceError` the
      moment the fence key moves off ``expected`` — a stopped rank that
      wakes after the fleet committed without it must lose, not finish.
      The first element may also be a zero-arg callable returning the
      current fence (the distributed-AMR group's monotonic epoch read)
      instead of a KV key.
    - ``abort_key``: raises :class:`RemoteAbortError` the moment a peer
      posts an abort marker there (the distributed-rollback fast path).
      The marker also VETOES completion: arrival keys are monotonic
      within a round, so a peer that arrived and later aborted (a
      deeper-phase failure, a commit-wait timeout) leaves its arrivals
      behind as ghosts — a slow rank waking into a "complete" barrier
      of an aborted round must abort with the fleet, not finish alone.

    On expiry, a ``membership`` whose lease view declares a missing
    peer DEAD upgrades the timeout to :class:`PeerDeadError` naming the
    rank; otherwise :class:`BarrierTimeoutError` blames the tag. An
    injected :meth:`~dccrg_tpu.faults.FaultPlan.barrier_hang` for the
    tag replaces this rank's arrival with a sleep, exercising the
    peers' timeout machinery deterministically."""
    timeout = barrier_timeout() if timeout is None else float(timeout)
    expected = sorted({int(r) for r in ranks})
    faults.fire("coord.barrier", tag=tag)
    hang = faults.take_barrier_hang(tag)
    deadline = time.monotonic() + timeout
    if hang is not None:
        # simulate a lost/slow rank: never (or late) post the arrival
        time.sleep(min(float(hang), max(0.0, deadline - time.monotonic())))
    kv.set(f"{tag}/{int(rank)}", str(value))

    def _arrivals() -> dict:
        got = kv.dir_get(f"{tag}/")
        if got is None:  # service hiccup: degrade to per-key reads
            got = {}
            for r in expected:
                v = kv.get(f"{tag}/{r}")
                if v is not None:
                    got[f"{tag}/{r}"] = v
        arrived = {}
        for k, v in got.items():
            tail = k.rsplit("/", 1)[-1]
            try:
                arrived[int(tail)] = v
            except ValueError:
                continue
        return arrived

    def _abort_marker():
        """Read the abort marker cheaply: a prefix listing returns
        only keys that EXIST, where the real service's get blocks
        ~100 ms on an absent one — this probe runs every poll and on
        every successful exit. The listing targets the marker's PARENT
        directory (the real service's dir-get only returns keys UNDER
        the prefix, never the prefix itself), then picks the exact
        key — which also keeps attempt 1 from shadowing attempt 10."""
        got = kv.dir_get(abort_key.rsplit("/", 1)[0] + "/")
        if got is not None:
            return got.get(abort_key)
        return kv.get(abort_key)

    def _finish(arrived: dict) -> dict:
        """Success-path exit: every expected rank arrived. An abort
        marker still vetoes completion (see docstring) — the arrival
        keys may be ghosts of a round the peers already rolled back."""
        if abort_key is not None:
            marker = _abort_marker()
            if marker is not None:
                raise _remote_abort(tag, abort_key, marker)
        return {r: arrived[r] for r in expected}

    last_live_check = 0.0
    while True:
        # completion is checked before the FENCE: presence keys are
        # monotonic within a round, so once any rank observed all
        # arrivals, every rank will — a fence bump the winner performs
        # right after passing must never strand a slower participant
        # that the barrier already counted. The ABORT marker is the
        # one thing that outranks completion (checked in _finish).
        arrived = _arrivals()
        if all(r in arrived for r in expected):
            return _finish(arrived)
        if fence is not None:
            fkey, fexp = fence
            cur = fkey() if callable(fkey) else kv.get(fkey)
            if cur is not None and str(cur) != str(fexp):
                # the real service's get BLOCKS briefly on an absent
                # key, so a bump landing during this very check can be
                # observed BEFORE the arrival that justified it was
                # re-read — re-sample the arrivals once: a barrier the
                # winner already counted this rank through must return
                # success, not convict a live participant as a zombie
                arrived = _arrivals()
                if all(r in arrived for r in expected):
                    return _finish(arrived)
                raise StaleFenceError(tag, fexp, cur)
        if abort_key is not None:
            marker = _abort_marker()
            if marker is not None:
                raise _remote_abort(tag, abort_key, marker)
        now = time.monotonic()
        if membership is not None and now - last_live_check > 0.25:
            last_live_check = now
            try:
                dead = set(membership.detect_dead_ranks())
            except Exception:  # noqa: BLE001 - view refresh is best-effort
                dead = set()
            missing_dead = [r for r in expected
                            if r not in arrived and r in dead]
            if missing_dead:
                raise PeerDeadError(tag, timeout, missing_dead,
                                    lease_s=membership.lease_s)
        if now >= deadline:
            raise BarrierTimeoutError(tag, timeout)
        time.sleep(poll_s)


def _remote_abort(tag: str, key: str, marker) -> RemoteAbortError:
    """Decode an abort marker into the typed error (tolerating a torn
    marker: an unreadable abort is still an abort)."""
    import json

    try:
        info = json.loads(unseal_record(marker, key))
        return RemoteAbortError(tag, rank=int(info.get("rank", -1)),
                                reason=str(info.get("reason", "")))
    except Exception:  # noqa: BLE001 - torn marker: abort anonymously
        return RemoteAbortError(tag, rank=-1, reason="torn abort marker")


class Membership:
    """Elastic fleet membership over heartbeat leases.

    Every rank :meth:`heartbeat`\\ s a monotonically bumped counter
    into the KV under ``<prefix>/<rank>`` at the ``heartbeat_s``
    cadence. :meth:`poll` reads every peer's key under a deadline
    (:func:`run_with_deadline` — a wedged KV read keeps the LAST view
    instead of blocking the step loop) and classifies each peer by
    how long ago the OBSERVER saw its value change:

    - ``live``    — changed within ``suspect_s`` (2 heartbeats);
    - ``suspect`` — stale past ``suspect_s`` but short of the lease;
    - ``dead``    — stale for ``lease_s`` or more: the rank's job
      leases are reclaimable, and barriers involving it raise
      :class:`PeerDeadError` instead of blaming a tag.

    Aging is strictly observer-clock (no cross-host clock
    comparison), ``clock`` is injectable (the fake-clock tests), and
    a peer that starts heartbeating again flips back to live — the
    elastic-regrow half of the contract. Every poll exports
    ``dccrg_fleet_membership{state}`` gauges and logs state
    transitions."""

    LIVE, SUSPECT, DEAD = "live", "suspect", "dead"

    def __init__(self, rank: int, n_ranks: int, *, kv=None,
                 heartbeat_s=None, lease_s=None, clock=time.monotonic,
                 prefix: str = "dccrg/hb"):
        self.rank = int(rank)
        self.n_ranks = max(1, int(n_ranks))
        self.kv = kv if kv is not None else default_kv()
        self.heartbeat_s = (heartbeat_seconds() if heartbeat_s is None
                            else max(0.01, float(heartbeat_s)))
        self.lease_s = max(2.0 * self.heartbeat_s,
                           lease_seconds() if lease_s is None
                           else float(lease_s))
        self.suspect_s = min(2.0 * self.heartbeat_s, self.lease_s / 2.0)
        self.clock = clock
        self.prefix = str(prefix)
        self._beat = 0
        self._last_beat_t = None
        self._auto = None
        now = self.clock()
        # a peer that has NEVER heartbeat gets the same full-lease
        # grace from construction as one that just stopped — a slow
        # starter is not a corpse
        self._seen = {r: [None, now] for r in range(self.n_ranks)
                      if r != self.rank}
        self._state = {r: self.LIVE for r in self._seen}

    def _key(self, rank: int) -> str:
        return f"{self.prefix}/{int(rank)}"

    def heartbeat(self, force: bool = False) -> bool:
        """Renew this rank's lease (throttled to ``heartbeat_s``
        unless ``force``); returns whether a write happened."""
        now = self.clock()
        if (not force and self._last_beat_t is not None
                and now - self._last_beat_t < self.heartbeat_s):
            return False
        self._beat += 1
        self.kv.set(self._key(self.rank), f"{self._beat}")
        self._last_beat_t = now
        return True

    def start_auto(self) -> None:
        """Start the daemon heartbeat thread (idempotent): liveness
        must not ride the serving loop's stalls — an XLA compile
        blocks a tick for seconds, and a compile is not a death. A
        SIGSTOP/SIGKILL freezes/kills this thread with the process,
        so the beats stop exactly when the host actually stops. Only
        meaningful under a real clock (fake-clock tests drive
        :meth:`heartbeat` by hand and never call this)."""
        if self._auto is not None:
            return
        stop = threading.Event()

        def _beat():
            while not stop.wait(self.heartbeat_s):
                try:
                    self.heartbeat(force=True)
                except Exception:  # noqa: BLE001 - beats are best-effort
                    pass

        t = threading.Thread(target=_beat, daemon=True,
                             name="dccrg-heartbeat")
        t.start()
        self._auto = (t, stop)

    def stop_auto(self) -> None:
        if self._auto is not None:
            self._auto[1].set()
            self._auto = None

    def _classify(self, age: float) -> str:
        if age >= self.lease_s:
            return self.DEAD
        if age > self.suspect_s:
            return self.SUSPECT
        return self.LIVE

    def poll(self, timeout: float | None = None) -> dict:
        """One deadline-bounded membership scan; returns
        ``{rank: state}`` for every peer. The KV reads run under
        :func:`run_with_deadline` (budget: ``timeout``, default one
        heartbeat, floor 50 ms) — on expiry the previous observations
        stand and keep aging, so a wedged store reads as staleness,
        never as a blocked step loop."""
        from . import telemetry

        budget = (max(0.05, self.heartbeat_s) if timeout is None
                  else max(0.01, float(timeout)))
        peers = list(self._seen)

        def _read():
            return [self.kv.get(self._key(r)) for r in peers]

        finished, vals, err = run_with_deadline(_read, budget,
                                                "membership-poll")
        now = self.clock()
        if finished and err is None and vals is not None:
            for r, v in zip(peers, vals):
                rec = self._seen[r]
                if v is not None and v != rec[0]:
                    rec[0], rec[1] = v, now
        else:
            telemetry.inc("dccrg_membership_poll_failures_total")
        for r, rec in self._seen.items():
            st = self._classify(now - rec[1])
            if st != self._state[r]:
                logger.warning(
                    "fleet membership: rank %d %s -> %s (lease age "
                    "%.2fs, lease bound %.2fs)", r, self._state[r], st,
                    now - rec[1], self.lease_s)
                telemetry.inc("dccrg_fleet_membership_transitions_total",
                              rank=str(r), state=st)
                self._state[r] = st
        counts = {self.LIVE: 1, self.SUSPECT: 0, self.DEAD: 0}  # self
        for st in self._state.values():
            counts[st] += 1
        for st, n in counts.items():
            telemetry.set_gauge("dccrg_fleet_membership", n, state=st)
        return dict(self._state)

    def detect_dead_ranks(self, timeout: float | None = None) -> list:
        """Deadline-bounded refresh + the ranks currently DEAD by
        lease. Never blocks past the poll budget."""
        self.poll(timeout=timeout)
        return self.dead_ranks()

    def state(self, rank: int) -> str:
        """``live``/``suspect``/``dead`` (self is always live)."""
        if int(rank) == self.rank:
            return self.LIVE
        return self._state.get(int(rank), self.DEAD)

    def lease_age(self, rank: int) -> float:
        """Seconds since this observer saw ``rank``'s lease change."""
        rec = self._seen.get(int(rank))
        return 0.0 if rec is None else self.clock() - rec[1]

    def dead_ranks(self) -> list:
        return sorted(r for r, s in self._state.items()
                      if s == self.DEAD)

    def live_ranks(self) -> list:
        """Every rank not currently dead, self included — the rank
        set the rank-aware scheduler partitions work over."""
        return sorted([self.rank] + [r for r, s in self._state.items()
                                     if s != self.DEAD])


#: the process-wide membership barrier timeouts consult — None (the
#: default) changes nothing anywhere
_MEMBERSHIP: list = [None]


def set_membership(m: "Membership | None") -> "Membership | None":
    """Register (or clear) the process-wide :class:`Membership` the
    barrier path consults; returns the previous one. With a
    registered membership, a barrier whose peer is DEAD by lease
    raises :class:`PeerDeadError` naming the rank instead of a bare
    :class:`BarrierTimeoutError` blaming the tag."""
    prev = _MEMBERSHIP[0]
    _MEMBERSHIP[0] = m
    return prev


def get_membership() -> "Membership | None":
    return _MEMBERSHIP[0]


def _raise_if_peer_dead(tag: str, timeout: float, poll: bool) -> None:
    """Raise :class:`PeerDeadError` when the registered membership
    (if any) knows of dead peers. ``poll=True`` refreshes the view
    first (bounded — this runs on the timeout path, where the barrier
    budget is already spent)."""
    m = _MEMBERSHIP[0]
    if m is None:
        return
    dead = (m.detect_dead_ranks(timeout=min(2.0, m.heartbeat_s * 2))
            if poll else m.dead_ranks())
    if dead:
        raise PeerDeadError(tag, timeout, dead, lease_s=m.lease_s)
