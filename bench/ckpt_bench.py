"""Checkpoint cost per periodic save: full keyframes vs dirty-field
deltas (the ROADMAP "Incremental checkpoints" item's measuring stick).

The workload is the production shape delta saves exist for — a step
loop over a multi-field schema where only the stepped field changes
between saves (the Vlasov-style wide per-cell payload of the
reference's home domain stays static): each periodic save is timed
and sized in both modes, ``full`` (``DCCRG_DELTA=0``: every save a
keyframe, byte-for-byte the pre-delta behavior — asserted against a
direct ``resilience.save_checkpoint``) and ``delta``
(``CheckpointStore.save`` dirty-field chains, keyframe cadence
``--keyframe-every``).  The final delta chain is materialized and
compared bitwise against a direct full save — the bench doubles as an
end-to-end integrity check.

``--overlap`` runs the async-save leg instead: the same periodic-save
loop with the next quantum's dispatch between save and drain,
measuring how much of each save's wall the serving loop actually
loses — synchronously (the whole save call) vs ``DCCRG_ASYNC_SAVE=1``
(the snapshot+submit call plus the residual drain after the quantum).
The saved files are asserted bitwise identical between the two legs
(the negative pin and the async parity pin in one comparison).
Acceptance: >= 70% of the save wall overlapped with the next
quantum's dispatch.

Run:  timeout -k 10 600 python bench/ckpt_bench.py [--n 32] [--saves 8]

JSON rows go to stdout like the other bench emitters; the summary row
carries the bytes-per-save table PERF.md quotes (acceptance: the
delta rows >= 10x fewer bytes than the full rows). The --overlap
summary's ``ckpt_stall_sync_seconds``/``ckpt_stall_async_seconds``
keys follow bench/trend.py's lower-is-better naming.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import dccrg_tpu as dt  # noqa: E402

# the multi-field scenario: one narrow stepped field, one wide static
# per-cell payload (Vlasov-style), one static tag — the step loop
# dirties ONLY "rho", so a delta carries the 16 B/cell offset-pair
# table + 4 B/cell of rho against the full format's ~276 B/cell
SCHEMA = {"rho": jnp.float32, "f": ((64,), jnp.float32),
          "tag": jnp.int32}


def _mk_grid(n, seed=0):
    g = (dt.Grid(cell_data=SCHEMA)
         .set_initial_length((n, n, n))
         .set_maximum_refinement_level(0)
         .set_neighborhood_length(1)
         .set_periodic(True, True, True)
         .initialize())
    rng = np.random.default_rng(seed)
    cells = g.plan.cells
    for name, (shape, dtype) in g.fields.items():
        g.set(name, cells,
              (rng.random((len(cells),) + shape) * 100).astype(dtype))
    g.update_copies_of_remote_neighbors()
    return g


def _kernel(c, nbr, offs, mask):
    return {"rho": 0.5 * c["rho"] + 0.125 * jnp.sum(
        jnp.where(mask, nbr["rho"], 0.0), axis=1)}


def run_mode(mode, n, saves, keyframe_every, workdir):
    """One measured pass: a step loop with a periodic save per step,
    in ``full`` (DCCRG_DELTA=0) or ``delta`` mode. Returns the rows."""
    from dccrg_tpu import resilience, supervise

    os.environ["DCCRG_DELTA"] = "0" if mode == "full" else "1"
    store_dir = os.path.join(workdir, mode)
    g = _mk_grid(n)
    store = supervise.CheckpointStore(store_dir,
                                      keyframe_every=keyframe_every)
    rows = []
    for step in range(saves):
        if step:
            g.run_steps(_kernel, ["rho"], ["rho"], 1)
        t0 = time.perf_counter()
        path = store.save(g, step)
        wall = time.perf_counter() - t0
        kind = ("delta" if path.endswith(resilience.DELTA_SUFFIX)
                else "keyframe")
        row = {"mode": mode, "step": step, "kind": kind,
               "bytes": os.path.getsize(path),
               "wall_s": round(wall, 4)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    final = store.list()[0][1]
    if mode == "full":
        # DCCRG_DELTA=0 must be byte-for-byte the pre-delta behavior
        direct = os.path.join(workdir, "direct.dc")
        resilience.save_checkpoint(g, direct)
        with open(final, "rb") as a, open(direct, "rb") as b:
            assert a.read() == b.read(), \
                "DCCRG_DELTA=0 save differs from a direct full save"
    else:
        # the chain must reconstruct the exact full bytes
        assert any(r["kind"] == "delta" for r in rows), \
            "delta mode produced no delta saves"
        direct = os.path.join(workdir, "direct_delta.dc")
        resilience.save_checkpoint(g, direct)
        if final.endswith(resilience.DELTA_SUFFIX):
            out = final + ".chain.bench"
            resilience.materialize_chain(final, out, g.fields)
            with open(out, "rb") as a, open(direct, "rb") as b:
                assert a.read() == b.read(), \
                    "materialized delta chain != direct full save"
            os.unlink(out)
    return rows


# ---------------------------------------------------------------------
# the --overlap leg: save wall overlapped with the next quantum
# ---------------------------------------------------------------------

def _sha(path):
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _async_write_wall_total():
    from dccrg_tpu import telemetry

    tot = 0.0
    for (nm, _lab), h in telemetry.registry().histograms.items():
        if nm == "dccrg_ckpt_async_write_seconds":
            tot += h.sum_seconds
    return tot


def run_overlap(n, saves, quantum_steps, workdir):
    """Periodic keyframe saves with the next quantum's dispatch
    between save and drain: the serving loop's actual per-save stall,
    synchronous vs DCCRG_ASYNC_SAVE=1, files bitwise identical. The
    async write's TRUE wall is measured on the writer thread
    (``dccrg_ckpt_async_write_seconds``), so the overlap fraction is
    (write wall not spent blocking the caller) / save wall — a short
    write under a long dispatch reads as a short save fully
    overlapped, not as a long one."""
    from dccrg_tpu import supervise

    def leg(async_on):
        os.environ["DCCRG_ASYNC_SAVE"] = "1" if async_on else "0"
        d = os.path.join(workdir, "async" if async_on else "sync")
        g = _mk_grid(n)
        g.run_steps(_kernel, ["rho"], ["rho"], quantum_steps)  # warm
        jax.block_until_ready(g.data["rho"])
        store = supervise.CheckpointStore(d, stem="ov")
        rows = []
        for i in range(saves):
            w0 = _async_write_wall_total()
            t0 = time.perf_counter()
            store.save(g, i, force_keyframe=True)
            submit = time.perf_counter() - t0
            t1 = time.perf_counter()
            g.run_steps(_kernel, ["rho"], ["rho"], quantum_steps)
            jax.block_until_ready(g.data["rho"])
            dispatch = time.perf_counter() - t1
            t2 = time.perf_counter()
            store.drain()
            residual = time.perf_counter() - t2
            # the save's wall: the blocking submit (snapshot/pull)
            # plus the write's wall as measured ON the writer thread
            # (sync mode: the save call is the whole wall)
            write_wall = (_async_write_wall_total() - w0 if async_on
                          else 0.0)
            save_wall = submit + write_wall if async_on else submit
            # the write ran concurrently with dispatch except for the
            # tail the caller had to block for (the residual drain)
            overlapped = max(0.0, write_wall - residual)
            rows.append({"save_call_s": submit, "dispatch_s": dispatch,
                         "drain_s": residual,
                         "stall_s": submit + residual,
                         "write_wall_s": write_wall,
                         "save_wall_s": save_wall,
                         "overlapped_s": overlapped if async_on else 0.0})
        digests = {os.path.basename(p): _sha(p) for _s, p in store.list()}
        return rows, digests

    sync_rows, sync_digests = leg(False)
    async_rows, async_digests = leg(True)
    os.environ.pop("DCCRG_ASYNC_SAVE", None)
    assert sync_digests == async_digests, \
        "DCCRG_ASYNC_SAVE=1 checkpoints differ bitwise from sync saves"
    mean = lambda rs, k: sum(r[k] for r in rs) / max(1, len(rs))  # noqa: E731
    wall_sync = mean(sync_rows, "stall_s")
    stall_async = mean(async_rows, "stall_s")
    # the acceptance metric: what fraction of the async save's wall
    # (blocking submit + the write's true writer-thread wall) ran
    # CONCURRENTLY with the next quantum's dispatch — i.e. everything
    # except the submit and the residual drain tail. The separate
    # stall-reduction ratio is the serving-loop payoff.
    overlap_frac = (mean(async_rows, "overlapped_s")
                    / max(mean(async_rows, "save_wall_s"), 1e-9))
    summary = {
        "cells": n ** 3, "saves": saves,
        "quantum_steps": quantum_steps,
        "ckpt_stall_sync_seconds": round(wall_sync, 4),
        "ckpt_stall_async_seconds": round(stall_async, 4),
        "async_submit_s_per_save": round(mean(async_rows,
                                              "save_call_s"), 4),
        "async_write_wall_s_per_save": round(mean(async_rows,
                                                  "write_wall_s"), 4),
        "async_residual_drain_s_per_save": round(mean(async_rows,
                                                      "drain_s"), 4),
        "dispatch_s_per_quantum": round(mean(async_rows,
                                             "dispatch_s"), 4),
        "save_wall_overlap_frac": round(overlap_frac, 3),
        "stall_reduction_frac": round(
            max(0.0, 1.0 - stall_async / max(wall_sync, 1e-9)), 3),
        "files_bitwise_identical": True,
    }
    for r in sync_rows:
        print(json.dumps(dict(r, mode="sync")), flush=True)
    for r in async_rows:
        print(json.dumps(dict(r, mode="async")), flush=True)
    print(json.dumps({"overlap_summary": summary}), flush=True)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32,
                    help="grid edge length (n^3 level-0 cells)")
    ap.add_argument("--saves", type=int, default=8,
                    help="periodic saves per mode")
    ap.add_argument("--keyframe-every", type=int, default=8)
    ap.add_argument("--overlap", action="store_true",
                    help="measure per-save serving stall sync vs "
                         "DCCRG_ASYNC_SAVE=1 (files asserted bitwise "
                         "identical)")
    ap.add_argument("--quantum-steps", type=int, default=48,
                    help="steps dispatched between an async save's "
                         "submit and its drain (the overlap window)")
    args = ap.parse_args()

    # a CPU-only host bench: probe the CPU backend in a killable
    # subprocess before any jax work
    from dccrg_tpu.resilience import safe_devices

    safe_devices(timeout=120, retries=1, platform="cpu")

    workdir = tempfile.mkdtemp(prefix="dccrg_ckpt_bench_")
    try:
        if args.overlap:
            return run_overlap(args.n, args.saves, args.quantum_steps,
                               workdir)
        rows = []
        for mode in ("full", "delta"):
            rows += run_mode(mode, args.n, args.saves,
                             args.keyframe_every, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    full = [r for r in rows if r["mode"] == "full"]
    delt = [r for r in rows if r["mode"] == "delta"
            and r["kind"] == "delta"]
    all_delta_mode = [r for r in rows if r["mode"] == "delta"]
    mean = lambda rs, k: sum(r[k] for r in rs) / max(1, len(rs))  # noqa: E731
    summary = {
        "cells": args.n ** 3, "saves": args.saves,
        "keyframe_every": args.keyframe_every,
        "full_bytes_per_save": round(mean(full, "bytes")),
        "delta_bytes_per_save": round(mean(delt, "bytes")),
        "chain_mean_bytes_per_save":
            round(mean(all_delta_mode, "bytes")),
        "full_wall_s_per_save": round(mean(full, "wall_s"), 4),
        "delta_wall_s_per_save": round(mean(delt, "wall_s"), 4),
        "bytes_ratio_full_over_delta":
            round(mean(full, "bytes") / max(1.0, mean(delt, "bytes")), 1),
    }
    print(json.dumps({"summary": summary}), flush=True)
    return summary


if __name__ == "__main__":
    main()
