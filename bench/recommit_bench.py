"""AMR plan re-commit cost, per phase (the ROADMAP "Hybrid re-commit
cost at 192^3" item's measuring stick).

Each size refines a z-slab (1/64 of the level-0 cells) and commits,
then refines a second slab and commits again — the *reuse* epoch the
epoch-to-epoch stream cache and the plan arena accelerate — and
finally runs two more alternating unrefine/refine commits so the
steady-state adapt loop (warm arena, stable sticky-cap shapes) is on
record too.  ``--no-reuse`` clears the stream cache before every
re-commit, isolating the reuse machinery's contribution.  Per-phase
timings (classify, tables) come from the plan builder's
``dccrg_plan_phase_seconds{phase}`` gauge (no stdout parsing).

``--overlap`` runs the zero-stall leg instead: the same adapt epochs
with a serving loop (small run_steps quanta) around them, measuring
**step-loop stall seconds** — how long the loop is actually blocked —
synchronous vs ``DCCRG_BG_RECOMMIT=1`` background builds. In sync
mode the stall is the whole ``stop_refining`` wall; in background
mode it is the (resolve + submit) wall plus the step-boundary swap
install, read from the ``dccrg_recommit_stall_seconds`` histogram the
swap point feeds. Plan fingerprints are asserted bitwise-identical
between the two modes at every epoch, and the bg leg also reports the
steps it served while the build ran.

Run:  timeout -k 10 1800 python bench/recommit_bench.py [--max 128]
      (192^3 takes minutes on a 1-core host; opt in with --max 192)

JSON rows go to stdout like the other bench emitters; the --overlap
summary keys (``recommit<N>_stall_sync_seconds`` /
``_stall_bg_seconds``) follow the bench/trend.py lower-is-better
naming so checked-in rounds trend automatically.
"""

import argparse
import hashlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import dccrg_tpu as dt  # noqa: E402
from dccrg_tpu import telemetry  # noqa: E402

PLAN_PHASES = ("classify", "tables")  # what a hybrid re-commit marks


def _commit(g, reuse):
    if not reuse:
        # fingerprint mismatch -> full rebuild (streams recomputed);
        # the arena still serves warm buffers, isolating stream reuse
        g._hybrid_reuse = {}
    t0 = time.perf_counter()
    g.stop_refining()
    total = time.perf_counter() - t0
    reg = telemetry.registry()
    return total, {p: round(reg.gauge_value(telemetry.PLAN_PHASE_GAUGE,
                                            phase=p), 3)
                   for p in PLAN_PHASES}


def run_size(n, reuse=True):
    g = (dt.Grid(cell_data={"density": jnp.float32})
         .set_initial_length((n, n, n))
         .set_maximum_refinement_level(1)
         .set_neighborhood_length(1)
         .initialize())
    n0 = np.uint64(n) ** 3
    nref = int(n0) // 64
    rows = []

    def emit(epoch, total, phases):
        row = {
            "size": f"{n}^3", "epoch": epoch, "reuse": reuse,
            "cells": len(g.plan.cells), "total_s": round(total, 2),
            "phases": phases,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    cells = g.plan.cells
    for c in cells[:nref]:
        g.refine_completely(c)
    emit("first", *_commit(g, reuse))

    cells = g.plan.cells
    lvl0 = cells[cells <= n0]
    for c in lvl0[-nref:]:
        g.refine_completely(c)
    emit("recommit", *_commit(g, reuse))

    # steady-state adapt loop: alternate a smaller unrefine/refine so
    # the sticky-cap shapes (and with them the arena buffers) settle
    for it in range(2):
        cells = g.plan.cells
        lvl1 = cells[cells > n0]
        for c in lvl1[:nref // 2:8]:
            g.unrefine_completely(int(c))
        emit(f"steady{it}a", *_commit(g, reuse))
        cells = g.plan.cells
        lvl0 = cells[cells <= n0]
        for c in lvl0[:nref // 16]:
            g.refine_completely(int(c))
        emit(f"steady{it}b", *_commit(g, reuse))
    arena = getattr(g, "_plan_arena", None)
    if arena is not None:
        print(json.dumps({"size": f"{n}^3", "arena": arena.stats()}),
              flush=True)
    del g
    return rows


# ---------------------------------------------------------------------
# the --overlap leg: step-loop stall seconds, sync vs background
# ---------------------------------------------------------------------

def _plan_fp(g):
    """Compact bitwise plan fingerprint (layout + materialized hood
    tables; the lazy to-tables stay lazy in BOTH modes, so they are
    excluded identically)."""
    h = hashlib.sha256()
    p = g.plan
    h.update(np.ascontiguousarray(p.cells).tobytes())
    h.update(np.ascontiguousarray(p.owner).tobytes())
    h.update(str((p.L, p.R)).encode())
    h.update(np.ascontiguousarray(p.row_of_pos).tobytes())
    for hood in p.hoods.values():
        h.update(np.ascontiguousarray(hood.nbr_rows).tobytes())
        h.update(np.ascontiguousarray(hood.nbr_mask).tobytes())
        for t in (hood.scale_rows, hood.hard_rows, hood.hard_nbr_rows,
                  hood.hard_offs, hood.hard_mask):
            if t is not None:
                h.update(np.ascontiguousarray(t).tobytes())
    return h.hexdigest()


def _diffuse(cell, nbr, offs, mask, *extra):
    s = jnp.sum(jnp.where(mask, nbr["density"] - cell["density"][:, None],
                          0.0), axis=1)
    return {"density": cell["density"] + 0.01 * s}


def _swap_stall_total():
    from dccrg_tpu import telemetry

    tot = 0.0
    for (nm, _lab), h in telemetry.registry().histograms.items():
        if nm == "dccrg_recommit_stall_seconds":
            tot += h.sum_seconds
    return tot


def run_overlap_size(n, quantum=2):
    """One size's sync-vs-background stall comparison. Both modes run
    the identical adapt schedule and serve the identical total step
    count; the difference is WHERE the build cost lands."""
    n0 = int(np.uint64(n) ** 3)
    nref = n0 // 64

    def serve(bg):
        os.environ["DCCRG_BG_RECOMMIT"] = "1" if bg else "0"
        g = (dt.Grid(cell_data={"density": jnp.float32})
             .set_initial_length((n, n, n))
             .set_maximum_refinement_level(1)
             .set_neighborhood_length(1)
             .initialize())
        cells = g.plan.cells
        g.set("density", cells, np.arange(len(cells)) % 97.0)
        g.run_steps(_diffuse, ["density"], ["density"], quantum)  # warm

        def quantum_step():
            # block per quantum: a real serving loop consumes each
            # quantum's results, and unconsumed async dispatches would
            # otherwise pile up and bill their compute to whatever
            # blocks next (the swap), corrupting the stall accounting
            g.run_steps(_diffuse, ["density"], ["density"], quantum)
            jax.block_until_ready(g.data["density"])

        epochs = []

        def adapt_epoch(label, schedule):
            schedule()
            stall0 = _swap_stall_total()
            t0 = time.perf_counter()
            g.stop_refining()
            adapt_wall = time.perf_counter() - t0
            served = 0
            if bg:
                # the serving loop: keep stepping on the live plan;
                # run_steps installs the finished plan at a boundary
                while g.bg_pending():
                    quantum_step()
                    served += quantum
                stall = adapt_wall + (_swap_stall_total() - stall0)
            else:
                stall = adapt_wall
            # equal total service in both modes: the sync leg serves
            # its quanta after the commit instead of during it
            while served < 8 * quantum:
                quantum_step()
                served += quantum
            epochs.append({"epoch": label,
                           "stall_s": round(stall, 3),
                           "adapt_call_s": round(adapt_wall, 3),
                           "fp": _plan_fp(g)})

        def first():
            for c in g.plan.cells[:nref]:
                g.refine_completely(c)

        def second():
            cs = g.plan.cells
            lvl0 = cs[cs <= np.uint64(n0)]
            for c in lvl0[-nref:]:
                g.refine_completely(int(c))

        def third():
            cs = g.plan.cells
            lvl1 = cs[cs > np.uint64(n0)]
            for c in lvl1[:nref // 2:8]:
                g.unrefine_completely(int(c))

        adapt_epoch("first", first)
        adapt_epoch("steady-refine", second)
        adapt_epoch("steady-unrefine", third)
        del g
        return epochs

    sync = serve(bg=False)
    bg = serve(bg=True)
    os.environ.pop("DCCRG_BG_RECOMMIT", None)
    rows = []
    for s, b in zip(sync, bg):
        assert s["fp"] == b["fp"], (
            f"plan fingerprint diverged at {s['epoch']} — background "
            "builds must be bitwise identical to synchronous ones")
        row = {"size": f"{n}^3", "epoch": s["epoch"],
               "stall_sync_s": s["stall_s"], "stall_bg_s": b["stall_s"],
               "stall_ratio": round(s["stall_s"]
                                    / max(b["stall_s"], 1e-9), 2),
               "fp_match": True}
        rows.append(row)
        print(json.dumps(row), flush=True)
    # steady-state summary (trend.py keys): the LAST two epochs are
    # the warm adapt loop the ROADMAP item is about
    steady_sync = sum(r["stall_sync_s"] for r in rows[1:])
    steady_bg = sum(r["stall_bg_s"] for r in rows[1:])
    summary = {
        f"recommit{n}_stall_sync_seconds": round(steady_sync, 3),
        f"recommit{n}_stall_bg_seconds": round(steady_bg, 3),
    }
    print(json.dumps({"size": f"{n}^3", "overlap_summary": summary}),
          flush=True)
    return rows, summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max", type=int, default=128,
                    help="largest edge length (64/128/192)")
    ap.add_argument("--no-reuse", action="store_true",
                    help="clear the stream-reuse cache before every "
                         "commit (isolates the reuse win)")
    ap.add_argument("--overlap", action="store_true",
                    help="measure step-loop stall seconds sync vs "
                         "DCCRG_BG_RECOMMIT=1 (bitwise plan parity "
                         "asserted per epoch)")
    args = ap.parse_args()

    # a CPU-only host bench: probe the CPU backend in a killable
    # subprocess before any jax work
    from dccrg_tpu.resilience import safe_devices

    safe_devices(timeout=120, retries=1, platform="cpu")

    results = []
    for n in (64, 128, 192):
        if n > args.max:
            continue
        if args.overlap:
            results.append(run_overlap_size(n))
        else:
            results.extend(run_size(n, reuse=not args.no_reuse))
    return results


if __name__ == "__main__":
    main()
