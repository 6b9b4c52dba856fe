"""The multi-device fused step must step like one device.

On several devices each iteration of compile_step_loop's program
exchanges the halo first (``dccrg.exchange``), then runs the bulk pass
over every local row (``dccrg.bulk``) and writes the results
(``dccrg.apply``). Every case here steps the same structure on the
8-device mesh (or 5 devices) and compares the cells with the same grid
on one device, or with ``update_copies_of_remote_neighbors`` +
``apply_stencil`` where a transfer predicate makes the ghosts differ
from the one-device neighbors by design.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dccrg_tpu.grid import Grid


def _mk(monkeypatch, n_dev, *, partition="block", force_tables=False,
        refine=False, periodic=(True, True, False), dims=(8, 8, 40),
        max_level=2, seed=7, refine_ids=(1, 3, 5)):
    """8x8x40 over ``n_dev`` devices (None: all of them): block slabs
    5 cells thick, so each device has inner and outer rows."""
    if force_tables:
        monkeypatch.setenv("DCCRG_FORCE_TABLES", "1")
    else:
        monkeypatch.delenv("DCCRG_FORCE_TABLES", raising=False)
    devices = jax.devices() if n_dev is None else jax.devices()[:n_dev]
    g = (
        Grid(cell_data={"v": jnp.float32, "w": jnp.float32})
        .set_initial_length(dims)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(max_level if refine else 0)
        .set_neighborhood_length(1)
        .initialize(Mesh(np.array(devices), ("dev",)), partition=partition)
    )
    if refine:
        for cid in refine_ids:
            g.refine_completely(int(cid))
        g.stop_refining()
    cells = g.plan.cells
    rng = np.random.default_rng(seed)
    g.set("v", cells, rng.random(len(cells)).astype(np.float32))
    g.set("w", cells, rng.random(len(cells)).astype(np.float32))
    g.update_copies_of_remote_neighbors()
    return g


def _kern(cell, nbr, offs, mask):
    s = jnp.sum(jnp.where(mask, nbr["v"], 0.0), axis=1)
    return {"v": 0.5 * cell["v"] + 0.125 * s}


def _kern2(cell, nbr, offs, mask):
    # non-power-of-two coefficients, two cross-coupled fields
    sv = jnp.sum(jnp.where(mask, nbr["v"], 0.0), axis=1)
    sw = jnp.sum(jnp.where(mask, nbr["w"], 0.0), axis=1)
    return {"v": 0.5 * cell["v"] + 0.125 * sw,
            "w": 0.9 * cell["w"] + 0.05 * sv}


def _stepped(monkeypatch, n_dev, steps, kern=_kern, ins=("v",),
             outs=("v",), **kw):
    g = _mk(monkeypatch, n_dev, **kw)
    g.run_steps(kern, list(ins), list(outs), steps)
    assert len(g.plan.cells) == len(np.unique(g.plan.cells))
    return g, {n: g.get(n, g.plan.cells) for n in outs}


def _assert_same(got, want):
    for n in want:
        np.testing.assert_array_equal(got[n], want[n])


@pytest.mark.parametrize("partition", ["block", "morton", "rcb"])
def test_mesh_step_matches_one_device(monkeypatch, partition):
    g, got = _stepped(monkeypatch, None, 5, partition=partition)
    assert g.n_dev == 8
    _, want = _stepped(monkeypatch, 1, 5, partition=partition)
    _assert_same(got, want)


def test_mesh_step_matches_with_tables(monkeypatch):
    """DCCRG_FORCE_TABLES: the table-driven gather and exchange."""
    _, got = _stepped(monkeypatch, None, 5, force_tables=True)
    _, want = _stepped(monkeypatch, 1, 5, force_tables=True)
    _assert_same(got, want)


def test_mesh_step_on_refined_grid(monkeypatch):
    """Hybrid (split-table) plans: the hard rows read landed ghosts."""
    _, got = _stepped(monkeypatch, None, 4, refine=True)
    _, want = _stepped(monkeypatch, 1, 4, refine=True)
    _assert_same(got, want)


def test_mesh_step_multi_field_exchange(monkeypatch):
    """Two exchanged fields, cross-coupled kernel."""
    kw = dict(kern=_kern2, ins=("v", "w"), outs=("v", "w"))
    _, got = _stepped(monkeypatch, None, 4, **kw)
    _, want = _stepped(monkeypatch, 1, 4, **kw)
    _assert_same(got, want)


def test_mesh_step_static_field(monkeypatch):
    """A static (non-exchanged) input field keeps its epoch ghosts."""
    def kern(cell, nbr, offs, mask):
        sw = jnp.sum(jnp.where(mask, nbr["w"], 0.0), axis=1)
        return {"v": cell["v"] + 0.015625 * sw * cell["w"]}

    kw = dict(kern=kern, ins=("v", "w"), outs=("v",))
    _, got = _stepped(monkeypatch, None, 3, **kw)
    _, want = _stepped(monkeypatch, 1, 3, **kw)
    _assert_same(got, want)


def test_mesh_step_odd_device_count(monkeypatch):
    """5 devices over 40 z-planes: uneven slabs, per-device widths
    differ."""
    g, got = _stepped(monkeypatch, 5, 4, seed=11)
    assert g.n_dev == 5
    _, want = _stepped(monkeypatch, 1, 4, seed=11)
    _assert_same(got, want)


def test_mesh_step_nonperiodic(monkeypatch):
    kw = dict(periodic=(False, False, False))
    _, got = _stepped(monkeypatch, None, 5, **kw)
    _, want = _stepped(monkeypatch, 1, 5, **kw)
    _assert_same(got, want)


def test_mesh_step_honors_transfer_predicates(monkeypatch):
    """Predicate-filtered per-field pair tables feed the fused step's
    exchange exactly as update_copies_of_remote_neighbors': blocked
    cells keep stale ghosts, so the reference is the same mesh's
    exchange + apply_stencil, step by step."""
    def blocked(g):
        # block transfers of cells whose id is 0 mod 3
        g.set_transfer_predicate(
            "v", lambda ids, s, r, h: (ids % np.uint64(3)) != 0)
        g.update_copies_of_remote_neighbors()
        return g

    g = blocked(_mk(monkeypatch, None))
    g.run_steps(_kern, ["v"], ["v"], 3)
    got = g.get("v", g.plan.cells)
    ref = blocked(_mk(monkeypatch, None))
    for _ in range(3):
        ref.update_copies_of_remote_neighbors()
        ref.apply_stencil(_kern, ["v"], ["v"])
    np.testing.assert_array_equal(got, ref.get("v", ref.plan.cells))
    # the predicate did block some ghosts: one device reads otherwise
    _, one = _stepped(monkeypatch, 1, 3)
    assert not np.array_equal(got, one["v"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mesh_step_fuzz_random_structures(monkeypatch, seed):
    """Random dims / partition / refinement / steps: the mesh and one
    device agree bitwise (power-of-two kernel)."""
    rng = np.random.default_rng(100 + seed)
    dims = (int(rng.choice([4, 8])), int(rng.choice([4, 8])),
            int(rng.choice([24, 40])))
    part = str(rng.choice(["block", "morton", "rcb"]))
    per = bool(rng.integers(0, 2))
    refine = bool(rng.integers(0, 2))
    steps = int(rng.integers(2, 6))
    n_cells = dims[0] * dims[1] * dims[2]
    refine_ids = tuple(int(c) for c in rng.integers(1, n_cells + 1, 3))
    kw = dict(dims=dims, partition=part, periodic=(per, per, False),
              refine=refine, max_level=1, refine_ids=refine_ids,
              seed=1000 + seed)
    _, got = _stepped(monkeypatch, None, steps, **kw)
    _, want = _stepped(monkeypatch, 1, steps, **kw)
    _assert_same(got, want)


def test_mesh_step_survives_balance(monkeypatch):
    """A partition change rebuilds the exchange tables per epoch."""
    g = _mk(monkeypatch, None)
    g.run_steps(_kern, ["v"], ["v"], 2)
    g.set_partitioning_option("method", "morton")
    g.balance_load()
    g.update_copies_of_remote_neighbors()
    g.run_steps(_kern, ["v"], ["v"], 2)
    _, want = _stepped(monkeypatch, 1, 4)
    np.testing.assert_array_equal(g.get("v", g.plan.cells), want["v"])
