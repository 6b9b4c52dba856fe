"""SlotwiseKernel (slot-at-a-time stencils) must match the dense
kernel contract.

The slot-wise protocol exists so the bulk pass never materializes the
[L, S] neighbor stack / [L, S, 3] offsets — at 512^3 those are
multi-GB HBM temps that OOM a single chip (the round-5 chip session's
finding).  Equivalence is checked with integer-valued float32 fields:
every sum is exact, so slot-order reassociation cannot hide a wrong
gather, mask, or offset.

Reference behavior being reproduced: dccrg's solver loop reads each
neighbor's data through the cached neighbor lists one neighbor at a
time (dccrg.hpp:5046-5413) — slot-wise is the same access pattern,
table-driven, inside one XLA program.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dccrg_tpu.grid import DEFAULT_NEIGHBORHOOD_ID, Grid, SlotwiseKernel


def _mk(monkeypatch, *, roll, refine=False, overlap=False,
        length=(8, 8, 40), periodic=(True, True, False)):
    monkeypatch.setenv("DCCRG_ROLL_STENCIL", "1" if roll else "0")
    monkeypatch.setenv("DCCRG_OVERLAP", "1" if overlap else "0")
    g = (
        Grid(cell_data={"v": jnp.float32, "w": jnp.float32})
        .set_initial_length(length)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(2 if refine else 0)
        .set_neighborhood_length(1)
        .initialize(partition="block")
    )
    if refine:
        for cid in g.local_cells().ids[:6:2]:
            g.refine_completely(int(cid))
        g.stop_refining()
    cells = g.plan.cells
    rng = np.random.default_rng(11)
    g.set("v", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    g.set("w", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    g.update_copies_of_remote_neighbors()
    return g


def _dense_kern(cell, nbr, offs, mask, *extra):
    # weights depend on the offset so a mixed-up slot <-> offset
    # pairing changes the result
    wgt = jnp.where(mask & (offs[..., 0] == 1), 2.0,
                    jnp.where(mask, 1.0, 0.0))
    s = jnp.sum(wgt * jnp.where(mask, nbr["v"], 0.0), axis=1)
    return {"v": cell["v"] + s + jnp.sum(
        jnp.where(mask, nbr["w"], 0.0), axis=1)}


def _slot_kern():
    def init(cell, *extra):
        return jnp.zeros(cell["v"].shape, jnp.float32)

    def slot(acc, cell, nbr, offs, mask, *extra):
        wgt = jnp.where(mask & (offs[..., 0] == 1), 2.0,
                        jnp.where(mask, 1.0, 0.0))
        return acc + wgt * jnp.where(mask, nbr["v"], 0.0) + jnp.where(
            mask, nbr["w"], 0.0)

    def finish(acc, cell, *extra):
        return {"v": cell["v"] + acc}

    return SlotwiseKernel(init, slot, finish)


@pytest.mark.parametrize("roll", [False, True])
@pytest.mark.parametrize("refine", [False, True])
def test_apply_stencil_matches_dense(monkeypatch, roll, refine):
    """Slot-wise apply_stencil == dense apply_stencil, bitwise (integer
    fields), on both gather modes and with the AMR split (hard-rows)
    pass."""
    g = _mk(monkeypatch, roll=roll, refine=refine)
    cells = g.plan.cells
    v0 = g.get("v", cells).copy()
    g.apply_stencil(_dense_kern, ["v", "w"], ["v"])
    want = g.get("v", cells).copy()

    g.set("v", cells, v0)
    g.update_copies_of_remote_neighbors()
    g.apply_stencil(_slot_kern(), ["v", "w"], ["v"])
    np.testing.assert_array_equal(g.get("v", cells), want)


@pytest.mark.parametrize("roll", [False, True])
@pytest.mark.parametrize("overlap", [False, True])
def test_run_steps_matches_dense(monkeypatch, roll, overlap):
    """Slot-wise fused step loop == dense fused step loop, bitwise,
    with and without the overlapped (inner/outer) execution."""
    g = _mk(monkeypatch, roll=roll, overlap=overlap)
    cells = g.plan.cells
    v0 = g.get("v", cells).copy()
    g.run_steps(_dense_kern, ["v", "w"], ["v"], 2)
    want = g.get("v", cells).copy()
    assert np.all(np.isfinite(want)) and want.max() < 2 ** 24

    g.set("v", cells, v0)
    g.update_copies_of_remote_neighbors()
    g.run_steps(_slot_kern(), ["v", "w"], ["v"], 2)
    np.testing.assert_array_equal(g.get("v", cells), want)


def test_advection_kernel_is_slotwise_and_matches_dense_math():
    """The headline GridAdvection kernel ships as a SlotwiseKernel and
    its dense __call__ adapter reproduces the pre-slotwise dense
    upwind-flux arithmetic exactly."""
    from dccrg_tpu.models.advection import make_uniform_flux_kernel

    kern = make_uniform_flux_kernel((0.25, 0.25, 1.0))
    assert isinstance(kern, SlotwiseKernel)

    rng = np.random.default_rng(3)
    L, S = 64, 6
    cell = {n: jnp.asarray(rng.random(L, dtype=np.float32))
            for n in ("density", "vx", "vy")}
    nbr = {n: jnp.asarray(rng.random((L, S), dtype=np.float32))
           for n in ("density", "vx", "vy")}
    offs = np.zeros((L, S, 3), np.int32)
    offs[:, 0, 0], offs[:, 1, 0] = 1, -1
    offs[:, 2, 1], offs[:, 3, 1] = 1, -1
    offs[:, 4, 2], offs[:, 5, 2] = 1, -1
    mask = np.ones((L, S), bool)
    mask[:, 5] = False
    dt = jnp.float32(0.01)

    got = kern(cell, nbr, jnp.asarray(offs), jnp.asarray(mask), dt)

    # the pre-slotwise dense reference (same math, [L, S] layout)
    f32 = jnp.float32
    inv = [4.0, 4.0, 1.0]
    rho_c = cell["density"][:, None]
    rho_n = nbr["density"]
    acc = jnp.zeros_like(rho_n)
    m_ = jnp.asarray(mask)
    o_ = jnp.asarray(offs)
    for d, vname in ((0, "vx"), (1, "vy")):
        v = 0.5 * (cell[vname][:, None] + nbr[vname])
        up_pos = jnp.where(v >= 0, rho_c, rho_n)
        up_neg = jnp.where(v >= 0, rho_n, rho_c)
        face_pos = m_ & (o_[..., d] == 1)
        face_neg = m_ & (o_[..., d] == -1)
        mm = v * (dt * f32(inv[d]))
        acc = acc - jnp.where(face_pos, up_pos * mm, 0.0)
        acc = acc + jnp.where(face_neg, up_neg * mm, 0.0)
    want = cell["density"] + jnp.sum(acc, axis=1)
    np.testing.assert_allclose(np.asarray(got["density"]),
                               np.asarray(want), rtol=2e-6, atol=2e-7)


def test_grid_advection_physics_on_slotwise_path():
    """End-to-end: the (now slot-wise) GridAdvection still advects —
    mass is conserved and the hump moves (l2 error stays finite and
    small) on a small periodic grid."""
    from dccrg_tpu.models.advection import GridAdvection

    adv = GridAdvection(n=24, nz=1)
    rho0 = adv.density().sum()
    for _ in range(8):
        adv.run(4)
    rho1 = adv.density().sum()
    np.testing.assert_allclose(rho0, rho1, rtol=1e-4)
    assert adv.l2_error() < 0.2


def test_single_device_closed_form_roll3d_matches_dense(monkeypatch):
    """On a single-device closed-form plan the slot gather lowers to
    exact 3-D rolls (no fixup scatter); results must stay bitwise equal
    to the dense kernel across periodic and walled dimensions."""
    import jax

    monkeypatch.setenv("DCCRG_ROLL_STENCIL", "1")
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("dev",))
    g = (
        Grid(cell_data={"v": jnp.float32, "w": jnp.float32})
        .set_initial_length((6, 5, 4))
        .set_periodic(True, False, True)
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(1)
        .initialize(mesh, partition="block")
    )
    hood = g.plan.hoods[DEFAULT_NEIGHBORHOOD_ID]
    assert hood.closed_form is not None and not hood.closed_form.get(
        "multi")
    cells = g.plan.cells
    rng = np.random.default_rng(5)
    g.set("v", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    g.set("w", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    v0 = g.get("v", cells).copy()
    g.apply_stencil(_dense_kern, ["v", "w"], ["v"])
    want = g.get("v", cells).copy()
    g.run_steps(_dense_kern, ["v", "w"], ["v"], 2)
    want2 = g.get("v", cells).copy()

    g.set("v", cells, v0)
    g.apply_stencil(_slot_kern(), ["v", "w"], ["v"])
    np.testing.assert_array_equal(g.get("v", cells), want)
    g.run_steps(_slot_kern(), ["v", "w"], ["v"], 2)
    np.testing.assert_array_equal(g.get("v", cells), want2)


def test_slotwise_include_to_raises(monkeypatch):
    g = _mk(monkeypatch, roll=False)
    with pytest.raises(ValueError, match="include_to"):
        g.apply_stencil(_slot_kern(), ["v", "w"], ["v"], include_to=True)


def _mesh_grid(n_dev, length, periodic, payload, reach=1):
    import jax
    from jax.sharding import Mesh

    cell_data = {"v": jnp.float32, "w": jnp.float32}
    if payload:
        cell_data["p"] = ((2,), jnp.float32)
    g = (
        Grid(cell_data=cell_data)
        .set_initial_length(length)
        .set_periodic(*periodic)
        .set_maximum_refinement_level(0)
        .set_neighborhood_length(reach)
        .initialize(Mesh(np.array(jax.devices()[:n_dev]), ("dev",)),
                    partition="block")
    )
    cells = g.plan.cells
    rng = np.random.default_rng(7)
    g.set("v", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    g.set("w", cells, rng.integers(0, 64, len(cells)).astype(np.float32))
    if payload:
        g.set("p", cells,
              rng.integers(0, 64, (len(cells), 2)).astype(np.float32))
    g.update_copies_of_remote_neighbors()
    return g


def _xyz_slot_kern(payload, reach=1):
    """Integer-valued and bounded (mod 1021): each of the
    ``(2 reach + 1)^3 - 1`` slots gets its own weight from its x, y AND
    z offset, so a neighbor taken from the wrong plane, row or slot
    changes the result exactly (sums stay below 2^24: exact in f32)."""
    b = 2 * reach + 1

    def init(cell, *extra):
        acc = {"v": jnp.zeros(cell["v"].shape, jnp.float32)}
        if payload:
            acc["p"] = jnp.zeros(cell["p"].shape, jnp.float32)
        return acc

    def slot(acc, cell, nbr, offs, mask, *extra):
        wgt = (1 + (offs[..., 0] + reach) + b * (offs[..., 1] + reach)
               + b * b * (offs[..., 2] + reach)).astype(jnp.float32)
        acc = dict(acc)
        acc["v"] = acc["v"] + jnp.where(mask, wgt * nbr["v"] + nbr["w"], 0.0)
        if payload:
            acc["p"] = acc["p"] + jnp.where(
                mask[:, None], wgt[..., None] * nbr["p"], 0.0)
        return acc

    def finish(acc, cell, *extra):
        out = {"v": jnp.mod(cell["v"] + acc["v"], 1021.0)}
        if payload:
            out["p"] = jnp.mod(cell["p"] + acc["p"], 1021.0)
        return out

    return SlotwiseKernel(init, slot, finish)


def _stepped(monkeypatch, n_dev, length, periodic, overlap, payload, op,
             reach=1):
    """Step the grid; returns its stepped fields, the slot gathers whose
    ``dccrg_slot_gather_programs_total`` rose, and the outer re-pass
    gathers whose ``dccrg_repass_programs_total`` rose."""
    from dccrg_tpu import telemetry

    monkeypatch.setenv("DCCRG_OVERLAP", "1" if overlap else "0")
    g = _mesh_grid(n_dev, length, periodic, payload, reach)
    ins = ("v", "w") + (("p",) if payload else ())
    outs = ("v",) + (("p",) if payload else ())
    kern = _xyz_slot_kern(payload, reach)
    reg = telemetry.registry()
    counters = (("dccrg_slot_gather_programs_total",
                 ("roll3d", "slab3d", "roll_fixup", "table")),
                ("dccrg_repass_programs_total", ("slab3d", "table")))
    counts = {(c, k): reg.counter_value(c, gather=k)
              for c, ks in counters for k in ks}
    if op == "run_steps":
        g.run_steps(kern, ins, outs, 3)
    else:
        for _ in range(2):
            g.update_copies_of_remote_neighbors()
            g.apply_stencil(kern, ins, outs)
    rose = [(c, k) for (c, k), n in counts.items()
            if reg.counter_value(c, gather=k) > n]
    built = {k for c, k in rose if c == counters[0][0]}
    repassed = {k for c, k in rose if c == counters[1][0]}
    if op == "run_steps" and n_dev > 1:
        assert g.last_overlap["mode"] == ("full" if overlap else "off")
        assert g.last_overlap["repass_gather"] == (
            next(iter(repassed)) if overlap else None)
    cells = g.plan.cells
    return {n: g.get(n, cells) for n in outs}, built, repassed


@pytest.mark.parametrize("n_dev, periodic_z, overlap, payload, op, reach", [
    (2, True, False, False, "run_steps", 1),
    (2, False, True, False, "run_steps", 1),
    (2, True, True, True, "run_steps", 1),
    (4, True, True, False, "run_steps", 1),
    (4, False, False, True, "run_steps", 1),
    (4, False, True, True, "run_steps", 1),
    (2, False, False, True, "apply_stencil", 1),
    (4, True, False, False, "apply_stencil", 1),
    (2, True, True, False, "run_steps", 2),
    (4, True, True, True, "run_steps", 2),
    (4, False, True, False, "run_steps", 2),
])
def test_slab_gather_matches_one_device(monkeypatch, n_dev, periodic_z,
                                        overlap, payload, op, reach):
    """A multi-device closed-form plan whose slabs are whole z planes
    takes the slab gather (rolls and copies of whole planes, in-plane
    rolls) and steps bitwise like the same grid on one device, with z
    periodic or walled, overlap on or off, and a [L, W] payload field.
    With the overlap on, the outer re-pass recomputes whole planes too.
    The 5 x 3 planes make L a bucket above the local rows, so pad rows
    exist. Reach 2 (z offsets +-1, +-2) makes two outer planes per slab
    side, over 32 planes so that every device keeps inner planes."""
    length, periodic = (5, 3, 16 * reach), (True, False, periodic_z)
    want, _, _ = _stepped(monkeypatch, 1, length, periodic, overlap, payload,
                          op, reach)
    got, built, repassed = _stepped(monkeypatch, n_dev, length, periodic,
                                    overlap, payload, op, reach)
    assert built == {"slab3d"}
    assert repassed == ({"slab3d"} if overlap else set())
    for n in want:
        np.testing.assert_array_equal(got[n], want[n])


@pytest.mark.parametrize("n_dev, length, overlap", [
    (8, (8, 6, 4), False),  # half-plane slabs (all rows outer)
    (3, (5, 3, 16), True),  # 16 planes over 3 devices: unequal slabs
])
def test_slab_gather_falls_back_off_plane_slabs(monkeypatch, n_dev, length,
                                                overlap):
    """Slabs that are not whole, equal z planes keep the flat roll with
    its fixup scatter and the element-gather outer re-pass, and still
    step bitwise like one device."""
    periodic = (True, False, True)
    want, _, _ = _stepped(monkeypatch, 1, length, periodic, overlap, True,
                          "run_steps")
    got, built, repassed = _stepped(monkeypatch, n_dev, length, periodic,
                                    overlap, True, "run_steps")
    assert built == {"roll_fixup"}
    assert repassed == ({"table"} if overlap else set())
    for n in want:
        np.testing.assert_array_equal(got[n], want[n])
