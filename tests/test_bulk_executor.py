"""Roll-plan Pallas bulk executor parity suite (DCCRG_BULK=pallas).

Runs under Pallas TPU interpret mode on the CPU test mesh (the same
discipline as tests/test_pallas_kernel.py), on single-device grids —
the executor's eligibility domain. Pins:

- roll-executor vs XLA roll path: fixup rows BITWISE after one pass
  (the fused scatter epilogue re-runs the reference slot loop with
  exact gathered neighbors), everything to L2/allclose tolerance over
  multi-step runs — across periodic/non-periodic boundaries,
  multi-field kernels and steps_per_pass in {1, 4};
- the negative pin: DCCRG_BULK unset (or =xla) compiles the
  pre-executor XLA program — the bulk path never enters the program
  cache;
- bf16 end-to-end state (Grid(dtype=)): allocate/step/checkpoint
  round-trip/digest dtype pinning/device fingerprints;
- fleet: dtype is part of the bucket key, and a bucket whose kernel
  has a registered bulk twin steps through the batched executor.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dccrg_tpu.grid import DEFAULT_NEIGHBORHOOD_ID, Grid, default_mesh

pytestmark = pytest.mark.pallas


def one_dev_mesh():
    return default_mesh(jax.devices()[:1])


def fixup_rows(grid):
    """All rows whose flat roll is wrong for some slot (the executor's
    scatter-epilogue target set)."""
    hood = grid.plan.hoods[DEFAULT_NEIGHBORHOOD_ID]
    roll = hood.roll_plan(grid.plan.L)
    wr = np.asarray(roll[1])
    return np.unique(wr[wr < grid.plan.L])


def make_diffuse_grid(periodic, mesh=None, dtype=jnp.float32):
    from dccrg_tpu.fleet import seeded_random_init

    g = (Grid(cell_data={"rho": jnp.float32}, dtype=dtype)
         .set_initial_length((16, 16, 16))
         .set_periodic(*periodic)
         .set_maximum_refinement_level(0)
         .set_neighborhood_length(0)
         .initialize(mesh if mesh is not None else one_dev_mesh()))
    seeded_random_init(g, 7)
    g.update_copies_of_remote_neighbors()
    return g


def diffuse_slotwise():
    from dccrg_tpu.fleet import FLEET_BULK_KERNELS

    return FLEET_BULK_KERNELS["diffuse"]


@pytest.mark.parametrize("periodic", [(True, True, True),
                                      (False, False, False)])
@pytest.mark.parametrize("spp", [1, 4])
def test_bulk_matches_xla_roll_path(periodic, spp, monkeypatch):
    """One pass: fixup rows bitwise vs the XLA roll path; multi-step
    (including a remainder pass shorter than steps_per_pass): allclose
    everywhere."""
    kern = diffuse_slotwise()
    dt = jnp.float32(0.05)

    def run(n_steps, bulk):
        if bulk:
            monkeypatch.setenv("DCCRG_BULK", "pallas")
            monkeypatch.setenv("DCCRG_BULK_SPP", str(spp))
        else:
            monkeypatch.delenv("DCCRG_BULK", raising=False)
        g = make_diffuse_grid(periodic)
        g.run_steps(kern, ["rho"], ["rho"], n_steps, extra_args=(dt,))
        return g, np.asarray(g.data["rho"][0][:g.plan.L])

    g_x, rho_x = run(spp, bulk=False)
    g_p, rho_p = run(spp, bulk=True)
    assert any(k[0] == "bulksteploop" for k in g_p._program_cache)
    W = fixup_rows(g_x)
    n0 = 16 ** 3
    if len(W):
        np.testing.assert_array_equal(rho_x[W], rho_p[W])
    np.testing.assert_allclose(rho_p[:n0], rho_x[:n0],
                               rtol=1e-6, atol=1e-6)

    _, rho_x6 = run(spp + 2, bulk=False)  # spp=4: exercises remainder
    _, rho_p6 = run(spp + 2, bulk=True)
    np.testing.assert_allclose(rho_p6[:n0], rho_x6[:n0],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("spp", [1, 4])
def test_bulk_multi_field_advection(spp, monkeypatch):
    """The north-star workload (3 fields in, 1 out, periodic x/y +
    non-periodic z) through the bulk executor: fixup rows bitwise
    after one pass, L2 parity over a longer run."""
    from dccrg_tpu.models.advection import GridAdvection

    def run(n_steps, bulk):
        if bulk:
            monkeypatch.setenv("DCCRG_BULK", "pallas")
            monkeypatch.setenv("DCCRG_BULK_SPP", str(spp))
        else:
            monkeypatch.delenv("DCCRG_BULK", raising=False)
        s = GridAdvection(n=16, nz=16, mesh=one_dev_mesh())
        dt = 0.5 * s.max_time_step()
        s.run(n_steps, dt)
        return s, np.asarray(s.grid.data["density"][0][:s.grid.plan.L])

    s_x, rho_x = run(spp, bulk=False)
    s_p, rho_p = run(spp, bulk=True)
    W = fixup_rows(s_x.grid)
    assert len(W)  # periodic wraps exist on this configuration
    if spp == 1:
        np.testing.assert_array_equal(rho_x[W], rho_p[W])
    else:
        # the deep pass's epilogue cascade recomputes DILATED sets;
        # XLA CPU contracts mul+add to FMA differently between the
        # full-array and gathered-subset programs for this
        # cancellation-heavy flux, so a few sensitive rows drift by
        # 1 ulp at intermediate sub-steps. The repair itself stays
        # exact: the overwhelming majority of fixup rows are bitwise
        # and the rest are a single float32 ulp off.
        exact = np.count_nonzero(rho_x[W] == rho_p[W]) / len(W)
        assert exact > 0.9, exact
        np.testing.assert_allclose(rho_p[W], rho_x[W],
                                   rtol=2e-6, atol=1e-9)
    n0 = 16 ** 3
    np.testing.assert_allclose(rho_p[:n0], rho_x[:n0],
                               rtol=1e-6, atol=1e-6)

    s_x2, _ = run(6, bulk=False)
    s_p2, _ = run(6, bulk=True)
    assert abs(s_p2.l2_error() - s_x2.l2_error()) < 1e-4


def test_bulk_negative_pin(monkeypatch):
    """DCCRG_BULK unset (and =xla) compiles the pre-executor XLA
    program: the bulk path never enters the program cache — the same
    discipline as DCCRG_INTEGRITY=0."""
    kern = diffuse_slotwise()
    dt = jnp.float32(0.05)
    for mode in (None, "xla"):
        if mode is None:
            monkeypatch.delenv("DCCRG_BULK", raising=False)
        else:
            monkeypatch.setenv("DCCRG_BULK", mode)
        g = make_diffuse_grid((True, True, True))
        g.run_steps(kern, ["rho"], ["rho"], 2, extra_args=(dt,))
        kinds = {k[0] for k in g._program_cache}
        assert "steploop" in kinds and "bulksteploop" not in kinds
    monkeypatch.setenv("DCCRG_BULK", "pallas")
    g = make_diffuse_grid((True, True, True))
    g.run_steps(kern, ["rho"], ["rho"], 2, extra_args=(dt,))
    kinds = {k[0] for k in g._program_cache}
    assert "bulksteploop" in kinds and "steploop" not in kinds


def test_bulk_ineligible_falls_back(monkeypatch):
    """DCCRG_BULK=pallas on an ineligible configuration (multi-device
    mesh) silently falls back to the XLA roll path."""
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    kern = diffuse_slotwise()
    monkeypatch.setenv("DCCRG_BULK", "pallas")
    g = make_diffuse_grid((True, True, True),
                          mesh=default_mesh(jax.devices()[:2]))
    g.run_steps(kern, ["rho"], ["rho"], 2,
                extra_args=(jnp.float32(0.05),))
    kinds = {k[0] for k in g._program_cache}
    assert "steploop" in kinds and "bulksteploop" not in kinds


def test_grid_dtype_bf16_end_to_end(tmp_path, monkeypatch):
    """Grid(dtype=bfloat16): allocate/step/checkpoint/digest/
    fingerprint all stay narrow. Also pins that the executor handles
    bf16 state (the flux arithmetic widens to f32 in-kernel)."""
    from dccrg_tpu import checkpoint as ckpt
    from dccrg_tpu import integrity, resilience
    from dccrg_tpu.models.advection import GridAdvection

    s = GridAdvection(n=16, nz=16, mesh=one_dev_mesh(),
                      dtype=jnp.bfloat16)
    g = s.grid
    assert g.state_dtype == jnp.bfloat16
    for name in ("density", "vx", "vy"):
        assert g.fields[name][1] == jnp.bfloat16
        assert g.data[name].dtype == jnp.bfloat16
    s.run(3, 0.5 * s.max_time_step())
    assert g.data["density"].dtype == jnp.bfloat16

    # digest is dtype-pinned: an f32 grid with the same physics can
    # never alias a bf16 digest
    d16 = ckpt.state_digest(g)
    s32 = GridAdvection(n=16, nz=16, mesh=one_dev_mesh())
    assert ckpt.state_digest(s32.grid) != d16

    # checkpoint round-trip preserves dtype and bytes
    path = str(tmp_path / "bf16.dcc")
    resilience.save_checkpoint(g, path)
    g2 = s.__class__(n=16, nz=16, mesh=one_dev_mesh(),
                     dtype=jnp.bfloat16).grid
    resilience.load_checkpoint_into(g2, path)
    assert g2.data["density"].dtype == jnp.bfloat16
    assert ckpt.state_digest(g2) == d16

    # device fingerprints widen 16-bit state losslessly
    fp = integrity.device_fingerprint(g.data["density"][0],
                                      int(g.plan.n_local[0]))
    assert np.asarray(fp).shape == (2,)

    # and the bulk executor accepts bf16 state
    monkeypatch.setenv("DCCRG_BULK", "pallas")
    sp = GridAdvection(n=16, nz=16, mesh=one_dev_mesh(),
                       dtype=jnp.bfloat16)
    sp.run(3, 0.5 * sp.max_time_step())
    assert sp.grid.data["density"].dtype == jnp.bfloat16
    ref = np.asarray(s.grid.data["density"][0], dtype=np.float32)
    got = np.asarray(sp.grid.data["density"][0], dtype=np.float32)
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.02)


def test_fleet_bucket_key_dtype():
    """A bf16 job can never share a compiled program with a float32
    bucket: dtype is part of the bucket key."""
    from dccrg_tpu.fleet import FleetJob

    a = FleetJob("a", length=(16, 16, 16), kernel="diffuse")
    b = FleetJob("b", length=(16, 16, 16), kernel="diffuse",
                 cell_data={"rho": jnp.bfloat16})
    assert a.bucket_key() != b.bucket_key()
    c = FleetJob("c", length=(16, 16, 16), kernel="diffuse")
    assert a.bucket_key() == c.bucket_key()


def test_fleet_bulk_bucket_matches_table_path(monkeypatch):
    """A GridBatch bucket selects the batched bulk executor through
    the fleet bulk-kernel registry under DCCRG_BULK=pallas, and its
    slots match the table-gather program to float re-association."""
    from dccrg_tpu.fleet import FleetJob, GridBatch

    def run(bulk):
        if bulk:
            monkeypatch.setenv("DCCRG_BULK", "pallas")
        else:
            monkeypatch.delenv("DCCRG_BULK", raising=False)
        jobs = [FleetJob(f"j{i}", length=(16, 16, 16), kernel="diffuse",
                         n_steps=4, params=(0.03 + 0.01 * i,), seed=i)
                for i in range(2)]
        batch = GridBatch(jobs[0], capacity=2)
        for j in jobs:
            j.apply_init(batch.grid)
            batch.admit(j)
        batch.step(np.array([4, 4], dtype=np.int32))
        # the solo-path shadow audit keys off this flag: bulk
        # arithmetic is not bitwise-comparable across programs
        assert batch.bulk_active() is bulk
        return [batch.extract(i) for i in range(2)]

    table = run(bulk=False)
    bulk = run(bulk=True)
    for st, sb in zip(table, bulk):
        for name in st:
            np.testing.assert_allclose(
                np.asarray(sb[name], dtype=np.float64),
                np.asarray(st[name], dtype=np.float64),
                rtol=1e-5, atol=1e-6)

