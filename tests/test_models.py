"""Model zoo: multi-field MHD + wide-payload Vlasov workloads, their
multi-device steps, and mixed-kernel fleet serving.

The acceptance pins of the zoo contract:

- both new models ride ``Grid.run_steps``, ``ResilientRunner``
  (rollback reconverges bitwise), per-job fleet checkpoints and the
  fuzz oracle with NO changes to those layers' public APIs;
- on a mesh each model (and the Poisson fused solve) matches one
  device, while its steps exchange only a proper field subset;
- jobs across >= 3 distinct kernels serve concurrently under one
  scheduler + SLO policy with per-slot fault isolation pinned
  bitwise vs solo runs, and a deadline job can shed a best-effort
  cohabitant from ANOTHER bucket on its lane (parked, resumed
  bitwise).
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from dccrg_tpu import faults, integrity, telemetry
from dccrg_tpu.fleet import (FLEET_KERNELS, FleetJob, _jobs_from_spec,
                             run_solo)
from dccrg_tpu.fuzz import GridFuzzer
from dccrg_tpu.models import available_models
from dccrg_tpu.models.mhd import (GridMHD, MHD_ALL, MHD_BFIELD,
                                  MHD_HYDRO, make_mhd_pass_kernels)
from dccrg_tpu.models.vlasov import (VLASOV_EXCHANGE, VLASOV_FIELDS,
                                     GridVlasov)
from dccrg_tpu.resilience import ResilientRunner
from dccrg_tpu.scheduler import FleetScheduler, SLOPolicy

pytestmark = pytest.mark.models


# -- the registry surface ---------------------------------------------

def test_zoo_registry_surface():
    zoo = {m["name"]: m for m in available_models()}
    assert {"mhd", "vlasov", "diffuse", "advect_x"} <= set(zoo)
    assert set(zoo["mhd"]["fields"]) == set(MHD_ALL)
    assert set(zoo["vlasov"]["conserved"]) == {"rho"}
    # registration happened on import: the fleet can name both
    assert "mhd" in FLEET_KERNELS and "vlasov" in FLEET_KERNELS
    assert integrity.conserved_fields(
        "mhd", (True, True, True), MHD_ALL) == MHD_ALL


def test_fleet_job_zoo_defaults():
    """A bare FleetJob naming a zoo kernel inherits its schema,
    field lists and default params from the registered spec."""
    j = FleetJob("z1", kernel="vlasov", length=(6, 6, 6), n_steps=4)
    assert set(j.cell_data) == set(VLASOV_FIELDS)
    assert j.cell_data["f"][0] != ()  # the wide payload
    assert j.fields_out == VLASOV_FIELDS
    j2 = FleetJob("z2", kernel="mhd", length=(6, 6, 6), n_steps=4)
    assert set(j2.cell_data) == set(MHD_ALL)
    # classic kernels keep the classic defaults
    j3 = FleetJob("z3", kernel="diffuse")
    assert set(j3.cell_data) == {"rho"} and j3.params == (0.1,)


# -- physics invariants -----------------------------------------------

def test_mhd_conservation():
    """Mass, momentum, energy and B totals are conserved by the blast
    run under full periodicity (the invariant surface the SDC defense
    registers)."""
    m = GridMHD(n=8)
    before = m.conserved_sums()
    m.run(6, dt=0.01)
    after = m.conserved_sums()
    n_cells = 8 ** 3
    for name in MHD_ALL:
        tol = integrity.sum_tolerance(before[name], n_cells, steps=6)
        assert abs(after[name] - before[name]) <= tol, (
            name, before[name], after[name], tol)
    # and the run actually did something
    assert after != before or m.time > 0


def test_vlasov_mass_conservation():
    v = GridVlasov(n=6, nv=12)
    m0 = v.total_mass()
    v.run(8, dt=0.04)
    m1 = v.total_mass()
    assert abs(m1 - m0) <= integrity.sum_tolerance(m0, 6 ** 3, steps=8)


# -- ResilientRunner: rollback reconverges bitwise --------------------

def _mhd_state(m):
    return b"".join(np.asarray(
        m.grid.get(n, m.grid.plan.cells)).tobytes() for n in MHD_ALL)


def test_mhd_resilient_runner_rollback_bitwise(tmp_path):
    import jax

    from dccrg_tpu.grid import default_mesh

    def mk():
        # single-device mesh: the rollback contract is mesh-agnostic
        # and the 8-field programs compile much faster unsharded
        m = GridMHD(n=6, mesh=default_mesh(jax.devices()[:1]))
        return m, lambda g, i: m.run(1, dt=0.01)

    ref, ref_step = mk()
    ResilientRunner(ref.grid, ref_step, str(tmp_path / "ref.dc"),
                    check_every=1, checkpoint_every=4, backoff=0.0,
                    diagnostics_dir=str(tmp_path)).run(10)

    inj, inj_step = mk()
    plan = faults.FaultPlan(seed=2)
    plan.nan_poison("rho", step=6)
    runner = ResilientRunner(inj.grid, inj_step, str(tmp_path / "i.dc"),
                             check_every=1, checkpoint_every=4,
                             backoff=0.0, diagnostics_dir=str(tmp_path))
    with plan:
        runner.run(10)
    assert runner.rollbacks == 1
    assert _mhd_state(inj) == _mhd_state(ref)


def test_vlasov_resilient_runner_rollback_bitwise(tmp_path):
    import jax

    from dccrg_tpu.grid import default_mesh

    def mk():
        v = GridVlasov(n=6, nv=10, mesh=default_mesh(jax.devices()[:1]))
        return v, lambda g, i: v.run(1, dt=0.04)

    ref, ref_step = mk()
    ResilientRunner(ref.grid, ref_step, str(tmp_path / "ref.dc"),
                    check_every=1, checkpoint_every=3, backoff=0.0,
                    diagnostics_dir=str(tmp_path)).run(8)
    inj, inj_step = mk()
    plan = faults.FaultPlan(seed=4)
    plan.nan_poison("f", step=5)
    runner = ResilientRunner(inj.grid, inj_step, str(tmp_path / "i.dc"),
                             check_every=1, checkpoint_every=3,
                             backoff=0.0, diagnostics_dir=str(tmp_path))
    with plan:
        runner.run(8)
    assert runner.rollbacks == 1
    for n in VLASOV_FIELDS:
        a = np.asarray(inj.grid.get(n, inj.grid.plan.cells))
        b = np.asarray(ref.grid.get(n, ref.grid.plan.cells))
        assert a.tobytes() == b.tobytes(), n


# -- the multi-device step against one device -------------------------

def _one_device():
    import jax

    from dccrg_tpu.grid import default_mesh

    return default_mesh(jax.devices()[:1])


def _mhd_pass_pair(mesh, exchange_all=False):
    """Five hydro steps, then five B steps, each pass exchanging only
    its own subsystem (or every field); the cells of every field."""
    m = GridMHD(n=8, nz=40, mesh=mesh)
    hydro, bpass = make_mhd_pass_kernels()
    lam = jnp.float32(0.01 * m.n)
    for kern, exch in ((hydro, MHD_HYDRO), (bpass, MHD_BFIELD)):
        m.grid.run_steps(kern, MHD_ALL, MHD_ALL, 5,
                         exchange_fields=MHD_ALL if exchange_all else exch,
                         extra_args=(lam,))
    return m.grid.n_dev, {n: np.asarray(m.grid.get(n, m.grid.plan.cells))
                          for n in MHD_ALL}


def test_mhd_pass_pair_mesh_matches_one_device():
    """The operator-split pair on the 8-device mesh: exchanging only a
    pass's own subsystem (the other one's ghosts go stale; that pass
    does not read them) is bitwise the same as exchanging every field,
    and matches the same grid on one device to rounding (the two
    programs contract the flux arithmetic differently: a few cells
    differ in the last bit)."""
    n8, got = _mhd_pass_pair(None)
    _, full = _mhd_pass_pair(None, exchange_all=True)
    n1, want = _mhd_pass_pair(_one_device())
    assert (n8, n1) == (8, 1)
    for n in MHD_ALL:
        assert got[n].tobytes() == full[n].tobytes(), n
        np.testing.assert_allclose(got[n], want[n], rtol=2e-6, atol=2e-6,
                                   err_msg=n)


def test_vlasov_mesh_matches_one_device():
    """GridVlasov on the 8-device mesh (only the moments exchange)
    steps bitwise like one device, the wide payload included."""
    out = []
    for mesh in (None, _one_device()):
        v = GridVlasov(n=8, nz=40, nv=8, mesh=mesh)
        v.run(4, dt=0.04)
        cells = v.grid.plan.cells
        out.append(b"".join(np.asarray(v.grid.get(n, cells)).tobytes()
                            for n in VLASOV_FIELDS))
    assert out[0] == out[1]


def test_vlasov_wide_payload_never_exchanges(monkeypatch):
    """The ragged-Cell_Data contract: the wide [Nv] payload's ghost
    rows keep their stale bytes across stepped exchanges — only the
    moments move."""
    v = GridVlasov(n=8, nz=40, nv=8)
    g = v.grid
    L = g.plan.L

    def ghost_bytes(name):
        host = np.asarray(g.data[name])
        return b"".join(
            host[d, L:L + len(g.plan.ghost_ids[d])].tobytes()
            for d in range(g.n_dev))

    f_before = ghost_bytes("f")
    rho_before = ghost_bytes("rho")
    v.run(4, dt=0.04)
    assert ghost_bytes("f") == f_before          # payload stayed local
    assert ghost_bytes("rho") != rho_before      # moments moved
    # a full exchange DOES move it (the bytes were genuinely stale)
    g.update_copies_of_remote_neighbors(fields=("f",))
    assert ghost_bytes("f") != f_before


# -- Poisson fused solve on a mesh ------------------------------------

def test_poisson_fused_solve_mesh_matches_one_device():
    """The fused BiCG solve on 4 devices (p0/p1 exchanged before both
    matvecs every iteration) takes the same iteration count as on one
    device and lands on the same solution; only the global dots'
    summation order differs (float64)."""
    import jax

    from dccrg_tpu.grid import default_mesh
    from dccrg_tpu.models.poisson import PoissonSolver

    out = {}
    for n_dev in (4, 1):
        s = PoissonSolver(length=(8, 8, 8), dtype=jnp.float64,
                          mesh=default_mesh(jax.devices()[:n_dev]))
        assert s.grid.n_dev == n_dev
        s.set_rhs_from(
            lambda x, y, z: np.cos(2 * np.pi * x / 8)
            + np.sin(2 * np.pi * y / 8))
        info = s.solve(rtol=1e-8)
        out[n_dev] = info["iterations"], np.asarray(s.solution())
    assert out[4][0] == out[1][0] > 0
    scale = np.abs(out[1][1]).max()
    np.testing.assert_allclose(out[4][1], out[1][1], rtol=0,
                               atol=1e-12 * scale)


# -- mixed-kernel fleet serving ---------------------------------------

def _zoo_jobs():
    return [FleetJob(f"{k}{i}", kernel=k, length=(6, 6, 6), n_steps=10,
                     seed=17 * i + 3, checkpoint_every=4)
            for k in ("advect_x", "mhd", "vlasov") for i in range(2)]


def _solo_digests(jobs):
    return {j.name: run_solo(FleetJob(
        j.name, kernel=j.kernel, length=j.length, n_steps=j.n_steps,
        seed=j.seed)) for j in jobs}


def test_mixed_kernel_fleet_isolation(tmp_path):
    """THE serving-diversity pin: advection + MHD + Vlasov jobs in
    ONE scheduler run (three distinct buckets), an injected NaN in
    the MHD victim — only the victim trips, and EVERY job's digest is
    bitwise its solo run's."""
    jobs = _zoo_jobs()
    solo = _solo_digests(jobs)
    victim = "mhd1"
    plan = faults.FaultPlan(seed=5)
    plan.nan_poison("rho", step=4, job=victim)
    with plan:
        report = FleetScheduler(str(tmp_path), jobs, quantum=4).run()
    assert plan.fired("step.poison") == 1
    assert len({j.bucket_key() for j in jobs}) == 3
    for j in jobs:
        row = report[j.name]
        assert row["status"] == "done"
        assert row["digest"] == solo[j.name], j.name
        if j.name != victim:
            assert not row["trips"], (j.name, row["trips"])
    assert report[victim]["trips"] >= 1


def test_mixed_kernel_fleet_checkpoint_resume(tmp_path):
    """Per-job fleet checkpoints work on the new schemas out of the
    box — the wide Vlasov field included: a fleet stopped after two
    ticks resumes in a FRESH scheduler over the same dir and every
    job still converges bitwise to its solo run."""
    jobs = [FleetJob(f"r_{k}", kernel=k, length=(6, 6, 6), n_steps=10,
                     seed=23, checkpoint_every=4)
            for k in ("advect_x", "mhd", "vlasov")]
    solo = _solo_digests(jobs)
    FleetScheduler(str(tmp_path), jobs, quantum=2).run(max_ticks=2)
    resumed = [FleetJob(j.name, kernel=j.kernel, length=j.length,
                        n_steps=j.n_steps, seed=j.seed,
                        checkpoint_every=4) for j in jobs]
    report = FleetScheduler(str(tmp_path), resumed, quantum=4,
                            resume=True).run()
    for j in resumed:
        assert report[j.name]["status"] == "done"
        assert report[j.name]["digest"] == solo[j.name], j.name


def test_mixed_kernel_lane_slo_shed(tmp_path):
    """A deadline MHD job whose LANE latency (the advect cohabitant
    bucket dispatches every tick too) projects past its SLO sheds the
    best-effort advect job out of the OTHER bucket: parked with a
    keyframe, resumed after the deadline job finishes, both bitwise
    equal to their solo runs."""
    jobs = [FleetJob("be_adv", kernel="advect_x", length=(6, 6, 6),
                     n_steps=12, seed=1, checkpoint_every=4),
            FleetJob("slo_mhd", kernel="mhd", length=(6, 6, 6),
                     n_steps=12, seed=2, checkpoint_every=4,
                     slo_ms=100.0)]
    solo = _solo_digests(jobs)
    base = telemetry.registry().counter_total(
        "dccrg_fleet_lane_sheds_total")
    pol = SLOPolicy(quantum=4, clock=lambda: 0.0)
    sched = FleetScheduler(str(tmp_path), jobs, quantum=4,
                           slo_policy=pol)
    sched._admit_pending()
    batches = [b for bs in sched.buckets.values() for b in bs]
    assert len(batches) == 2  # two kernels -> two buckets, one lane
    # hand-fed: 20 ms/quantum each; 3 remaining quanta x 40 ms lane
    # latency blows the 100 ms budget, own-bucket 60 ms does not
    for b in batches:
        pol.observe(b.key, 0.02)
    sched._shed_for_lane()
    by_name = {j.name: j for j in jobs}
    assert by_name["be_adv"].status == "parked"
    assert by_name["slo_mhd"].status == "running"
    assert telemetry.registry().counter_total(
        "dccrg_fleet_lane_sheds_total") - base == 1
    report = sched.run()
    for j in jobs:
        assert report[j.name]["status"] == "done"
        assert report[j.name]["digest"] == solo[j.name], j.name
    assert report["slo_mhd"]["slo_met"] is True


def test_lane_shed_negative_pin_without_slo(tmp_path):
    """No SLO jobs -> the lane-shed pass never parks anything,
    whatever the measured latencies (mixed-kernel fleets without
    deadlines keep the exact pre-PR behavior)."""
    jobs = [FleetJob("a", kernel="advect_x", length=(6, 6, 6),
                     n_steps=6, seed=1),
            FleetJob("m", kernel="mhd", length=(6, 6, 6),
                     n_steps=6, seed=2)]
    pol = SLOPolicy(quantum=4, clock=lambda: 0.0)
    sched = FleetScheduler(str(tmp_path), jobs, quantum=4,
                           slo_policy=pol)
    sched._admit_pending()
    for bs in sched.buckets.values():
        for b in bs:
            pol.observe(b.key, 99.0)
    sched._shed_for_lane()
    assert not sched._parked
    assert all(j.status == "running" for j in jobs)


def test_fleet_sdc_fingerprints_cover_wide_field(tmp_path):
    """The integrity layer fingerprints the wide [Nv] float32 field:
    a FINITE silent flip in the Vlasov payload convicts as a CORRUPT
    trip and the victim still converges to its solo digest."""
    jobs = [FleetJob(f"vl{i}", kernel="vlasov", length=(6, 6, 6),
                     n_steps=10, seed=5 + i, checkpoint_every=3)
            for i in range(3)]
    solo = _solo_digests(jobs)
    plan = faults.FaultPlan(seed=9)
    plan.silent_flip("f", step=5, job="vl1")
    with plan:
        report = FleetScheduler(str(tmp_path), jobs, quantum=3).run()
    assert plan.fired("step.flip") == 1
    assert report["vl1"]["sdc_trips"] >= 1
    for j in jobs:
        assert report[j.name]["status"] == "done"
        assert report[j.name]["digest"] == solo[j.name], j.name
        if j.name != "vl1":
            assert not report[j.name]["trips"]


# -- fuzz + CLI surfaces ----------------------------------------------

def test_mhd_schema_fuzz_leg():
    """The MHD-schema GridFuzzer leg: txn/fault mutation sites over
    the 8-field schema, with the multi-field exchange op exercising
    random ``fields=`` subsets against the ghost oracle."""
    fz = GridFuzzer(11, ops=12, schema="mhd", fault_rate=0.3).run()
    assert fz.ops_run == 12
    assert fz.schema == "mhd"


def test_jobs_from_spec_names_zoo_kernels(tmp_path):
    """A CLI job file can name any zoo kernel without spelling out
    its schema; the scheduler serves it to completion."""
    spec = {"jobs": [
        {"name": "jm", "kernel": "mhd", "n": 6, "steps": 4},
        {"name": "jv", "kernel": "vlasov", "n": 6, "steps": 4},
        {"name": "jd", "kernel": "diffuse", "n": 6, "steps": 4},
    ]}
    jobs = _jobs_from_spec(spec)
    assert set(jobs[0].cell_data) == set(MHD_ALL)
    assert "f" in jobs[1].cell_data
    assert jobs[2].params == (0.1,)  # the classic default held
    report = FleetScheduler(str(tmp_path), jobs, quantum=4).run()
    assert all(r["status"] == "done" for r in report.values())
    json.dumps({n: r["digest"] for n, r in report.items()})  # sane
