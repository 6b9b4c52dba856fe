"""Telemetry subsystem: registry semantics, span tracing (including
the zero-allocation no-op contract on the step path), JSONL trace
round-trips and cross-rank merging, Prometheus exposition, latency-SLO
fleet admission (deterministic under a fake clock) and the
strictly-best-effort exporter contract (a failing telemetry write can
NEVER trip or roll back the run it observes)."""

import json
import time
import tracemalloc

import numpy as np
import pytest

import jax.numpy as jnp

from dccrg_tpu import Grid, faults, resilience, supervise, telemetry
from dccrg_tpu.fleet import FleetJob, run_solo
from dccrg_tpu.scheduler import FleetScheduler, SLOPolicy

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts with tracing off, an empty ring and a fresh
    registry, and leaves the process the same way (the registry is
    process-global by design)."""
    telemetry.configure(trace=False)
    telemetry.clear_trace()
    telemetry.registry().reset()
    telemetry._METRICS_STATE["last"] = None
    yield
    telemetry.configure(trace=False)
    telemetry.clear_trace()
    telemetry.registry().reset()
    telemetry._METRICS_STATE["last"] = None


# -- registry ---------------------------------------------------------

def test_counters_gauges_histograms():
    telemetry.inc("dccrg_trips_total", kind="numerics")
    telemetry.inc("dccrg_trips_total", kind="numerics")
    telemetry.inc("dccrg_trips_total", kind="corrupt")
    telemetry.set_gauge("dccrg_arena_pool_hits", 7)
    telemetry.observe("dccrg_step_seconds", 0.01)
    telemetry.observe("dccrg_step_seconds", 0.02)
    reg = telemetry.registry()
    assert reg.counter_value("dccrg_trips_total", kind="numerics") == 2
    assert reg.counter_value("dccrg_trips_total", kind="corrupt") == 1
    assert reg.counter_total("dccrg_trips_total") == 3
    h = reg.histogram("dccrg_step_seconds")
    assert h.total == 2 and abs(h.sum_seconds - 0.03) < 1e-9
    assert h.quantile(0.5) >= 0.01


def test_histogram_is_the_one_implementation():
    """Satellite pin: supervise.LatencyHistogram IS the telemetry
    histogram type, with the historical API intact."""
    assert supervise.LatencyHistogram is telemetry.LogHistogram
    h = supervise.LatencyHistogram()
    assert h.BASE == 1e-4 and h.N_BUCKETS == 30
    h.record(0.05)
    assert h.total == 1 and h.max_seconds == 0.05
    assert len(h.buckets()) == 30 and len(h.counts) == 30
    assert h.quantile(0.5) >= 0.05
    assert "p50" in h.summary()


def test_dump_prometheus_exposition():
    telemetry.inc("dccrg_saves_total", kind="keyframe")
    telemetry.observe("dccrg_fleet_quantum_seconds", 0.004, job="a")
    text = telemetry.dump_prometheus()
    assert "# TYPE dccrg_saves_total counter" in text
    assert 'dccrg_saves_total{kind="keyframe"} 1' in text
    assert "# TYPE dccrg_fleet_quantum_seconds histogram" in text
    assert 'le="+Inf"' in text
    assert 'dccrg_fleet_quantum_seconds_count{job="a"} 1' in text
    # bucket counts are cumulative and end at the total
    lines = [ln for ln in text.splitlines()
             if ln.startswith("dccrg_fleet_quantum_seconds_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts) and counts[-1] == 1
    # label values are user strings (job names): escaped, not trusted
    telemetry.inc("dccrg_fleet_trips_total", job='we"ird\\name')
    assert 'job="we\\"ird\\\\name"' in telemetry.dump_prometheus()


# -- span tracer ------------------------------------------------------

def test_span_nesting_depth_and_parent():
    telemetry.configure(trace=True)
    with telemetry.span("outer"):
        with telemetry.span("inner", {"k": 3}):
            pass
    evs = telemetry.events()
    byname = {e["name"]: e for e in evs}
    assert byname["inner"]["depth"] == 1
    assert byname["inner"]["parent"] == "outer"
    assert byname["inner"]["k"] == 3
    assert byname["outer"]["depth"] == 0 and "parent" not in byname["outer"]
    assert all(e["dur"] >= 0 for e in evs)


def test_ambient_tags_scope():
    telemetry.configure(trace=True)
    with telemetry.tags(job="j42"):
        with telemetry.span("ckpt.save"):
            pass
    with telemetry.span("ckpt.save"):
        pass
    evs = [e for e in telemetry.events() if e["name"] == "ckpt.save"]
    assert evs[0]["job"] == "j42" and "job" not in evs[1]


def test_trace_ring_is_bounded_and_drops_are_counted():
    telemetry.configure(trace=True, ring=32)
    for i in range(100):
        with telemetry.span("s"):
            pass
    assert len(telemetry.events()) == 32
    # the 68 evicted events are accounted, not silently forgotten
    assert telemetry.registry().counter_value(
        "dccrg_trace_dropped_total") == 68
    telemetry.configure(ring=telemetry.trace_ring_default())


def test_noop_mode_is_singleton_and_zero_allocation():
    """DCCRG_TRACE off: span() returns ONE shared no-op object — no
    event dict, no ring append, no per-call allocation on the step
    path."""
    assert not telemetry.trace_enabled()
    assert telemetry.span("grid.step") is telemetry.span("fleet.quantum")
    with telemetry.span("warmup"):
        pass
    tracemalloc.start()
    tracemalloc.reset_peak()
    c0, _ = tracemalloc.get_traced_memory()
    for _ in range(5000):
        with telemetry.span("grid.step"):
            pass
    c1, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # a per-call allocation would retain/peak at >= 5000 x object
    # size; the sub-kB residue is tracemalloc bookkeeping noise
    assert c1 - c0 < 512, "no-op spans retained allocations"
    assert peak - c0 < 4096, "no-op spans allocated per call"
    assert telemetry.events() == []


def test_span_lands_on_profiler_host_plane(tmp_path):
    """DCCRG_TRACE off, a jax.profiler session on: the span is a
    TraceAnnotation on the trace's host plane, and the ring stays
    empty."""
    import glob

    import jax

    assert not telemetry.trace_enabled()
    assert not telemetry.profiling()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert telemetry.profiling()
        with telemetry.span("grid.step"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    assert not telemetry.profiling()
    assert telemetry.events() == []
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    host = [e for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events if e.name == "grid.step"]
    assert len(host) == 1 and host[0].duration_ns >= 2e6


def test_record_span_and_traced_decorator():
    telemetry.configure(trace=True)
    telemetry.record_span("hybrid.classification", 0.125, {"n": 2})

    @telemetry.traced("fn.x", counter="dccrg_fn_x_total")
    def f(a):
        return a + 1

    assert f(1) == 2
    telemetry.configure(trace=False)
    assert f(2) == 3  # untraced call still counts
    evs = telemetry.events()
    assert [e["name"] for e in evs] == ["hybrid.classification", "fn.x"]
    assert evs[0]["dur"] == 0.125
    assert telemetry.registry().counter_value("dccrg_fn_x_total") == 2


# -- JSONL export + merge ---------------------------------------------

def test_jsonl_roundtrip_and_flush_clears_ring(tmp_path):
    telemetry.configure(trace=True)
    with telemetry.span("a", {"job": "x"}):
        pass
    with telemetry.span("b"):
        pass
    p = tmp_path / "trace.jsonl"
    n = telemetry.flush_trace(str(p))
    assert n == 2 and telemetry.events() == []
    evs = telemetry.read_trace(str(p))
    assert [e["name"] for e in evs] == ["a", "b"]
    assert evs[0]["job"] == "x"
    assert all(set(e) >= {"name", "ts", "dur", "rank", "depth"}
               for e in evs)
    # appending a second flush extends the same file
    with telemetry.span("c"):
        pass
    assert telemetry.flush_trace(str(p)) == 1
    assert [e["name"] for e in telemetry.read_trace(str(p))] == \
        ["a", "b", "c"]


def test_merge_traces_orders_across_ranks(tmp_path):
    r0 = tmp_path / "r0.jsonl"
    r1 = tmp_path / "r1.jsonl"
    r0.write_text("".join(json.dumps(e) + "\n" for e in [
        {"name": "grid.step", "ts": 1.0, "dur": 0.5, "rank": 0,
         "depth": 0},
        {"name": "ckpt.save", "ts": 3.0, "dur": 0.2, "rank": 0,
         "depth": 0}]))
    r1.write_text("".join(json.dumps(e) + "\n" for e in [
        {"name": "grid.step", "ts": 2.0, "dur": 0.5, "rank": 1,
         "depth": 0}]) + "{torn line")
    evs = telemetry.merge_traces([str(r0), str(r1)])
    assert [(e["ts"], e["rank"]) for e in evs] == \
        [(1.0, 0), (2.0, 1), (3.0, 0)]
    stats = telemetry.span_stats(evs)
    assert stats["grid.step"]["count"] == 2
    assert abs(stats["grid.step"]["total_s"] - 1.0) < 1e-9


def test_cli_merge_and_summary(tmp_path, capsys):
    from dccrg_tpu.telemetry import _main

    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps({"name": "s", "ts": 1.0, "dur": 0.1,
                             "rank": 0, "depth": 0}) + "\n")
    assert _main(["merge", str(p)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[0])["name"] == "s"
    assert _main(["summary", str(p)]) == 0
    summ = json.loads(capsys.readouterr().out)
    assert summ["events"] == 1 and summ["ranks"] == [0]
    assert summ["spans"]["s"]["count"] == 1


# -- metrics file export ----------------------------------------------

def test_metrics_file_export(tmp_path, monkeypatch):
    telemetry.inc("dccrg_trips_total", kind="numerics")
    p = tmp_path / "metrics.prom"
    monkeypatch.setenv("DCCRG_METRICS_FILE", str(p))
    assert telemetry.maybe_export_metrics(now=0.0)
    assert "dccrg_trips_total" in p.read_text()
    # inside the min interval: no rewrite
    telemetry.inc("dccrg_trips_total", kind="numerics")
    assert not telemetry.maybe_export_metrics(now=1.0)
    # past it: the fresh value lands
    assert telemetry.maybe_export_metrics(
        now=1.0 + telemetry.metrics_every_default())
    assert 'dccrg_trips_total{kind="numerics"} 2' in p.read_text()


# -- best-effort exporters: fault injection ---------------------------

def test_export_failure_is_swallowed_and_counted(tmp_path):
    telemetry.configure(trace=True)
    with telemetry.span("s"):
        pass
    plan = faults.FaultPlan(seed=0)
    plan.telemetry_io_error(times=1)
    with plan:
        assert telemetry.flush_trace(str(tmp_path / "t.jsonl")) == 0
    assert plan.fired("telemetry.export") == 1
    assert telemetry.registry().counter_value(
        "dccrg_telemetry_export_errors_total") == 1
    # the ring was still cleared: a dead sink must not grow memory
    assert telemetry.events() == []


def _mk_grid(seed=0):
    g = (Grid(cell_data={"v": jnp.float32})
         .set_initial_length((8, 8, 4))
         .set_periodic(True, True, False)
         .set_maximum_refinement_level(0)
         .set_neighborhood_length(1)
         .set_load_balancing_method("block")
         .initialize())
    cells = g.plan.cells
    g.set("v", cells, ((cells.astype(np.float64) * (seed + 7) % 31) / 31)
          .astype(np.float32))
    g.update_copies_of_remote_neighbors()
    return g


def _kernel(c, nbr, offs, mask):
    return {"v": jnp.float32(0.5) * c["v"] + jnp.float32(0.125) * jnp.sum(
        jnp.where(mask, nbr["v"], jnp.float32(0)), axis=1)}


def test_exporter_faults_never_trip_a_run(tmp_path, monkeypatch):
    """The satellite pin: EVERY telemetry write failing (trace file
    AND metrics file) must leave the supervised run with zero trips
    and zero rollbacks — telemetry is strictly best-effort."""
    monkeypatch.setenv("DCCRG_TRACE_FILE", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("DCCRG_METRICS_FILE", str(tmp_path / "m.prom"))
    monkeypatch.setenv("DCCRG_METRICS_EVERY", "0")
    telemetry.configure(trace=True)
    telemetry._METRICS_STATE["last"] = None

    def step_fn(grid, _i):
        grid.run_steps(_kernel, ["v"], ["v"], 1)
        telemetry.flush_trace()

    sup = supervise.SupervisedRunner(
        _mk_grid(), step_fn, str(tmp_path / "ckpt"), check_every=2,
        checkpoint_every=3, backoff=0.0)
    plan = faults.FaultPlan(seed=1)
    plan.telemetry_io_error(times=faults.EVERY)
    with plan:
        sup.run(7)
    assert sup.step == 7
    assert sup.trips == [] and sup.rollbacks == 0
    assert plan.fired("telemetry.export") > 0
    assert telemetry.registry().counter_value(
        "dccrg_telemetry_export_errors_total") > 0
    assert not (tmp_path / "t.jsonl").exists()


# -- instrumented boundaries ------------------------------------------

def test_solo_run_records_distinct_boundary_spans(tmp_path):
    """A single-grid run shows its phases as distinct spans: step
    dispatch, halo exchange (real on the 8-device CPU mesh), AMR
    adapt + plan recommit, checkpoint save/load — and the registry
    carries the matching counters."""
    telemetry.configure(trace=True)
    g = (Grid(cell_data={"v": jnp.float32})
         .set_initial_length((8, 8, 4))
         .set_periodic(True, True, False)
         .set_maximum_refinement_level(1)
         .set_neighborhood_length(1)
         .initialize())
    cells = g.plan.cells
    g.set("v", cells, np.linspace(0.0, 1.0, len(cells),
                                  dtype=np.float32))
    g.update_copies_of_remote_neighbors()
    g.run_steps(_kernel, ["v"], ["v"], 2)
    g.refine_completely(cells[0])
    g.stop_refining()
    path = str(tmp_path / "a.dc")
    resilience.save_checkpoint(g, path)
    names = {e["name"] for e in telemetry.events()}
    assert {"grid.step", "grid.exchange", "grid.adapt",
            "grid.recommit", "ckpt.save"} <= names
    reg = telemetry.registry()
    assert reg.counter_value("dccrg_saves_total", kind="keyframe") == 1
    assert "dccrg_saves_total" in telemetry.dump_prometheus()


# -- SLO policy: deterministic under a fake clock ---------------------

def _slo_jobs():
    """Three same-bucket jobs: A outranks B outranks C by priority; C
    alone carries a (tight) completion SLO."""
    a = FleetJob("slo_a", length=(8, 8, 8), n_steps=16, priority=2,
                 seed=1, checkpoint_every=100)
    b = FleetJob("slo_b", length=(8, 8, 8), n_steps=16, priority=1,
                 seed=2, checkpoint_every=100)
    c = FleetJob("slo_c", length=(8, 8, 8), n_steps=16, priority=0,
                 seed=3, checkpoint_every=100, slo_ms=1000.0)
    return a, b, c


def test_slo_policy_ewma_projection_and_slack():
    clk = {"t": 0.0}
    pol = SLOPolicy(quantum=8, alpha=0.5, clock=lambda: clk["t"])
    a, _b, c = _slo_jobs()
    key = c.bucket_key()
    assert pol.quantum_latency(key) is None
    assert pol.projected_completion_s(c) == 0.0  # no data, no reorder
    pol.observe(key, 2.0)
    pol.observe(key, 4.0)
    assert pol.quantum_latency(key) == pytest.approx(3.0)
    # 16 steps at quantum 8 = 2 quanta -> 6 s projected
    assert pol.projected_completion_s(c) == pytest.approx(6.0)
    c.slo_t0 = 0.0
    clk["t"] = 0.25
    # slack = 1.0 - 0.25 - 6.0
    assert pol.slack_s(c) == pytest.approx(-5.25)
    assert pol.slack_s(a) is None  # best-effort job
    # violated SLO sorts into category 0, ahead of any priority
    assert pol.admission_key(c, 99) < pol.admission_key(a, 0)
    # without violation the baseline (-priority, seq) order holds
    clk["t"] = 0.0
    pol.reset_key(key)
    assert pol.admission_key(a, 0) < pol.admission_key(c, 99)


def test_slo_admission_reorders_vs_priority_baseline(tmp_path):
    """THE acceptance pin: identical job mixes, identical capacity
    pressure (2 slots for 3 jobs) — the priority-only baseline admits
    A+B and queues the SLO job C; with a measured quantum-latency
    EWMA projecting C past its deadline, the SLO policy admits C
    FIRST, displacing the lowest-priority best-effort job.
    Deterministic: fake clock, hand-fed latency observations, no
    stepping."""
    # baseline: no latency data -> byte-identical to priority order
    base = FleetScheduler(str(tmp_path / "base"), _slo_jobs(),
                          max_batch=2, quantum=8,
                          slo_policy=SLOPolicy(quantum=8,
                                               clock=lambda: 0.0))
    base._admit_pending()
    assert {j.name: j.status for j in base._by_name.values()} == {
        "slo_a": "running", "slo_b": "running", "slo_c": "queued"}

    # SLO run: the measured EWMA (10 s/quantum) projects C's 2
    # remaining quanta far past its 1 s deadline -> C admits first,
    # then A by priority; B waits
    jobs = _slo_jobs()
    pol = SLOPolicy(quantum=8, clock=lambda: 0.0)
    pol.observe(jobs[2].bucket_key(), 10.0)
    slo = FleetScheduler(str(tmp_path / "slo"), jobs, max_batch=2,
                         quantum=8, slo_policy=pol)
    slo._admit_pending()
    assert {j.name: j.status for j in slo._by_name.values()} == {
        "slo_a": "running", "slo_b": "queued", "slo_c": "running"}


def test_slo_shed_requeues_to_smaller_bucket(tmp_path):
    """A bucket whose measured quantum latency blows its tightest
    admitted SLO sheds its best-effort cohabitants (keyframed +
    requeued) and rebuilds at half capacity with the survivors
    migrated bit-exactly; the fleet then completes with every digest
    still equal to the solo baseline."""
    jobs = [FleetJob(f"shed{i}", length=(8, 8, 8), n_steps=16,
                     priority=i, seed=i, checkpoint_every=4,
                     params=(0.01,),  # stable dt for the 26-nbr kernel
                     slo_ms=(100.0 if i == 3 else None))
            for i in range(4)]
    solo = {j.name: run_solo(FleetJob(
        j.name, length=(8, 8, 8), n_steps=16, seed=j.seed,
        params=(0.01,)))
        for j in jobs}
    pol = SLOPolicy(quantum=8, clock=lambda: 0.0)
    sched = FleetScheduler(str(tmp_path), jobs, max_batch=8,
                           quantum=8, slo_policy=pol)
    sched._admit_pending()
    (batch,) = [b for bs in sched.buckets.values() for b in bs]
    cap0 = batch.capacity
    assert len(batch.jobs) == 4
    # hand-fed latency: 10 s/quantum blows shed3's 100 ms budget
    pol.observe(batch.key, 10.0)
    pre = {j.name: batch.digest(s) for s, j in batch.jobs}
    sched._shed_for_slo(batch)
    shed = [j for j in jobs if j.status == "queued"]
    assert len(shed) == 2 and all(j.slo_ms is None for j in shed)
    assert all(j.requeues == 1 for j in shed)
    (small,) = [b for bs in sched.buckets.values() for b in bs]
    assert small is not batch and small.capacity < cap0
    # survivors migrated bit-exactly; the SLO job survived the shed
    names = {j.name for _s, j in small.jobs}
    assert "shed3" in names
    for s, j in small.jobs:
        assert small.digest(s) == pre[j.name]
    assert telemetry.registry().counter_total(
        "dccrg_fleet_slo_sheds_total") == 2
    # the EWMA reset: the smaller bucket is measured fresh
    assert pol.quantum_latency(batch.key) is None
    # and the whole fleet still converges bitwise to the solo runs
    report = sched.run()
    assert all(r["status"] == "done" for r in report.values())
    assert {n: r["digest"] for n, r in report.items()} == solo
    assert report["shed3"]["slo_ms"] == 100.0
    assert report["shed3"]["slo_met"] is True  # fake clock: 0 elapsed


def test_priority_only_baseline_unchanged_without_slo(tmp_path):
    """No SLO jobs -> the admission pass is the exact priority-FIFO
    baseline and the shed pass never fires, however bad the measured
    latency (nothing to violate)."""
    jobs = [FleetJob(f"pb{i}", length=(8, 8, 8), n_steps=8,
                     priority=i % 3, seed=i, checkpoint_every=100)
            for i in range(5)]
    pol = SLOPolicy(quantum=8, clock=lambda: 0.0)
    pol.observe(jobs[0].bucket_key(), 1e6)
    sched = FleetScheduler(str(tmp_path), jobs, max_batch=3,
                           quantum=8, slo_policy=pol)
    sched._admit_pending()
    running = sorted(j.name for j in jobs if j.status == "running")
    # priorities 2,2 then 1 admit first (FIFO within a priority)
    assert running == ["pb1", "pb2", "pb4"]
    for bs in sched.buckets.values():
        for b in bs:
            assert pol.shed_victims(b.key, b.jobs) == []


# -- the fleet acceptance: trace coverage + exposition ----------------

def test_fleet_trace_covers_step_wall_clock(tmp_path):
    """Acceptance pin: one fleet run with tracing on produces a trace
    whose depth-0 spans account for >= 95% of the measured serving
    wall-clock, with admission / quantum dispatch / checkpoint saves
    visible as distinct (and job-tagged) spans, and dump_prometheus
    exposing the trips/rollbacks/audits/saves counters."""
    telemetry.configure(trace=True,
                        ring=max(telemetry.trace_ring_default(), 1 << 16))
    jobs = [FleetJob(f"cov{i}", length=(12, 12, 12), n_steps=12,
                     priority=i % 2, seed=i, checkpoint_every=4,
                     params=(0.01,))
            for i in range(4)]
    sched = FleetScheduler(str(tmp_path), jobs, quantum=4)
    t0 = time.perf_counter()
    report = sched.run()
    wall = time.perf_counter() - t0
    assert all(r["status"] == "done" for r in report.values())
    evs = telemetry.events()
    names = {e["name"] for e in evs}
    assert {"fleet.admit", "fleet.quantum", "ckpt.save"} <= names
    # per-job checkpoint saves carry the owning job's tag
    assert any(e.get("job", "").startswith("cov")
               for e in evs if e["name"] == "ckpt.save")
    cov = telemetry.root_coverage(evs, wall)
    assert cov >= 0.95, f"spans cover only {cov:.1%} of {wall:.3f}s"
    # the same run exports as a JSONL trace file, one event per span
    trace = tmp_path / "fleet_trace.jsonl"
    n = telemetry.flush_trace(str(trace))
    assert n == len(evs)
    assert len(telemetry.read_trace(str(trace))) == n
    text = telemetry.dump_prometheus()
    for metric in ("dccrg_saves_total",
                   "dccrg_fleet_quantum_seconds",
                   "dccrg_fleet_admissions_total",
                   "dccrg_integrity_checks_total"):
        assert metric in text, metric
    reg = telemetry.registry()
    assert reg.counter_total("dccrg_fleet_admissions_total") == 4
    h = reg.histogram("dccrg_fleet_quantum_seconds", job="cov0")
    assert h is not None and h.total >= 3  # 12 steps / quantum 4


def test_fleet_trip_and_rollback_counters(tmp_path):
    """An injected NaN trip surfaces in the registry: the trips and
    rollbacks counters (the fleet CLI summary's source) count the
    victim's recovery."""
    jobs = [FleetJob(f"ctr{i}", length=(8, 8, 8), n_steps=12, seed=i,
                     params=(0.01,), checkpoint_every=4)
            for i in range(3)]
    plan = faults.FaultPlan(seed=3)
    plan.nan_poison("rho", step=6, job="ctr1")
    sched = FleetScheduler(str(tmp_path), jobs, quantum=4)
    with plan:
        report = sched.run()
    assert all(r["status"] == "done" for r in report.values())
    reg = telemetry.registry()
    assert reg.counter_value("dccrg_fleet_trips_total", job="ctr1",
                             kind="nan") == 1
    assert reg.counter_value("dccrg_fleet_rollbacks_total",
                             job="ctr1") == 1
    assert reg.counter_total("dccrg_fleet_trips_total", job="ctr0") == 0
    assert report["ctr1"]["rollbacks"] == 1
    text = telemetry.dump_prometheus()
    assert "dccrg_fleet_trips_total" in text
    assert "dccrg_fleet_rollbacks_total" in text
