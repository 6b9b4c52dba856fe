"""Device time of the step program by phase, and the host time of the
program's own ``grid.step`` spans, in the traced window.

The step program (``Grid.compile_step_loop``) wraps each phase of a
step in ``jax.named_scope("dccrg.<phase>")``: ``dccrg.exchange``,
``dccrg.bulk``, ``dccrg.repass``, ``dccrg.apply``. A profiler trace
shows an op's name but not its metadata, so the program publishes the
table op name -> scope of its compiled module on its first call under a
profiler session (``telemetry.program_scopes()``), and that table labels
the ops here. An op the table has no scope for is ``unscoped``; an op
missing from the table altogether is ``unscoped`` too, and is also
counted apart as ``missing``.

One device is read: the one with the most non-collective time, the one
``stencil_roofline`` reads. Its ops are read as ``trace.py`` reads them
(leaf events of its op lines, clipped to ``bench:window``), and its
busy time, their union, is split by phase: each instant goes in equal
parts to the ops that run in it. On a TPU core one op runs at a time,
so each phase holds its ops' own time and the phases sum to the busy
time; on the CPU backend of the tests, ops of several devices overlap
on host threads and the sum holds all the same.

While a profiler session records, the program's ``telemetry.span``s are
``TraceAnnotation``s on the host plane; the ``grid.step`` spans that
start inside the window give the host's time per ``run_steps`` call.

The trace is read once per run and the result kept in ``rec``. Where
the run was not traced, or the program publishes no table (a program
without its scopes), every reader returns None.
"""

from __future__ import annotations

import sys
import time
from collections import defaultdict
from pathlib import Path
from runpy import run_path

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / ".trace"  # run.py's
WINDOW = "bench:window"
STEP_SPAN = "grid.step"
UNSCOPED = "unscoped"
PLAN_PHASE_GAUGE = "dccrg_plan_phase_seconds"
PLAN_PHASES = ("partition", "classify", "tables", "fields")
TOP = 20

trace = run_path(str(HERE / "trace.py"))


def program_telemetry():
    """The program's telemetry module, where the run imported it."""
    return sys.modules.get("dccrg_tpu.telemetry")


def extract(path: Path, device_line=trace["tpu_ops"], keep_op=lambda name: True):
    """{"devices": {key: [[(name, start_ns, end_ns), ...] per line]},
    "window": [(start_ns, end_ns), ...], "steps": [(start_ns, end_ns),
    ...]} from one ``.xplane.pb``: device ops as ``trace.extract`` reads
    them, the ``bench:window`` span and the program's ``grid.step``
    spans on the host plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices = defaultdict(list)
    spans = {WINDOW: [], STEP_SPAN: []}
    for plane in pd.planes:
        for line in plane.lines:
            key = device_line(plane.name, line.name)
            if key is not None:
                devices[key].append([
                    (trace["op_name"](e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in line.events if keep_op(e.name)])
            if plane.name == trace["HOST_PLANE"]:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": dict(devices), "window": spans[WINDOW],
            "steps": spans[STEP_SPAN]}


def split(ops, label):
    """Nanoseconds per label of the union of ``ops`` ((name, start,
    end)): each stretch between two op edges goes in equal parts to the
    ops running in it, so the labels sum to the union's length."""
    edges = sorted((t, d, label(n)) for n, s, e in ops if e > s
                   for t, d in ((s, 1), (e, -1)))
    running = defaultdict(int)
    n_running, prev = 0, None
    out = defaultdict(float)
    for t, d, lab in edges:
        if n_running and t > prev:
            share = (t - prev) / n_running
            for k, c in running.items():
                if c:
                    out[k] += share * c
        running[lab] += d
        n_running += d
        prev = t
    return dict(out)


def reduce(doc, table: dict, device) -> dict:
    """Seconds of ``device``'s busy time in the window per scope, the
    part of it in ops missing from ``table``, the unscoped and missing
    ops that took most time, and the host seconds of each ``grid.step``
    span that starts in the window."""
    if len(doc["window"]) != 1:
        raise RuntimeError(f"expected one window span, found {len(doc['window'])}")
    lo, hi = doc["window"][0]
    ops = trace["clip"]([e for line in doc["devices"][device]
                         for e in trace["leaves"](line)], lo, hi)
    phase_ns = split(ops, lambda n: table.get(n, UNSCOPED))
    by_op = split(ops, lambda n: n)
    missing = {n: t for n, t in by_op.items() if n not in table}
    unscoped = {n: t for n, t in by_op.items()
                if table.get(n, UNSCOPED) == UNSCOPED}

    def top(d):
        return [[n, t * 1e-9] for n, t in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "device": device,
        "busy_s": trace["length"](trace["union"]((s, e) for _, s, e in ops)) * 1e-9,
        "phase_s": {k: v * 1e-9 for k, v in phase_ns.items()},
        "missing_s": sum(missing.values()) * 1e-9,
        "top_missing": top(missing),
        "top_unscoped": top(unscoped),
        "dispatch_s": [(e - s) * 1e-9 for s, e in doc["steps"] if lo <= s < hi],
    }


def read(rec, trace_dir: Path = TRACE_DIR, **extract_kw):
    """The phase reduction of this run's traced window (memoised in
    ``rec``), or None."""
    if "phases" not in rec:
        rec["phases"] = _read(rec, trace_dir, **extract_kw)
    return rec["phases"]


def _read(rec, trace_dir, **extract_kw):
    tr = rec.get("trace")
    telemetry = program_telemetry()
    scopes = getattr(telemetry, "program_scopes", dict)()
    if tr is None or not rec.get("steps") or not scopes:
        return None
    t = time.perf_counter()
    table = {op: scope for ops in scopes.values() for op, scope in ops.items()}
    device = max(tr["per_device"], key=lambda d: d["other_s"])["device"]
    doc = extract(trace["find_xplane"](trace_dir), **extract_kw)
    if device not in doc["devices"]:
        return None  # the trace was reduced with other device lines
    out = reduce(doc, table, device)
    steps = rec["steps"]
    scope_seconds = {m: telemetry.registry().gauge_value(
        "dccrg_scope_table_seconds", module=m) for m in scopes}

    def ms(pairs):
        return ", ".join(f"{k} {1e3 * v / steps:.3f}" for k, v in pairs)

    print(f"phases on device {device} over {steps} steps ({rec.get('calls')} "
          f"calls), ms per step: busy {1e3 * out['busy_s'] / steps:.3f} = "
          f"{ms(sorted(out['phase_s'].items()))}; missing "
          f"{1e3 * out['missing_s'] / steps:.3f}; unscoped ops: "
          f"{ms(out['top_unscoped'])}; missing ops: {ms(out['top_missing'])}; "
          f"{len(out['dispatch_s'])} grid.step spans; "
          f"scope table s {scope_seconds}; plan phases s "
          f"{ {p: plan_phase_s(p) for p in PLAN_PHASES} }; read in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    return out


def ms_per_step(rec, scope: str):
    """Device ms per step in ``scope`` on the read device (0.0 where
    the scope has no ops)."""
    out = read(rec)
    if out is None:
        return None
    return 1e3 * out["phase_s"].get(scope, 0.0) / rec["steps"]


def dispatch_ms_per_call(rec):
    """Mean host ms of the ``grid.step`` spans that start in the window."""
    out = read(rec)
    if out is None or not out["dispatch_s"]:
        return None
    return 1e3 * sum(out["dispatch_s"]) / len(out["dispatch_s"])


def plan_phase_s(phase: str):
    """Seconds of ``phase`` in the newest plan build that ran it (the
    program's ``dccrg_plan_phase_seconds`` gauge), or None."""
    telemetry = program_telemetry()
    get = getattr(getattr(telemetry, "registry", lambda: None)(),
                  "gauge_value", None)
    return None if get is None else get(PLAN_PHASE_GAUGE, phase=phase)
