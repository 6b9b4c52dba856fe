"""The program's host spans in the traced window.

While a profiler session records, every ``telemetry.span`` of the
program is a ``TraceAnnotation`` on the host plane, on the device
trace's clock. This reads the spans that start inside ``bench:window``,
by name, from the run's ``.xplane.pb`` in one pass, and keeps the
result in ``rec``. Where the run was not traced, or its trace has no
single window, every reader returns None.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from runpy import run_path

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / ".trace"  # run.py's
WINDOW = "bench:window"

trace = run_path(str(HERE / "trace.py"))


def durations(rec, trace_dir: Path = TRACE_DIR):
    """{span name: [seconds of each span that starts in the window]}
    of the host plane (memoised in ``rec``), or None."""
    if "host_spans" not in rec:
        rec["host_spans"] = _read(rec, trace_dir)
    return rec["host_spans"]


def _read(rec, trace_dir):
    if rec.get("trace") is None:
        return None
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(trace["find_xplane"](trace_dir)))
    window, events = [], []
    for plane in pd.planes:
        if plane.name != trace["HOST_PLANE"]:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window.append((e.start_ns, e.start_ns + e.duration_ns))
                else:
                    events.append((e.name, e.start_ns, e.duration_ns))
    if len(window) != 1:
        return None
    lo, hi = window[0]
    out = defaultdict(list)
    for name, start, dur in events:
        if lo <= start < hi:
            out[name].append(dur * 1e-9)
    return dict(out)


def ms_per_epoch(rec, name: str, epoch: str = "amr.adapt"):
    """Host ms of the spans ``name`` in the window over the number of
    ``epoch`` spans in it (None where there is no such epoch)."""
    spans = durations(rec)
    if not spans or not spans.get(epoch):
        return None
    return 1e3 * sum(spans.get(name, ())) / len(spans[epoch])
