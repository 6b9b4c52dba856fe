"""Reduce one profiler trace (the ``.xplane.pb`` of a ``--trace 1``
run) to what the per-layer metrics read.

A device is a plane ``/device:TPU:<i>``. Its operations are the events
of its ``XLA Ops`` line: the ops the core runs one after another,
collectives included (on a v5e the core's time in a halo exchange
shows in its ``collective-permute-start`` ops; the ``-done`` ops read
about 0). The ``Async XLA Ops`` line, the
asynchronous ops from start to done, is not read: on four chips it is
recorded for device 0 only, and its spans say how far apart the
compiler scheduled a start and its done, not what the transfer cost.
So every device is read the same way. An event's name is its HLO
instruction (``%fusion.3 = f32[...] fusion(...)``); the reduction keeps
the part before `` = ``. Only leaf events count: an event that encloses
another event of the same line (a control-flow op around its body) is
dropped, so that no time is counted twice. The harness's host spans
are the events named ``bench:<what>`` on the host plane; the span
``bench:window`` bounds the measured window, and every other span
labels the idle gaps that fall inside it.

What comes out, per device and averaged over the devices used:
busy seconds (the union of the core's op intervals inside the window),
the same for collective ops (the core held by the exchange) and for
the others, the ops that took most time, and the idle gaps summed by
the host span that was open in them.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
# the op kinds that move data between chips, as HLO names them (the
# CPU backend calls a collective-permute "ppermute")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|psum|send|recv|collective", re.IGNORECASE)
TOP = 10


def tpu_ops(plane_name: str, line_name: str):
    """The device key of a TPU core's op line, or None."""
    m = DEVICE_PLANE.match(plane_name)
    return int(m.group(1)) if m and line_name == OPS_LINE else None


def op_name(name: str) -> str:
    """``%fusion.3 = f32[8] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def extract(path: Path, device_line=tpu_ops, keep_op=lambda name: True):
    """{"devices": {key: [[(name, start_ns, end_ns), ...] per line]},
    "spans": [(name, start_ns, end_ns), ...]} from one ``.xplane.pb``.
    A TPU core has one line; the CPU backend in the tests has a line per
    thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices = defaultdict(list)
    spans = []
    for plane in pd.planes:
        for line in plane.lines:
            key = device_line(plane.name, line.name)
            if key is not None:
                devices[key].append([
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if keep_op(e.name)])
            if plane.name == HOST_PLANE:
                spans.extend(
                    (e.name[len(SPAN_PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": dict(devices), "spans": spans}


def leaves(events):
    """Drop events that enclose a later event of the same line."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[1] < e[2] and nxt[2] <= e[2]:
            continue  # a parent of the next event
        out.append(e)
    return out


def union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def label_gaps(gaps, spans):
    """Idle seconds per host span: each gap is cut at the span edges
    inside it, and each piece goes to the shortest span that covers it
    ("no span" where none does)."""
    totals = defaultdict(float)
    for s, e in gaps:
        near = [sp for sp in spans if sp[2] > s and sp[1] < e]
        cuts = sorted({s, e, *(t for sp in near for t in sp[1:] if s < t < e)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [sp for sp in near if sp[1] <= mid <= sp[2]]
            name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
                else "no span"
            totals[name] += (b - a) * 1e-9
    return totals


def reduce(doc, n_devices: int) -> dict:
    """The per-device and averaged reduction of an ``extract`` result
    over the ``window`` span."""
    windows = [sp for sp in doc["spans"] if sp[0] == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one window span, found {len(windows)}")
    _, lo, hi = windows[0]
    keys = sorted(doc["devices"])[:n_devices]
    if not keys:
        raise RuntimeError("the trace has no device operations")
    inner = [sp for sp in doc["spans"] if sp[0] != "window"]
    per_device, op_time, gap_time = [], defaultdict(float), defaultdict(float)
    for k in keys:
        ops = clip([e for line in doc["devices"][k] for e in leaves(line)], lo, hi)
        busy = union((s, e) for _, s, e in ops)
        coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        other = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        per_device.append({
            "device": k, "ops": len(ops),
            "busy_s": length(busy) * 1e-9,
            "other_s": length(other) * 1e-9,
            "collective_s": length(coll) * 1e-9,
        })
        for n, s, e in ops:
            op_time[n] += (e - s) * 1e-9 / len(keys)
        gaps = subtract([(lo, hi)], busy)
        for name, sec in label_gaps(gaps, inner).items():
            gap_time[name] += sec / len(keys)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "per_device": per_device,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }


def reduce_trace(trace_dir: Path, n_devices: int, **extract_kw) -> dict:
    return reduce(extract(find_xplane(trace_dir), **extract_kw), n_devices)
