#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` names its
configuration (``benchmark/configs/<config>.json``, which names its
driver ``benchmark/drivers/<driver>.py``) and its traffic
(``benchmark/traffic/<traffic>.json``); each metric is read by
``benchmark/metrics/<metric>.py``. A new cell, configuration or metric
is new files and entries; this file does not change.

One process drives the cell's chips. There is no CPU path: a platform
other than ``tpu``, fewer devices than the cell asks for, or a device
kind missing from ``benchmark/peaks.json`` ends the run with exit code 2
and no result line.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the JAX profiler and the result
carries its per-layer metrics, ``device.busy_s``/``window_s`` and a
``breakdown`` of device ops and idle gaps.

``--control 1`` runs the configuration's lower-precision control in
place of the configuration as stated (its file's ``control`` keys
override its own) through this same run and comparison: its ``correct``
has to read false. The driver's runs never pass it.
"""

import time

T0 = time.perf_counter()  # set-up runs from here to the first timed step

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = HERE / ".trace"

# libtpu logs under /tmp/tpu_logs unless told otherwise; the benchmark
# writes nowhere outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the program is imported from the checkout's root; this directory
# leaves the path, so that trace.py cannot shadow the standard library's
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(ROOT))


class NoChip(RuntimeError):
    """The run cannot be measured here: no result is printed."""


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(bench: dict, name: str, base: Path) -> dict:
    """The cell's BENCHMARK.json entry, its configuration and traffic
    files, and the metrics it reports, all by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(base / configs[cell["config"]]["file"])
    traffic = read_json(base / "benchmark" / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def chip_devices(chips: int, peaks: dict):
    """The first ``chips`` TPU devices and their kind's peaks, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not tpu")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                     "benchmark/peaks.json")
    return devs[:chips], peaks[devs[0].device_kind]


def memory_peak(devices):
    """Peak bytes in use on the fullest device (None where the backend
    keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def span(name: str):
    """The harness's host span: a TraceAnnotation around each call into
    the program, so that idle gaps in the device trace can be labelled
    by what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def run(args, bench_path: Path = ROOT / "BENCHMARK.json",
        devices_fn=chip_devices, trace_kw=None) -> int:
    """One run of ``args.workload``. The tests hand in another
    ``devices_fn`` (CPU devices) and ``trace_kw`` (CPU trace lines);
    nothing else steers a run."""
    trace_kw = trace_kw or {}
    base = bench_path.parent
    bench = read_json(bench_path)
    spec = resolve_cell(bench, args.workload, base)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if args.control:
        config = {**config, **config["control"]}
    peaks = read_json(HERE / "peaks.json")["devices"]
    start = {"harness_s": time.perf_counter() - T0}
    t = time.perf_counter()
    devices, peak = devices_fn(int(cell["chips"]), peaks)
    start["backend_s"] = time.perf_counter() - t  # import jax, start the TPUs
    dev = devices[0]
    t = time.perf_counter()
    driver = load_module(base / "benchmark" / "drivers" / f"{config['driver']}.py")

    from dccrg_tpu.compat import use_compile_cache

    use_compile_cache()  # <checkout>/.jax_cache, a fixed path
    start["imports_s"] = time.perf_counter() - t
    rec = {"chips": len(devices), "peak": peak, "seed": args.seed,
           "start_s": time.perf_counter() - T0}

    t = time.perf_counter()
    with span("build"):
        model = driver.build(config, traffic, devices)
    rec["plan_build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with span("load"):
        driver.load(model, args.seed)
    rec["load_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with span("warm"):
        driver.warm(model)
    rec["compile_s"] = time.perf_counter() - t

    tracing = bool(args.trace)
    if tracing:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans are the harness's own
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    rec["setup_s"] = time.perf_counter() - T0
    print("setup {setup_s:.3f} s: start {start_s:.3f}, plan build "
          "{plan_build_s:.3f}, seeded data {load_s:.3f}, compile and warm-up "
          "{compile_s:.3f}".format(**rec), file=sys.stderr)
    print("start {:.3f} s: ".format(rec["start_s"])
          + ", ".join(f"{k[:-2]} {v:.3f}" for k, v in start.items()),
          file=sys.stderr)
    try:
        with span("window"):
            rec.update(driver.window(model, args.seconds, span))
    finally:
        if tracing:
            jax.profiler.stop_trace()
    rec["memory_peak_bytes"] = memory_peak(devices)
    checks = driver.check(model, rec)
    del model
    correct = all(c["ok"] for c in checks.values())

    if tracing:
        t = time.perf_counter()
        rec["trace"] = load_module(HERE / "trace.py").reduce_trace(
            TRACE_DIR, len(devices), **trace_kw)
        print(f"trace reduced in {time.perf_counter() - t:.1f} s",
              file=sys.stderr)
    metrics = {}
    for m in spec["per_layer" if tracing else "end_to_end"]:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"] if correct else rec["attempted"],
              "metrics": metrics, "device": device}
    if tracing:
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result))
    return 0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        return run(args)
    except NoChip as e:
        print(f"no measurement: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
