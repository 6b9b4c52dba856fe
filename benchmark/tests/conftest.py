"""CPU checks of the benchmark harness: 4 virtual CPU devices, tiny
cells defined only by the files in ``data/``.

    python -m pytest benchmark/tests -q

The flags must be set before jax is imported anywhere."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
DATA = HERE / "data"
sys.path.insert(0, str(BENCH.parent))  # the program, from the checkout

# the CPU backend has no device planes: its XLA ops run on host
# threads named tf_XLA*, all counted as one device here
CPU_RUNTIME = ("ThunkExecutor", "ThreadpoolListener", "Rendezvous",
               "PjRtCpuExecutable", "Handle inputs", "CommonPjRtClient",
               "Wait", "InvokeRendezvous", "end: ")


def cpu_line(plane_name, line_name):
    if plane_name == "/host:CPU" and line_name.startswith("tf_XLA"):
        return "cpu"
    return None


def cpu_op(name):
    return not name.startswith(CPU_RUNTIME)


CPU_TRACE = {"device_line": cpu_line, "keep_op": cpu_op}
CPU_PEAK = {"hbm_bytes_per_s": 1e11, "bf16_flops_per_s": 1e12, "hbm_bytes": 1e10}


def cpu_devices(chips, peaks):
    return jax.devices()[:chips], CPU_PEAK


def load(path):
    from importlib import util

    spec = util.spec_from_file_location("bench_" + path.stem, path)
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def run_mod():
    return load(BENCH / "run.py")


# the tiny cells are judged by the committed limits and control
COMMITTED = BENCH / "configs" / "advection3d-uniform-512.json"


@pytest.fixture
def cell_tree(tmp_path):
    """A checkout-shaped tree that holds only the tiny cells' files:
    BENCHMARK.json, configs (with the committed configuration's limit
    and control), traffic, and the real drivers."""
    (tmp_path / "benchmark" / "configs").mkdir(parents=True)
    (tmp_path / "benchmark" / "traffic").mkdir()
    shutil.copy(DATA / "bench.json", tmp_path / "BENCHMARK.json")
    committed = json.loads(COMMITTED.read_text())
    for f in DATA.glob("tiny-*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(limit=committed["limit"], control=committed["control"])
        (tmp_path / "benchmark" / "configs" / f.name).write_text(json.dumps(cfg))
    for f in DATA.glob("tiny_*.json"):
        shutil.copy(f, tmp_path / "benchmark" / "traffic" / f.name)
    (tmp_path / "benchmark" / "drivers").symlink_to(BENCH / "drivers")
    return tmp_path


@pytest.fixture
def run_cell(run_mod, cell_tree, capsys):
    """Run one tiny cell through run.run on CPU devices; return its
    result line."""
    def go(workload, trace=0, seed=123456789012, seconds=0.5, control=0):
        args = run_mod.parse_args([
            "--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--control", str(control)])
        capsys.readouterr()
        rc = run_mod.run(args, bench_path=cell_tree / "BENCHMARK.json",
                         devices_fn=cpu_devices, trace_kw=CPU_TRACE)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])
    return go
