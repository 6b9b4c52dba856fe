#!/usr/bin/env python
"""Geometry lookup micro-benchmark.

The reference's only in-tree performance numbers are geometry lookup
throughputs (tests/geometry README, recorded in BASELINE.md):

  Cartesian  cell size lookup:   1.24-1.39 s / 1e8 cells  (~7.7e7 /s)
  Cartesian  cell position:      3.7-4.79  s / 1e8 cells  (~2.4e7 /s)
  Stretched  cell size lookup:   3.6-4.1   s / 1e8 cells  (~2.6e7 /s)
  Stretched  cell position:      7.99-11.36 s / 1e8 cells (~1.0e7 /s)

(AMD Phenom II X6 1075T, one core.)  This driver measures the same
lookups through dccrg_tpu's vectorized geometry layer and prints one
JSON line per metric with the speedup over the reference midpoint.

Run:  timeout -k 10 600 python bench/geometry_bench.py [n_lookups]

(No safe_devices probe: this bench is pure numpy/ctypes host code and
never touches jax.)
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import ctypes

import numpy as np

# keep large result buffers on the heap so repeated calls reuse pages
# instead of page-faulting a fresh mmap every time (the lookups
# themselves are ~10x faster than the fault-in otherwise)
try:
    ctypes.CDLL("libc.so.6").mallopt(-3, 1 << 30)  # M_MMAP_THRESHOLD
except OSError:
    pass

from dccrg_tpu.geometry import CartesianGeometry, StretchedCartesianGeometry
from dccrg_tpu.mapping import Mapping
from dccrg_tpu.topology import GridTopology

# reference midpoints, lookups per second (BASELINE.md)
REFERENCE = {
    "cartesian size": 1e8 / 1.315,
    "cartesian position": 1e8 / 4.245,
    "stretched size": 1e8 / 3.85,
    "stretched position": 1e8 / 9.675,
}


def measure(fn, ids, trials=5):
    """Best-of-N throughput: the machine is a shared single vCPU, so
    the minimum time is the signal, the rest is neighbor noise."""
    fn(ids)  # warm (allocator + native code paths)
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        fn(ids)
        best = min(best, time.perf_counter() - t0)
    return len(ids) / best


def main(n: int = 10_000_000) -> None:
    # same setup scale as the reference test: refined grid, random ids
    mapping = Mapping((32, 32, 32), maximum_refinement_level=5)
    topology = GridTopology((False, False, False))
    cart = CartesianGeometry(
        mapping, topology, start=(0.0, 0.0, 0.0),
        level_0_cell_length=(1.0, 2.0, 3.0),
    )
    coords = [np.cumsum(np.abs(np.random.default_rng(d).standard_normal(33)) + 0.1)
              for d in range(3)]
    stretched = StretchedCartesianGeometry(mapping, topology, coordinates=coords)

    rng = np.random.default_rng(0)
    lvl = rng.integers(0, 6, size=n)
    # random existing ids: level-major numbering
    ids = np.empty(n, dtype=np.uint64)
    base = 1
    counts = {}
    for l in range(6):
        counts[l] = (base, 32768 * 8**l)
        base += 32768 * 8**l
    for l in range(6):
        m = lvl == l
        lo, span = counts[l]
        ids[m] = lo + rng.integers(0, span, size=int(m.sum()))

    for name, geom in (("cartesian", cart), ("stretched", stretched)):
        for metric, fn in (("size", geom.get_length), ("position", geom.get_center)):
            rate = measure(fn, ids)
            key = f"{name} {metric}"
            print(json.dumps({
                "metric": f"geometry {key} lookups/sec",
                "value": rate,
                "unit": "lookups/s",
                "vs_baseline": rate / REFERENCE[key],
            }))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000)
