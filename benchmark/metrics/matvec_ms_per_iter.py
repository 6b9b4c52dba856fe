"""Device ms per BiCG iteration in the solve program's ``dccrg.matvec``
scope (A p0 and transpose(A) p1: the neighbour gather, the kernel and
the write-back), on the device with the most non-collective time
(phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["ms_per_step"](rec, "dccrg.matvec")
