"""Device ms per BiCG iteration in the solve program's ``dccrg.dot``
scope (the three global dot products of an iteration, and the initial
ones), on the device with the most non-collective time (phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["ms_per_step"](rec, "dccrg.dot")
