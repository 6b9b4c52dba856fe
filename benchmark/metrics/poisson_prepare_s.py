"""Seconds of the Poisson solver's ``prepare`` (the host pass over the
face-neighbour lists that classifies cells and computes the geometry
factors, and their upload), from the program's
``dccrg_plan_phase_seconds{phase="poisson_prepare"}`` gauge after the
run (phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["plan_phase_s"]("poisson_prepare")
