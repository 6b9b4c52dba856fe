"""Seconds of the plan build's ``classify`` phase (outer rows, local
and ghost id lists per device), from the program's
``dccrg_plan_phase_seconds`` gauge after the run (phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["plan_phase_s"]("classify")
