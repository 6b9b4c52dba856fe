"""Mean host ms of the program's ``grid.step`` spans (one per
``run_steps`` call: program lookup and dispatch) that start in the
traced window, read from the trace's host plane (phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["dispatch_ms_per_call"](rec)
