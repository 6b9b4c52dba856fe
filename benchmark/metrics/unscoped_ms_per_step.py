"""Device ms per step in ops of no ``dccrg.*`` scope (the loop's own
ops, set-up of the call outside the loop, ops missing from the
program's table), on the device with the most non-collective time
(phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["ms_per_step"](rec, "unscoped")
