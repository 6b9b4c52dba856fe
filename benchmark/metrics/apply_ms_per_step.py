"""Device ms per step in the step program's ``dccrg.apply`` scope (the
write of the result into the state), on the device with the most
non-collective time (phases.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["ms_per_step"](rec, "dccrg.apply")
