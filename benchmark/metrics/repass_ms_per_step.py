"""Device ms per step in the step program's ``dccrg.repass`` scope (the
overlap's outer re-pass and its scatter into the result), on the device
with the most non-collective time (phases.py). 0.0 where there is no
re-pass."""

from pathlib import Path
from runpy import run_path


def read(rec):
    phases = run_path(str(Path(__file__).resolve().parents[1] / "phases.py"))
    return phases["ms_per_step"](rec, "dccrg.repass")
