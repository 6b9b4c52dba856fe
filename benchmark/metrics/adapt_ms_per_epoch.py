"""Mean host ms of the program's ``amr.adapt`` spans (one per
``AmrAdvection.adapt``: the criterion stencil, the flag pull and pass,
``stop_refining`` with its plan rebuild, the projection and the static
fields' refresh) that start in the traced window (host_spans.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    spans = run_path(str(Path(__file__).resolve().parents[1] / "host_spans.py"))
    return spans["ms_per_epoch"](rec, "amr.adapt")
