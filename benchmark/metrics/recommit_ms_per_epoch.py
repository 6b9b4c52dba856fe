"""Host ms per adapt epoch in the program's ``grid.recommit`` spans (the
plan rebuild for the new cell set, nested in ``stop_refining``) that
start in the traced window, over its ``amr.adapt`` spans
(host_spans.py)."""

from pathlib import Path
from runpy import run_path


def read(rec):
    spans = run_path(str(Path(__file__).resolve().parents[1] / "host_spans.py"))
    return spans["ms_per_epoch"](rec, "grid.recommit")
