"""The comparison that decides ``correct`` fails when the timed path is
broken underneath, and fails on the control (``--control 1``: the
configuration's bfloat16 storage), all at the committed limits.

Each fault is planted in the program with monkeypatch and the rest of
a run goes through as usual, on the CPU in place of the chip."""

import jax
import pytest

from dccrg_tpu.grid import Grid


def unchanged_step(monkeypatch):
    """A step that returns its state unchanged."""
    monkeypatch.setattr(Grid, "run_steps", lambda self, *a, **k: None)


def half_left_out(monkeypatch):
    """Half of each device's rows left out of the step: they keep the
    values they had before it."""
    orig = Grid.run_steps

    def run_steps(self, kernel, fields_in, fields_out, *a, **k):
        before = self.data["density"]
        orig(self, kernel, fields_in, fields_out, *a, **k)
        half = before.shape[1] // 2
        self.data["density"] = self.data["density"].at[:, :half].set(before[:, :half])

    monkeypatch.setattr(Grid, "run_steps", run_steps)


def altered_step(monkeypatch):
    """One cell of the stepped state altered where it is produced."""
    orig = Grid.run_steps

    def run_steps(self, kernel, fields_in, fields_out, *a, **k):
        orig(self, kernel, fields_in, fields_out, *a, **k)
        self.data["density"] = self.data["density"].at[0, 37].add(1e-3)

    monkeypatch.setattr(Grid, "run_steps", run_steps)


def no_exchange(monkeypatch):
    """The halo exchange between devices left out: each device gets
    back what it sent."""
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)


@pytest.mark.parametrize("workload", ["tiny.advection.1dev", "tiny.advection.4dev"])
@pytest.mark.parametrize("fault", [unchanged_step, half_left_out, altered_step])
def test_fault_is_not_correct(run_cell, monkeypatch, workload, fault):
    fault(monkeypatch)
    res = run_cell(workload)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_exchange_fault_is_not_correct(run_cell, monkeypatch):
    """The flux has no z-face term (vz = 0), so in z slabs the final
    density does not see a lost exchange; the ghost rows, which the
    check compares with the reference one step back, do."""
    no_exchange(monkeypatch)
    res = run_cell("tiny.advection.4dev")
    assert res["checks"]["max_abs_err"]["value"] <= res["checks"]["max_abs_err"]["limit"]
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["tiny.advection.1dev", "tiny.advection.4dev"])
def test_control_is_not_correct(run_cell, workload):
    """The control, through run.py's own comparison."""
    res = run_cell(workload, control=1)
    assert res["correct"] is False
