"""Cells advanced per second over the window: cells x steps finished,
over the seconds from the first call to the sync that ends the window."""


def read(rec):
    return rec["cell_updates"] / rec["window_s"]
