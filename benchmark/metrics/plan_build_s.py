"""Seconds in the model's public constructor (and, for a solver, its
prepare): the grid plan and geometry built on the host."""


def read(rec):
    return rec["plan_build_s"]
