"""Seconds of the warm-up call, which compiles (or loads from the
persistent cache) the one program the window runs."""


def read(rec):
    return rec["compile_s"]
