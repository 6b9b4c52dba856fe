"""Seconds from process start to the first timed step: JAX start,
model construction, seeded data, compile and warm-up."""


def read(rec):
    return rec["setup_s"]
