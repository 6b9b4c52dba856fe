"""Share of the traced window in which the core ran no operation,
averaged over the devices used, each read from its ``XLA Ops`` line
(trace.py)."""


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
