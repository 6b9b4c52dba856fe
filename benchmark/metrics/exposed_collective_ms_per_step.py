"""Milliseconds per step in which the core is held by a collective op,
on the device where that is largest. Read from each device's ``XLA
Ops`` line alike (on a v5e the time shows in the collective-permute-start
ops); the async start-to-done spans, recorded on device 0 alone, are not
read (trace.py). None where no device spent time in a collective."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or not rec.get("steps"):
        return None
    worst = max(d["collective_s"] for d in tr["per_device"])
    return 1e3 * worst / rec["steps"] if worst > 0 else None
