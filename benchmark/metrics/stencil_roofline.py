"""Share of the HBM roofline reached by one ``run_steps`` call, on the
slowest device.

Least time: the bytes that any implementation of the call must move on
that device, its stepped field read once and written once in its
storage dtype (``least_bytes_per_call``, from the driver), over the
chip's HBM bandwidth (peaks.json). Time: the core's time in
non-collective ops (its ``XLA Ops`` line) in the traced window over
the calls made in it. The flop bound is far lower (about 30 flops per cell-update against
8 bytes: 20 us per 512^3 call at 197 TFLOP/s, against 1.31 ms for the
bytes), so bytes bound it.
"""


def read(rec):
    tr = rec.get("trace")
    if tr is None or not rec.get("calls"):
        return None
    slow = max(range(len(tr["per_device"])),
               key=lambda i: tr["per_device"][i]["other_s"])
    device_s = tr["per_device"][slow]["other_s"] / rec["calls"]
    if device_s <= 0:
        return None
    least_s = rec["least_bytes_per_call"][slow] / rec["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
