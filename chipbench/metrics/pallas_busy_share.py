"""Kernels: share of the device's busy time in the traced window spent in
Pallas kernels (the custom-call ops). In the sparse cells these are the
LSCD kernels, so a faster LSCD kernel lowers the share and a slower one, or
XLA work moved into Pallas, raises it."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0 or tr["pallas_s"] <= 0:
        return None
    return 100.0 * tr["pallas_s"] / tr["busy_s"]
