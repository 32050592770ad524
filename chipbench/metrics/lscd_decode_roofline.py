"""Kernels: the decode step's Pallas (LSCD) kernels against the HBM
roofline, from the traced window. The decode step is the compiled program
with Pallas kernels that ran most often (once per server step); its
kernels' HBM bytes (operands and results outside on-chip memory, read from
the instruction shapes the trace names) over the peak bandwidth, divided by
their device time. At decode widths these kernels are bound by bytes, not
operations (N = slots <= 128)."""

from chipbench import peaks


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("kernels"):
        return None
    k = max(tr["kernels"].values(), key=lambda v: v["runs"])
    if k["seconds"] <= 0 or not k["hbm_bytes"]:
        return None
    bw = peaks.for_kind(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * k["hbm_bytes"] / bw / k["seconds"]
