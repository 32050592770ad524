"""Model: the whole step's share of the chip's bf16 peak -- the window's
required operations over the device's busy time in the traced window times
the peak. Required operations (chipbench/flops.py) count every token the
window emitted: its prompt's prefill for a first token, one decode position
otherwise -- the kept weights of the sparse projections, the dense logits
head for the sampled position, attention at each position's real context.
Padding, compute-as-dense tile expansion and recomputation do not count.
At a fixed offered load the required operations are fixed by the traffic,
so only the device time they took moves this share."""

from chipbench import peaks


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["busy_s"] <= 0 or not rec["required_flops"]:
        return None
    peak = peaks.for_kind(rec["device_kind"])["bf16_flops"]
    return 100.0 * rec["required_flops"] / (tr["busy_s"] * peak)
