"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the XLA op intervals / window), averaged over chips."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
