"""Device step: time per batched decode launch (the program's
decode_time_s counter, which ends in the sampled tokens' device-to-host
copy, over the window's steps that decoded)."""


def read(rec):
    if not rec["decode_launches"]:
        return None
    return 1e3 * rec["delta"]["decode_time_s"] / rec["decode_launches"]
