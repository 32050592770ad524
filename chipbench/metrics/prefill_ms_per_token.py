"""Device step: admission time per real prompt token (the program's
admit_time_s counter over its prefill_tokens counter, in the window)."""


def read(rec):
    d = rec["delta"]
    if not d["prefill_tokens"]:
        return None
    return 1e3 * d["admit_time_s"] / d["prefill_tokens"]
