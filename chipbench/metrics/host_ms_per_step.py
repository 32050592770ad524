"""Scheduler: host time per server step that is neither admission nor
decode -- the harness's clock around ``server.step()`` in the window, minus
the program's own admit_time_s + decode_time_s counters (which end in a
device sync), per step."""


def read(rec):
    if not rec["steps_in_window"]:
        return None
    d = rec["delta"]
    other = rec["host_step_s"] - d["admit_time_s"] - d["decode_time_s"]
    return 1e3 * other / rec["steps_in_window"]
