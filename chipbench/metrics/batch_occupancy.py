"""Scheduler: share of decode slot-steps in the window that carried a live
request (the program's counters: delta active_slot_steps / delta
slot_steps). At a fixed offered rate this is Little's law: live requests =
rate x mean time in the system, so a faster step drains slots sooner and
the share falls."""


def read(rec):
    d = rec["delta"]
    if not d["slot_steps"]:
        return None
    return 100.0 * d["active_slot_steps"] / d["slot_steps"]
