"""Scheduler: median time a request waited in the admission queue, from the
program's ``queue`` spans that end in the window: from its submit, or its
requeue after a preemption, to the start of the admission plan that takes
it. It holds the step in flight and the admissions queued ahead, the part
of the time to first token that is not its own prefill. None without the
program's spans, or when their ring dropped records."""

import numpy as np

from chipbench import program_spans


def read(rec):
    got = program_spans.records(rec)
    if got is None:
        return None
    recs, t0, t1 = got
    waits = [r["dur"] for r in program_spans.spans(recs, "queue")
             if t0 <= r["ts"] + r["dur"] < t1]
    if not waits:
        return None
    return 1e3 * float(np.median(np.asarray(waits, np.float64)))
