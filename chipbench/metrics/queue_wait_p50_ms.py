"""Scheduler: median time a request waited in the program's admission
queue, from its ``queue`` spans that end in the window: from its submit, or
its requeue after a preemption, to the start of the admission plan that
takes it. The harness submits only between steps, on the thread that
steps, so a request the step in flight keeps waiting waits in the
harness's generator (``rec["lateness_s"]``), not here: with free slots the
span holds the submit-to-plan bookkeeping alone (~25 us on a TPU v5e host) and
cannot move with the time to first token. It is not in ``BENCHMARK.json``
for that reason. None without the program's spans, or when their ring
dropped records."""

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
