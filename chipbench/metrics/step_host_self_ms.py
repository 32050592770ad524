"""Scheduler: host time per server step that is not spent blocked on the
device, from the program's spans: the mean, over ``step`` spans that start
in the window, of the step less its ``wait`` children (the blocking reads
of device results), plus the ``stream`` span of token callbacks that
follows it. None without the program's spans, or when their ring dropped
records."""

import bisect

from chipbench import program_spans


def read(rec):
    got = program_spans.records(rec)
    if got is None:
        return None
    recs, t0, t1 = got
    steps = [r for r in program_spans.spans(recs, "step")
             if t0 <= r["ts"] < t1]
    if not steps:
        return None
    waits = program_spans.spans(recs, "wait")
    wait_ts = [r["ts"] for r in waits]
    streams = program_spans.spans(recs, "stream")
    stream_ts = [r["ts"] for r in streams]
    total = 0.0
    for st in steps:
        end = st["ts"] + st["dur"]
        lo = bisect.bisect_left(wait_ts, st["ts"])
        hi = bisect.bisect_right(wait_ts, end)
        total += st["dur"] - sum(w["dur"] for w in waits[lo:hi]
                                 if w["ts"] + w["dur"] <= end)
        i = bisect.bisect_left(stream_ts, end)
        if i < len(streams):
            total += streams[i]["dur"]
    return 1e3 * total / len(steps)
