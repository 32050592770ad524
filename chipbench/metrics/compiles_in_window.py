"""Device step: JAX traces and compiles that overlap the window, from the
program's ``compile`` spans (``repro.obs.trace.record_compiles``). Every
shape is warmed before the window, so anything here is a recompile the
measured requests wait for. None without the program's spans, or when
their ring dropped records."""

from chipbench import program_spans


def read(rec):
    got = program_spans.records(rec)
    if got is None:
        return None
    recs, t0, t1 = got
    return sum(1 for r in program_spans.spans(recs, "compile")
               if r["ts"] < t1 and r["ts"] + r["dur"] > t0)
