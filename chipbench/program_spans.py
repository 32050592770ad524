"""The program's own spans (``repro.obs.trace``) set beside a profiler trace.

With the program's tracer on, the serving step is a tree of spans on the
tracer's clock: ``step`` (``admit``, ``prefill``, ``sample``, ``stage``,
``decode``/``mixed``/``verify``, ``wait``, ``commit``), then ``stream``;
``queue`` spans on the scheduler track and ``compile`` spans on the host
track. A traced run keeps them as ``rec["program"]``:

    {"records": [TraceRecord.as_dict(), ...],
     "window": [t_open, t_close],      # tracer clock, read right after the
     "dropped": n}                     # window annotation opens / before
                                       # it closes

The profiler's events sit on its own session clock. The two window stamps,
set against the ``chipbench.window`` event, give the offset between the
clocks at each end; when they agree within ``ANCHOR_TOL_S`` the program's
spans are shifted by their mean and each idle gap of the device is named
by the path of program spans open at its midpoint (``step/decode/wait``,
``step/commit``, ``stream``, ``compile``). Where no program span is open,
or the offsets disagree, a gap takes the harness's ``chipbench.*`` name,
else ``host``. A ring that dropped records is refused, not read.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from chipbench import trace_reduce
from chipbench.harness import log

# The two ends of the window may disagree by this much before the
# program's spans are refused a place on the profiler's timeline.
ANCHOR_TOL_S = 1e-3
# Tracks whose spans say what the host was doing (not a request's stay).
HOST_TRACKS = ("engine", "host")


def program_record(tracer, t_open: float, t_close: float) -> Dict:
    """What a traced run keeps of the tracer: ``rec["program"]``."""
    return {"records": [r.as_dict() for r in tracer.records()],
            "window": [t_open, t_close], "dropped": tracer.dropped}


def records(rec: Dict) -> Optional[Tuple[List[Dict], float, float]]:
    """(records, t_open, t_close) on the tracer's clock, or None when the
    run kept no program spans or its ring overflowed."""
    prog = rec.get("program")
    if not prog or prog["dropped"] > 0:
        return None
    t0, t1 = prog["window"]
    return prog["records"], t0, t1


def spans(recs: Iterable[Dict], name: str) -> List[Dict]:
    """The spans called ``name``, by start."""
    return sorted((r for r in recs if r["kind"] == "span"
                   and r["name"] == name), key=lambda r: r["ts"])


def anchor(window_ns: Tuple[int, int], program: Dict) -> Dict:
    """Offsets (profiler clock minus tracer clock, seconds) at the window's
    open and close, and the shift to apply when they agree (else None)."""
    t0, t1 = program["window"]
    at_open = window_ns[0] * 1e-9 - t0
    at_close = window_ns[1] * 1e-9 - t1
    agree = abs(at_open - at_close) <= ANCHOR_TOL_S
    return {"anchor_offsets_s": [at_open, at_close],
            "shift_s": (at_open + at_close) / 2 if agree else None}


def _label(points: List[float], intervals: List[Tuple[float, float, str]],
           path: bool) -> List[Optional[str]]:
    """For each point (sorted), the intervals open at it: their names
    joined outermost first (``path``), else the innermost name alone.
    ``intervals`` are sorted by (start, -end); one sweep over both."""
    out: List[Optional[str]] = []
    open_: List[Tuple[float, float, str]] = []
    j = 0
    for t in points:
        while j < len(intervals) and intervals[j][0] <= t:
            open_.append(intervals[j])
            j += 1
        open_ = [iv for iv in open_ if iv[1] > t]
        if not open_:
            out.append(None)
        elif path:
            out.append("/".join(iv[2] for iv in open_))
        else:
            out.append(open_[-1][2])
    return out


def _planes(planes):
    """The window, the harness's host spans and each device's op
    intervals, as ``trace_reduce.reduce_planes`` reads them."""
    window = None
    host = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(trace_reduce.PREFIX):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == trace_reduce.WINDOW:
                        window = (s, e)
                    else:
                        host.append((s, e, ev.name[len(
                            trace_reduce.PREFIX):]))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices.append([(ev.start_ns,
                                     ev.start_ns + ev.duration_ns)
                                    for ev in line.events])
    return window, host, devices


def attribute(planes, program: Dict) -> Optional[Dict]:
    """Name every idle gap of the window by the program's spans. Returns
    the new keys of a reduced trace (``anchor_offsets_s``,
    ``program_anchored``, ``idle_gaps`` with the top gaps so named,
    ``idle_by_span``: idle seconds per name, averaged over chips), or
    None when the trace holds no window or no device op."""
    window, host, devices = _planes(planes)
    if window is None or not devices:
        return None
    w0, w1 = window
    anc = anchor(window, program)
    shift = anc["shift_s"]
    a, b = anc["anchor_offsets_s"]
    log(f"program clock offsets at the window's open / close: {a:.6f} / "
        f"{b:.6f} s")
    prog: List[Tuple[float, float, str]] = []
    if shift is None:
        log(f"the offsets disagree by more than {ANCHOR_TOL_S} s: idle "
            f"gaps keep the harness's names")
    elif program["dropped"]:
        log(f"the program's ring dropped {program['dropped']} records: "
            f"idle gaps keep the harness's names")
    else:
        for r in program["records"]:
            if r["kind"] == "span" and r["track"] in HOST_TRACKS:
                s = (r["ts"] + shift) * 1e9
                prog.append((s, s + r["dur"] * 1e9, r["name"]))
    gaps: List[Tuple[float, float]] = []
    for ops in devices:
        iv = [c for c in (trace_reduce._clip(s, e, w0, w1) for s, e in ops)
              if c is not None]
        prev = w0
        for s, e in trace_reduce.union(iv) + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] + g[1])
    mids = [(s + e) / 2 for s, e in gaps]
    prog.sort(key=lambda x: (x[0], -x[1]))
    host.sort(key=lambda x: (x[0], -x[1]))
    named = [p or h or "host" for p, h in zip(_label(mids, prog, True),
                                              _label(mids, host, False))]
    n = len(devices)
    by_span: Dict[str, float] = {}
    for name, (s, e) in zip(named, gaps):
        by_span[name] = by_span.get(name, 0.0) + (e - s) * 1e-9 / n
    top = sorted(zip(named, gaps), key=lambda g: g[1][1] - g[1][0],
                 reverse=True)[:trace_reduce.TOP]
    return {"anchor_offsets_s": anc["anchor_offsets_s"],
            "program_anchored": bool(prog),
            "idle_gaps": [[name, (e - s) * 1e-9] for name, (s, e) in top],
            "idle_by_span": dict(sorted(by_span.items(),
                                        key=lambda kv: -kv[1]))}
