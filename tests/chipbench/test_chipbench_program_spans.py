"""The program's spans beside the profiler's trace: the clock anchor, idle
gaps named by span paths, the readers of queue wait, host self time and
in-window compiles, the compile listener they rest on, and the tiny cell
served with its tracer on and off."""

from __future__ import annotations

import collections
import time

import pytest

import chipbench_tiny  # puts the program on the path
from chipbench import harness, program_spans, trace_reduce
from chipbench.run import execute
from repro.obs.trace import Tracer, record_compiles, set_tracer

Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


# ---------------------------------------------------------------------------
# the anchor, with the real profiler
# ---------------------------------------------------------------------------

def test_anchored_tracer_span_lands_on_its_probe(tmp_path):
    import jax
    tr = Tracer().enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            t_open = tr.clock()
            time.sleep(0.005)
            with jax.profiler.TraceAnnotation("probe"):
                t0 = tr.clock()
                time.sleep(0.02)
                tr.span("step", "probe", "engine", t0)
            time.sleep(0.005)
            t_close = tr.clock()
    finally:
        jax.profiler.stop_trace()
    planes = list(jax.profiler.ProfileData.from_file(
        trace_reduce.find_trace(str(tmp_path))).planes)
    window, _, _ = program_spans._planes(planes)
    (probe,) = [ev for p in planes if p.name.startswith("/host:")
                for line in p.lines for ev in line.events
                if ev.name == "probe"]
    anc = program_spans.anchor(
        window, program_spans.program_record(tr, t_open, t_close))
    a, b = anc["anchor_offsets_s"]
    assert abs(a - b) <= program_spans.ANCHOR_TOL_S
    (span,) = tr.records()
    start_ns = (span.ts + anc["shift_s"]) * 1e9
    assert abs(start_ns - probe.start_ns) < 1e6
    assert abs(start_ns + span.dur * 1e9
               - (probe.start_ns + probe.duration_ns)) < 1e6


# ---------------------------------------------------------------------------
# idle gaps named by the program's spans (a small synthetic timeline)
# ---------------------------------------------------------------------------

SHIFT_S = -100.0       # profiler clock minus tracer clock


def _t(ns):
    """A profiler timestamp on the tracer's clock."""
    return ns * 1e-9 - SHIFT_S


def _span(name, s_ns, e_ns, track="engine"):
    return {"ts": _t(s_ns), "kind": "span", "cat": "step", "name": name,
            "track": track, "dur": (e_ns - s_ns) * 1e-9, "args": {}}


def _timeline():
    host = Plane("/host:CPU", [Line("python", [
        Ev("chipbench.window", 1000, 12000, []),
        Ev("chipbench.step", 1000, 7500, []),
        Ev("chipbench.wait", 12000, 1000, []),
    ])])
    ops = [Ev("%fusion.1 = f32[8] fusion()", s, e - s, [])
           for s, e in [(1000, 3000), (5000, 7000), (9000, 10000),
                        (11500, 12000)]]
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops)])
    records = [_span("step", 1000, 8500), _span("admit", 1000, 1100),
               _span("decode", 1200, 4500), _span("wait", 2500, 4500),
               _span("commit", 7500, 8500), _span("stream", 8600, 8900),
               _span("compile", 10500, 11000, track="host"),
               dict(_span("queue", 0, 9000, track="scheduler"),
                    cat="sched")]
    program = {"records": records, "window": [_t(1000), _t(13000)],
               "dropped": 0}
    return [host, dev], program


def test_idle_gaps_are_named_by_the_innermost_program_span():
    planes, program = _timeline()
    r = program_spans.attribute(planes, program)
    assert r["program_anchored"] is True
    assert r["anchor_offsets_s"] == [pytest.approx(SHIFT_S)] * 2
    # gaps: 3000-5000 in the decode's wait, 7000-9000 in the commit,
    # 10000-11500 in a compile, 12000-13000 with no program span open
    assert r["idle_by_span"] == {
        "step/decode/wait": pytest.approx(2000e-9),
        "step/commit": pytest.approx(2000e-9),
        "compile": pytest.approx(1500e-9),
        "wait": pytest.approx(1000e-9)}
    assert [g[0] for g in r["idle_gaps"]] == [
        "step/decode/wait", "step/commit", "compile", "wait"]


def test_disagreeing_anchors_keep_the_harness_names(capsys):
    planes, program = _timeline()
    program["window"][1] += 2e-3
    r = program_spans.attribute(planes, program)
    assert r["program_anchored"] is False
    a, b = r["anchor_offsets_s"]
    assert a - b == pytest.approx(2e-3)
    assert r["idle_by_span"] == {"step": pytest.approx(4000e-9),
                                 "host": pytest.approx(1500e-9),
                                 "wait": pytest.approx(1000e-9)}
    assert "disagree" in capsys.readouterr().err


def test_a_ring_that_dropped_records_names_nothing():
    planes, program = _timeline()
    program["dropped"] = 3
    r = program_spans.attribute(planes, program)
    assert r["program_anchored"] is False
    assert "step/commit" not in r["idle_by_span"]


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def _rec(dropped=0):
    def sp(name, ts, dur):
        return {"ts": ts, "kind": "span", "cat": "step", "name": name,
                "track": "engine", "dur": dur, "args": {}}
    records = [
        # queue stays ending in [10, 20): 6 s and 2 s
        sp("queue", 5, 6), sp("queue", 12, 2), sp("queue", 15, 10),
        sp("queue", 1, 2),
        # a step before the window, then two in it
        sp("step", 9, 0.5), sp("wait", 9.1, 0.2), sp("stream", 9.6, 0.1),
        sp("step", 10, 2), sp("wait", 10.5, 1), sp("stream", 12.1, 0.5),
        sp("step", 13, 3), sp("wait", 13.5, 0.5), sp("wait", 14.5, 1),
        sp("stream", 16.2, 0.3),
        # compiles: two overlap the window
        sp("compile", 5, 6), sp("compile", 19.5, 2), sp("compile", 21, 1),
        sp("compile", 2, 1),
    ]
    return {"program": {"records": records, "window": [10.0, 20.0],
                        "dropped": dropped}}


@pytest.mark.parametrize("name,want", [
    ("queue_wait_p50_ms", 4000.0),
    ("step_host_self_ms", 1e3 * ((2 - 1 + 0.5) + (3 - 1.5 + 0.3)) / 2),
    ("compiles_in_window", 2),
])
def test_program_span_reader(name, want):
    read = harness.load_reader(name)
    assert read(_rec()) == pytest.approx(want)
    assert read(_rec(dropped=1)) is None
    assert read({"trace": None}) is None


# ---------------------------------------------------------------------------
# the compile listener
# ---------------------------------------------------------------------------

def test_record_compiles_until_unregistered():
    import jax
    import jax.numpy as jnp
    tr = Tracer().enable()
    stop = record_compiles(tr)
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
    finally:
        stop()
    comp = [r for r in tr.records() if r.cat == "compile"]
    assert {r.args["event"] for r in comp} == {"jaxpr_trace_duration",
                                               "backend_compile_duration"}
    assert all(r.name == "compile" and r.track == "host" and r.dur >= 0
               for r in comp)
    n = len(tr)
    jax.jit(lambda x: x - 2)(jnp.ones(5))
    assert len(tr) == n


# ---------------------------------------------------------------------------
# the tiny cell served with the program's tracer on, and off
# ---------------------------------------------------------------------------

SPAN_READERS = ("queue_wait_p50_ms", "step_host_self_ms",
                "compiles_in_window")


@pytest.fixture
def fresh_tracer():
    """A process-wide tracer of the default size for one test: the server
    takes it as its own, and no other test sees what it records."""
    tracer = Tracer()
    prev = set_tracer(tracer)
    yield tracer
    set_tracer(prev)


def _serve(tmp_path, monkeypatch, traced):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    cell = chipbench_tiny.cell("gelu")
    cfg = harness.model_config(cell)
    params = harness.served_params(cell, cfg, {})
    server = harness.make_server(params, cfg, cell)
    harness.warm_shapes(server, cell, cfg.vocab)
    reqs = harness.build_requests(cell, 2 ** 31 + 5, 2.0, cfg.vocab)
    trace_dir = str(tmp_path / "trace") if traced else None
    run = harness.serve(server, reqs, open_at=cell.params["warmup_s"],
                        seconds=2.0, trace_dir=trace_dir, tail_s=10.0)
    return server, cell, run


def test_traced_serve_keeps_the_programs_spans(tmp_path, monkeypatch,
                                               fresh_tracer):
    server, cell, run = _serve(tmp_path, monkeypatch, traced=True)
    assert server.batcher.tracer.enabled is False
    rec = harness.window_record(cell, run)
    prog = rec["program"]
    assert prog is run["program"] and prog["dropped"] == 0
    t0, t1 = prog["window"]
    steps = [r for r in program_spans.spans(prog["records"], "step")
             if t0 <= r["ts"] < t1]
    assert len(steps) >= 5
    for name in SPAN_READERS:
        assert harness.load_reader(name)(rec) is not None, name
    assert harness.load_reader("step_host_self_ms")(rec) > 0
    # the tracer's window stamps sit on the profiler's window event
    planes = trace_reduce.load_planes(
        trace_reduce.find_trace(str(tmp_path / "trace")))
    window, _, _ = program_spans._planes(planes)
    assert program_spans.anchor(window, prog)["shift_s"] is not None
    assert rec["delta"]["steps"] == len(
        [s for s in run["steps"] if run["open"]["t"] <= s[0]
         < run["close"]["t"]])


def test_untraced_serve_leaves_the_tracer_off(tmp_path, monkeypatch,
                                              fresh_tracer):
    server, cell, run = _serve(tmp_path, monkeypatch, traced=False)
    assert fresh_tracer.enabled is False and len(fresh_tracer) == 0
    assert "program" not in run
    rec = harness.window_record(cell, run)
    assert "program" not in rec
    for name in SPAN_READERS:
        assert harness.load_reader(name)(rec) is None, name


def test_traced_run_reports_the_span_metrics(tmp_path, monkeypatch,
                                             fresh_tracer):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    cell = chipbench_tiny.cell("gelu")
    res = execute(cell, seed=2 ** 31 + 23, seconds=2.0, trace=True,
                  t_start=time.time())
    assert res["correct"] is True
    assert fresh_tracer.dropped == 0 and fresh_tracer.enabled is False
    spans = [m["name"] for m in cell.per_layer
             if m["source"] == "program_span"]
    assert {"step_host_self_ms", "compiles_in_window"} <= set(spans)
    for name in spans:
        assert name in res["metrics"], name
    assert res["metrics"]["compiles_in_window"]["unit"] == "count"
