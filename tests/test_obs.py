"""Observability plane: tracer, timeline export, metrics, kernel profiling.

DESIGN.md §15 contracts: tracing is off by default and the hot path pays
one flag check when off (overhead guard); under the virtual clock two
replays of the same trace fingerprint — including a chaos FaultPlan —
export byte-identical Perfetto timelines; latency reservoirs are bounded
and deterministically seeded; the metrics registry's three views (JSON /
Prometheus / digest) read live scheduler state; kernel profiling pairs the
roofline prediction with a fenced measurement and invalidates stale
autotune-cache entries.
"""

import copy
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import tiled_csl
from repro.kernels import ops, schedule
from repro.models import transformer
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs.metrics import MetricsRegistry, Reservoir
from repro.obs.trace import TraceRecord, Tracer, get_tracer
from repro.serving import api, faults, loadgen
from repro.serving.config import SchedulerConfig, ServeConfig
from repro.serving.scheduler import SchedulerMetrics


@pytest.fixture(scope="module")
def model():
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _chaos_replay(model, seed=0):
    """One small fault-laden replay on a private tracer; returns
    (records, server, result)."""
    params, cfg = model
    plan = faults.FaultPlan([
        faults.FaultEvent(step=2, kind="step_error", op="decode",
                          attempts=1),
        faults.FaultEvent(step=3, kind="nan_logits", slot=0, op="decode"),
        faults.FaultEvent(step=4, kind="pool_storm", blocks=10, duration=2),
    ])
    trace = loadgen.make_trace(
        seed=seed, n_requests=8, rate=0.8, vocab=cfg.vocab,
        tenants=[loadgen.TenantSpec("obs", suffix_len=(4, 10),
                                    max_new=(6, 10))])
    clock = loadgen.StepClock(dt=1.0)
    tracer = Tracer().enable(clock)
    server = api.StreamingServer(
        params, cfg, n_slots=4, max_len=64, cache_kind="paged",
        block_size=8, n_blocks=16, clock=clock, fault_plan=plan,
        tracer=tracer)
    result = loadgen.replay(server, trace, clock)
    return tracer.records(), server, result


# -- tracer ------------------------------------------------------------------

def test_tracer_off_by_default_and_noop():
    tr = Tracer()
    assert not tr.enabled
    tr.event("sched", "submit", "scheduler", uid=1)
    tr.span("step", "decode", "engine", 0.0, 1.0)
    assert len(tr) == 0 and tr.records() == []


def test_tracer_ring_bounded():
    tr = Tracer(capacity=4).enable()
    for i in range(10):
        tr.event("sched", f"e{i}", "scheduler")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [r.name for r in tr.records()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_virtual_clock_and_span_defaults():
    t = {"now": 5.0}
    tr = Tracer().enable(lambda: t["now"])
    tr.event("sched", "a", "scheduler")
    t["now"] = 8.0
    tr.span("step", "b", "engine", 5.5)          # t1 defaults to clock()
    a, b = tr.records()
    assert a.ts == 5.0 and a.kind == "event" and a.dur == 0.0
    assert b.ts == 5.5 and b.kind == "span" and b.dur == pytest.approx(2.5)


def test_tracer_off_is_never_invoked(model, monkeypatch):
    """Overhead guard: with tracing off, the serving stack never calls into
    the tracer's emission surface or reads its clock — the hot path pays
    one flag check. Covers bucketed admission, chunked admission and
    preemption requeues."""
    def _boom(*a, **k):
        raise AssertionError("tracer emission with tracing off")

    monkeypatch.setattr(Tracer, "event", _boom)
    monkeypatch.setattr(Tracer, "span", _boom)
    monkeypatch.setattr(get_tracer(), "clock", _boom)
    assert not get_tracer().enabled
    params, cfg = model
    server = api.StreamingServer(params, cfg, n_slots=2, max_len=32,
                                 cache_kind="paged", block_size=4,
                                 n_blocks=16)
    rng = np.random.default_rng(0)
    for _ in range(3):
        server.submit(api.GenerationRequest(
            prompt=rng.integers(0, cfg.vocab, 5).astype(np.int64),
            max_new_tokens=4))
    responses = server.run_until_drained()
    assert len(responses) == 3
    for chunked in (False, True):
        server = _small_pool_server(model, chunked=chunked)
        assert len(server.run_until_drained()) == 6
        assert server.metrics.preemptions > 0


class _Ticks:
    """A clock that advances one unit per read: every stamp is distinct, so
    nesting and order checks are strict."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _small_pool_server(model, *, chunked=False, tracer=None):
    """A paged server whose pool is too small for its six requests, so
    some are preempted and requeued."""
    params, cfg = model
    sc = (SchedulerConfig(n_slots=3, max_len=48, chunked_prefill=True,
                          chunk_size=4, chunk_budget=8) if chunked
          else SchedulerConfig(n_slots=3, max_len=48))
    server = api.StreamingServer(params, cfg, config=ServeConfig(
        scheduler=sc, cache_kind="paged", block_size=4, n_blocks=12),
        tracer=tracer)
    rng = np.random.default_rng(0)
    for _ in range(6):
        server.submit(api.GenerationRequest(
            prompt=rng.integers(0, cfg.vocab, 8).astype(np.int64),
            max_new_tokens=16))
    return server


def _inside(inner, outer):
    return outer.ts <= inner.ts and inner.ts + inner.dur <= outer.ts + \
        outer.dur


def test_step_span_tree_of_a_drained_server(model):
    """The serving step is a span tree: one ``step`` per counted step,
    every ``wait`` inside a ``sample``/``decode`` inside its ``step``, one
    ``queue`` span per admission (requeues included) ending before that
    admission, and a ``stream`` span after each step — all on the tracer's
    own clock."""
    clock = _Ticks()
    tracer = Tracer().enable(clock)
    server = _small_pool_server(model, tracer=tracer)
    server.run_until_drained()
    m = server.metrics
    assert m.preemptions > 0
    recs = tracer.records()
    assert tracer.dropped == 0
    assert all(0 < r.ts and r.ts + r.dur <= clock.t for r in recs)
    assert not [r for r in recs if r.name == "tick"]
    spans = {}
    for r in recs:
        if r.kind == "span":
            spans.setdefault(r.name, []).append(r)
    steps = sorted(spans["step"], key=lambda r: r.ts)
    assert len(steps) == m.steps
    assert [r.args["step"] for r in steps] == list(range(1, m.steps + 1))
    assert set(steps[0].args) == {"step", "active", "finished", "queue",
                                  "admit_launches", "blocks_in_use",
                                  "degradation"}
    assert sum(r.args["admit_launches"] for r in steps) == m.prefill_calls
    parents = spans["sample"] + spans["decode"]
    assert len(spans["wait"]) == len(parents)
    for w in spans["wait"]:
        (p,) = [p for p in parents if _inside(w, p)]
        assert sum(_inside(p, st) for st in steps) == 1
    for name in ("admit", "stage", "commit", "prefill", "sample"):
        for r in spans[name]:
            assert sum(_inside(r, st) for st in steps) == 1, name
    assert "wall_us" not in {k for r in spans["decode"] for k in r.args}
    # queue stays: one per admission, preemption requeues included
    admits = [r for r in recs if r.kind == "event" and r.name == "admit"]
    assert len(spans["queue"]) == len(admits) == m.admitted
    assert len(admits) > len({r.args["uid"] for r in admits})
    for uid in {r.args["uid"] for r in admits}:
        qs = [q for q in spans["queue"] if q.args["uid"] == uid]
        ads = [a for a in admits if a.args["uid"] == uid]
        assert len(qs) == len(ads)
        for q, a in zip(sorted(qs, key=lambda r: r.ts),
                        sorted(ads, key=lambda r: r.ts)):
            assert q.ts + q.dur < a.ts
        assert [q.args["resume"] > 0 for q in qs] == \
            [False] + [True] * (len(qs) - 1)
    # the token callbacks follow each step
    streams = sorted(spans["stream"], key=lambda r: r.ts)
    assert len(streams) == len(steps)
    for st, sm in zip(steps, streams):
        assert sm.ts > st.ts + st.dur
    assert sum(r.args["tokens"] for r in streams) == sum(
        r.args["tokens"] for r in recs if r.name == "finish")


# -- replay determinism (the timeline half of the CI latency contract) -------

def test_chaos_replay_timelines_byte_identical(model):
    """Two replays of the same (trace fingerprint, FaultPlan) pair export
    byte-identical Perfetto JSON after normalization."""
    rec1, srv1, res1 = _chaos_replay(model)
    rec2, srv2, res2 = _chaos_replay(model)
    assert len(rec1) > 0
    dump1 = obs_export.dumps_chrome_trace(rec1)
    dump2 = obs_export.dumps_chrome_trace(rec2)
    assert dump1 == dump2
    # the chaos actually fired, so the equality is over a non-trivial run
    assert len(srv1.batcher.faults.fired) >= 3
    assert srv1.batcher.metrics.quarantined >= 1


def test_trace_carries_every_scheduler_transition(model):
    records, server, result = _chaos_replay(model)
    m = server.batcher.metrics
    names = [r.name for r in records if r.kind == "event"]
    assert names.count("admit") == m.admitted
    assert names.count("quarantine") == m.quarantined
    assert names.count("preempt") == m.preemptions
    assert names.count("degradation") == m.degradation_transitions
    assert names.count("retry") == m.step_retries
    fault_kinds = [r.name for r in records if r.cat == "fault"
                   and r.name != "retry"]
    assert len(fault_kinds) == len(server.batcher.faults.fired)
    # engine step spans carry batch-shape args
    decode_spans = [r for r in records
                    if r.kind == "span" and r.name == "decode"]
    assert decode_spans and all("batch" in r.args for r in decode_spans)
    assert all("blocks_touched" in r.args for r in decode_spans)


# -- export ------------------------------------------------------------------

def _mini_records():
    return [
        TraceRecord(2.0, "span", "sched", "req1", "slot1", 3.0,
                    {"uid": 1}),
        TraceRecord(1.0, "event", "sched", "submit", "scheduler",
                    0.0, {"uid": 1}),
        TraceRecord(1.5, "span", "step", "decode", "engine", 0.25, {}),
        TraceRecord(1.0, "event", "kernel", "spmm 128x128x8", "kernel"),
    ]


def test_chrome_trace_structure_and_normalization():
    trace = obs_export.to_chrome_trace(_mini_records())
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    # process_name + one thread_name per track, canonical track order
    assert meta[0]["args"]["name"] == "flash-llm-serve"
    thread_names = [e["args"]["name"] for e in meta[1:]]
    assert thread_names == ["scheduler", "engine", "kernel", "slot1"]
    body = [e for e in evs if e["ph"] != "M"]
    # earliest record normalized to ts=0; integer microseconds
    assert min(e["ts"] for e in body) == 0
    assert all(isinstance(e["ts"], int) for e in body)
    spans = [e for e in body if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"req1", "decode"}
    assert all("dur" in e for e in spans)
    instants = [e for e in body if e["ph"] == "i"]
    assert all(e["s"] == "t" for e in instants)


def test_track_sort_order():
    key = obs_export._track_sort_key
    tracks = ["slot10", "kernel", "slot2", "engine", "aux", "scheduler",
              "slot0"]
    assert sorted(tracks, key=key) == [
        "scheduler", "engine", "kernel", "slot0", "slot2", "slot10", "aux"]


def test_top_spans_ranks_by_duration():
    trace = obs_export.to_chrome_trace(_mini_records())
    top = obs_export.top_spans(trace, n=5)
    assert [s["name"] for s in top] == ["req1", "decode"]
    assert top[0]["track"] == "slot1"
    assert top[0]["dur_us"] == 3_000_000
    assert top[0]["args"] == {"uid": 1}
    assert obs_export.top_spans({"traceEvents": []}) == []


# -- reservoir ---------------------------------------------------------------

def test_reservoir_bounded_and_counts():
    r = Reservoir(capacity=8, seed="x")
    for i in range(100):
        r.append(float(i))
    assert len(r) == 8
    assert r.count == 100
    assert all(0.0 <= v < 100.0 for v in r)
    with pytest.raises(ValueError):
        Reservoir(capacity=0)


def test_reservoir_below_capacity_is_exact():
    r = Reservoir(capacity=2048)
    vals = [float(i) * 0.5 for i in range(50)]
    for v in vals:
        r.append(v)
    assert list(r) == vals
    assert r[10] == vals[10]


def test_reservoir_deterministic_by_seed():
    def fill(key):
        r = Reservoir(capacity=4)
        r.reseed(key)
        for i in range(200):
            r.append(float(i))
        return list(r)

    assert fill("fp:abc") == fill("fp:abc")
    assert fill("fp:abc") != fill("fp:xyz")


def test_reservoir_deepcopy_detached():
    r = Reservoir(capacity=4, seed="k")
    for i in range(10):
        r.append(float(i))
    c = copy.deepcopy(r)
    assert list(c) == list(r) and c.count == r.count
    c.append(99.0)
    assert list(c) != list(r) or c.count != r.count


def test_metrics_as_dict_shape_stable():
    """The Reservoir swap keeps SchedulerMetrics.as_dict consumable: the
    latency fields still quack like sample sequences."""
    m = SchedulerMetrics()
    m.ttft_s.append(1.0)
    m.tpot_s.append(0.5)
    from repro.serving.scheduler import latency_summary
    s = latency_summary(m.ttft_s)
    assert s["n"] == 1 and s["p50"] == 1.0


# -- metrics registry --------------------------------------------------------

def test_registry_views():
    reg = MetricsRegistry()
    state = {"steps": 7, "occ": 0.5}
    res = Reservoir(seed="t")
    for v in (1.0, 2.0, 3.0):
        res.append(v)
    reg.counter("repro_x_steps_total", "1", "Steps", lambda: state["steps"])
    reg.gauge("repro_x_occupancy", "1", "Occupancy", lambda: state["occ"])
    reg.histogram("repro_x_ttft_s", "s", "TTFT", lambda: res)
    snap = reg.snapshot()
    assert snap["repro_x_steps_total"] == 7
    assert snap["repro_x_ttft_s"]["n"] == 3
    assert snap["repro_x_ttft_s"]["p50"] == 2.0
    assert json.loads(reg.to_json()) == snap
    prom = reg.render_prometheus()
    assert "# HELP repro_x_steps_total Steps [unit: 1]" in prom
    assert "# TYPE repro_x_steps_total counter" in prom
    assert "# TYPE repro_x_ttft_s summary" in prom
    assert 'repro_x_ttft_s{quantile="0.5"} 2' in prom
    assert "repro_x_ttft_s_count 3" in prom
    digest = reg.digest()
    assert "x_steps_total=7" in digest
    assert "x_ttft_s_p50=2" in digest
    # live reads: mutate state, views follow
    state["steps"] = 9
    assert reg.snapshot()["repro_x_steps_total"] == 9
    with pytest.raises(ValueError):
        reg.counter("repro_x_steps_total", "1", "dup", lambda: 0)
    with pytest.raises(ValueError):
        reg.register("repro_x_new", "timer", "1", "bad kind", lambda: 0)


def test_registered_scheduler_fields_exist():
    """Every field the registry binds must exist on SchedulerMetrics —
    getattr's default would otherwise silently report 0 forever."""
    m = SchedulerMetrics()
    for field, kind, unit, help_text in obs_metrics._SCHED_FIELDS:
        assert hasattr(m, field), f"_SCHED_FIELDS names missing {field!r}"
    reg = obs_metrics.register_scheduler_metrics(
        MetricsRegistry(), lambda: m)
    for key in obs_metrics.DIGEST_KEYS:
        assert key in reg.names()


def test_scheduler_registry_reads_live_metrics():
    m = SchedulerMetrics()
    reg = obs_metrics.register_scheduler_metrics(MetricsRegistry(),
                                                 lambda: m)
    m.steps = 3
    m.admitted = 2
    m.ttft_s.append(1.5)
    snap = reg.snapshot()
    assert snap["repro_scheduler_steps_total"] == 3
    assert snap["repro_scheduler_admitted_total"] == 2
    assert snap["repro_scheduler_ttft_s"]["p50"] == 1.5


def test_http_exposition_roundtrip():
    m = SchedulerMetrics()
    m.steps = 11
    reg = obs_metrics.register_scheduler_metrics(MetricsRegistry(),
                                                 lambda: m)
    srv = obs_metrics.start_http_server(reg, 0)       # ephemeral port
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            text = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "repro_scheduler_steps_total 11" in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json") as resp:
            snap = json.loads(resp.read().decode())
        assert snap["repro_scheduler_steps_total"] == 11
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        srv.shutdown()


# -- kernel profiling + roofline drift ---------------------------------------

def _small_csl(seed=0, m=128, k=256, sparsity=0.8):
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((m, k)).astype(np.float32)
    dense[rng.random(dense.shape) < sparsity] = 0.0
    return tiled_csl.encode(dense)


def test_profiler_refuses_device_without_published_peaks():
    """A measured (pallas) launch is compared with the measuring device's
    own peaks; a device missing from roofline.DEVICE_PEAKS (here the CPU)
    raises before anything runs."""
    prof = obs_profile.KernelProfiler()
    prof.note_dispatch("spmm", 128, 256, 8, 0.8, 1, 1024, 128, 128,
                       "pallas", schedule.Schedule(128, 128, 8, 1))
    with pytest.raises(ValueError, match="no published peaks"):
        prof.measure(reps=1)


def test_profiler_records_and_measures():
    t = _small_csl()
    b = jnp.ones((256, 8), jnp.float32)
    with obs_profile.profiled(obs_profile.KernelProfiler()) as prof:
        ops.spmm(t, b, backend="interpret")
        ops.spmm(t, b, backend="interpret")       # same shape: one launch
    assert len(prof.launches) == 1
    (key, launch), = prof.launches.items()
    assert prof.dispatch_counts[key] == 2
    assert launch.kind == "spmm" and launch.predicted_s > 0
    rows = prof.measure(reps=1)
    assert len(rows) == 1
    r = rows[0]
    assert r["dispatches"] == 2
    assert r["measured_us"] > 0 and r["predicted_us"] > 0
    assert r["drift"] == pytest.approx(r["measured_us"] / r["predicted_us"])
    # off again: dispatches stop recording
    ops.spmm(t, b, backend="interpret")
    assert prof.dispatch_counts[key] == 2
    table = obs_profile.render_drift_table(rows)
    assert "spmm" in table and "drift" in table
    assert obs_profile.render_drift_table([]).startswith("(no ")


def test_staleness_invalidates_poisoned_cache(tmp_path):
    """A cache entry whose stored timing drifted beyond tolerance is
    invalidated — and stays gone through the merge-on-save cycle — so
    select() falls back to the analytic pick (autotune-cache staleness
    signal, ISSUE acceptance)."""
    t = _small_csl()
    b = jnp.ones((256, 8), jnp.float32)
    with obs_profile.profiled(obs_profile.KernelProfiler()) as prof:
        ops.spmm(t, b, backend="interpret")
    (key, launch), = prof.launches.items()
    cache = schedule.ScheduleCache(str(tmp_path / "tuned.json"))
    # a poisoned entry: right schedule, absurd stored timing (a world that
    # no longer exists — different machine / kernel revision)
    cache.put(launch.cache_key, launch.schedule, measured_us=1e-3)
    cache.save()
    rows = prof.measure(reps=1)
    dropped = prof.apply_staleness(cache, rows, tol=0.5)
    assert dropped == [launch.cache_key]
    assert rows[0]["stale_cache_entry"]["key"] == launch.cache_key
    assert cache.entry(launch.cache_key) is None
    # the invalidation survives merge-on-save (the _dropped set)
    cache.save()
    assert schedule.ScheduleCache(cache.path).entry(launch.cache_key) is None
    # a fresh put() re-registers the key (re-autotune wins)
    cache.put(launch.cache_key, launch.schedule, measured_us=rows[0][
        "measured_us"])
    cache.save()
    assert schedule.ScheduleCache(cache.path).entry(
        launch.cache_key) is not None
    # drift_report composes measure + staleness
    with obs_profile.profiled(obs_profile.KernelProfiler()) as prof2:
        ops.spmm(t, b, backend="interpret")
    rep = prof2.drift_report(reps=1)
    assert rep["n_unique_launches"] == 1 and rep["stale_keys"] == []


def test_fresh_measurement_within_tol_keeps_entry(tmp_path):
    t = _small_csl()
    b = jnp.ones((256, 8), jnp.float32)
    with obs_profile.profiled(obs_profile.KernelProfiler()) as prof:
        ops.spmm(t, b, backend="interpret")
    (key, launch), = prof.launches.items()
    rows = prof.measure(reps=1)
    cache = schedule.ScheduleCache(str(tmp_path / "tuned.json"))
    cache.put(launch.cache_key, launch.schedule,
              measured_us=rows[0]["measured_us"])
    assert prof.apply_staleness(cache, rows, tol=10.0) == []
    assert cache.entry(launch.cache_key) is not None


def test_kernel_launches_traced(model):
    """ops dispatch emits kernel trace events with the selected schedule
    and predicted roofline cost."""
    t = _small_csl()
    b = jnp.ones((256, 8), jnp.float32)
    tr = Tracer().enable()
    from repro.obs import trace as trace_mod
    prev = trace_mod.set_tracer(tr)
    try:
        ops.spmm(t, b, backend="interpret")
    finally:
        trace_mod.set_tracer(prev)
    kernel_events = [r for r in tr.records() if r.cat == "kernel"]
    assert len(kernel_events) == 1
    ev = kernel_events[0]
    assert ev.track == "kernel"
    assert ev.args["backend"] == "interpret"
    assert set(ev.args["schedule"]) == {"m_tb", "k_tb", "n_tb", "split_k"}
    assert ev.args["predicted_us"] > 0


@pytest.mark.parametrize("k,split_k,tiles,steps", [
    (256, 1, 2, 1),      # Kt 2: one 2-tile step
    (2176, 1, 1, 17),    # Kt 17: no divisor up to 16, one tile a step
    (2048, 2, 8, 2),     # Kt 16 in two slices: one 8-tile step each
])
def test_kernel_event_counts_tiles_per_step(k, split_k, tiles, steps):
    """The kernel event shows whether a launch expands several K tiles per
    grid step (tiles_per_step 1 means it does not) and how many steps its
    compute kernel takes."""
    t = _small_csl(k=k)
    b = jnp.ones((k, 8), jnp.float32)
    tr = Tracer().enable()
    from repro.obs import trace as trace_mod
    prev = trace_mod.set_tracer(tr)
    try:
        ops.spmm(t, b, backend="interpret", split_k=split_k)
    finally:
        trace_mod.set_tracer(prev)
    ev, = [r for r in tr.records() if r.cat == "kernel"]
    assert ev.args["tiles_per_step"] == tiles
    assert ev.args["grid_steps"] == steps


# -- obs cross-check pass (tools/check.py --obs) -----------------------------

def test_obs_pass_clean():
    from repro.analysis import obs_pass
    found, stats = obs_pass.run_obs_pass()
    assert found == []
    assert stats["nonzero_series"] >= 3
    assert stats["records"] > 0
