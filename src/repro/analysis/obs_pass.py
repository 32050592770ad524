"""Observability cross-check pass (``tools/check.py --obs``, DESIGN.md §15).

The metrics registry and the trace stream are two views of the same events:
``SchedulerMetrics.preemptions`` counts what the ``preempt`` trace events
narrate, ``steps`` counts the ``step`` spans, ``quarantined`` pairs with
``quarantine`` events, and every fault the injector fires must land in
the timeline. Instrumentation drift — a new code path that bumps a counter
but forgets its trace event (or vice versa) — silently produces timelines
that lie about what the counters report.

This pass runs one small fault-laden replay (seeded trace + handcrafted
:class:`~repro.serving.faults.FaultPlan` covering transient step errors,
NaN-poisoned logits, a pool storm, and an injected latency spike) with a
*private* tracer, then asserts counter == record count for every paired
series. A mismatch is an ``OB-EVENT`` finding anchored to the pseudo-path
``obs:<scenario>`` (allowlist-suppressible, like trace-audit findings).

Pure cross-checking: the scenario's scheduling *quality* is the chaos
bench's business (``benchmarks/chaos.py``); this pass only cares that the
two observability surfaces agree.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from repro.analysis.findings import Finding

#: (metrics attribute, trace (cat, name)) pairs that must count together.
PAIRED_SERIES: Tuple[Tuple[str, Tuple[str, str]], ...] = (
    ("admitted", ("sched", "admit")),
    ("preemptions", ("sched", "preempt")),
    ("quarantined", ("sched", "quarantine")),
    ("deadline_expired", ("sched", "deadline")),
    ("cancelled", ("sched", "cancel")),
    ("degradation_transitions", ("sched", "degradation")),
    ("step_retries", ("fault", "retry")),
    # chunked-prefill mixed steps (§16): one "sched"/"chunk" event per
    # mixed launch (0 == 0 in non-chunked scenarios)
    ("mixed_steps", ("sched", "chunk")),
    # one "step" span per server step (the span tree's root, §15)
    ("steps", ("step", "step")),
)


def _scenario(seed: int):
    """One tiny chaos replay with a private tracer; returns
    (records, metrics, injector, n_responses)."""
    import jax

    from repro import configs
    from repro.models import transformer
    from repro.obs.trace import Tracer
    from repro.serving import api, faults, loadgen

    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(seed), cfg)
    plan = faults.FaultPlan([
        faults.FaultEvent(step=2, kind="step_error", op="decode",
                          attempts=1),
        faults.FaultEvent(step=3, kind="nan_logits", slot=0, op="decode"),
        faults.FaultEvent(step=4, kind="pool_storm", blocks=10, duration=3),
        faults.FaultEvent(step=6, kind="slow_step", delay_s=4.0),
    ])
    trace = loadgen.make_trace(
        seed=seed, n_requests=10, rate=0.8, vocab=cfg.vocab,
        tenants=[loadgen.TenantSpec("obs", suffix_len=(4, 10),
                                    max_new=(6, 10))])
    clock = loadgen.StepClock(dt=1.0)
    tracer = Tracer().enable(clock)
    server = api.StreamingServer(
        params, cfg, n_slots=4, max_len=64, cache_kind="paged",
        block_size=8, n_blocks=16, clock=clock, fault_plan=plan,
        tracer=tracer)
    result = loadgen.replay(server, trace, clock)
    return (tracer.records(), server.batcher.metrics,
            server.batcher.faults, len(result.responses))


def run_obs_pass(seed: int = 0) -> Tuple[List[Finding], Dict[str, int]]:
    """Cross-check the metrics counters against the trace event stream."""
    records, metrics, injector, n_responses = _scenario(seed)
    path = f"obs:chaos_replay(seed={seed})"
    hint = ("every counter bump and its trace event live together at the "
            "source (scheduler.py / batching.py / faults.py) — re-pair them")
    found: List[Finding] = []
    by_key: Dict[Tuple[str, str], int] = {}
    for r in records:
        k = (r.cat, r.name)
        by_key[k] = by_key.get(k, 0) + 1

    nonzero = 0
    for attr, (cat, name) in PAIRED_SERIES:
        counter = getattr(metrics, attr)
        events = by_key.get((cat, name), 0)
        if counter:
            nonzero += 1
        if counter != events:
            found.append(Finding(
                "OB-EVENT", path, 0,
                f"metrics.{attr}={counter} but the trace carries {events} "
                f"{name!r} record(s)", hint))
    # injected faults only — "retry" is the batcher's *reaction* (paired
    # with step_retries above), not an injector firing
    n_fault_events = sum(1 for r in records
                         if r.kind == "event" and r.cat == "fault"
                         and r.name != "retry")
    if len(injector.fired) != n_fault_events:
        found.append(Finding(
            "OB-EVENT", path, 0,
            f"injector fired {len(injector.fired)} fault(s) but the trace "
            f"carries {n_fault_events} fault event(s)", hint))
    # every request that finished must have closed its slot span
    n_finish = by_key.get(("sched", "finish"), 0)
    n_slot_spans = sum(1 for r in records
                       if r.kind == "span" and r.track.startswith("slot"))
    n_failed = (metrics.quarantined + metrics.deadline_expired
                + metrics.cancelled + metrics.preemptions)
    if n_slot_spans != n_finish + n_failed:
        found.append(Finding(
            "OB-EVENT", path, 0,
            f"{n_slot_spans} slot span(s) for {n_finish} finish + "
            f"{n_failed} fail/preempt event(s) — a request left a slot "
            f"without closing its span", hint))
    stats = {"records": len(records), "checks": len(PAIRED_SERIES) + 2,
             "nonzero_series": nonzero, "responses": n_responses}
    return found, stats
