#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` (see ``chipbench/harness.py``),
warms it up, serves its traffic open-loop for ``--seconds`` on the wall clock,
checks a seeded sample of the served tokens against the plain float32
reference, and prints one JSON object as the last line of standard output.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler with the program's own
tracer on, the metrics are its per-layer metrics, and the breakdown's idle
gaps are named by the program's span paths. Exits non-zero, printing no
result, when JAX finds no accelerator or fewer chips than the cell asks for,
or when the program under test (``src/``) is not beside it.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def execute(cell, *, seed: int, seconds: float, trace: bool,
            t_start: float) -> dict:
    """Set up, serve, read and check one window of ``cell``."""
    import jax
    from chipbench import harness, program_spans, trace_reduce

    log = harness.log
    dev = jax.devices()[0]
    setup = {"jax_init_s": time.time() - t_start}
    cfg = harness.model_config(cell)
    params = harness.served_params(cell, cfg, setup)
    weight_bytes = harness.tree_bytes(params)
    server = harness.make_server(params, cfg, cell)
    t0 = time.perf_counter()
    setup["warm_buckets"] = harness.warm_shapes(server, cell, cfg.vocab)
    setup["warm_shapes_s"] = time.perf_counter() - t0
    reqs = harness.build_requests(cell, seed, seconds, cfg.vocab)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(harness.CACHE, "traces", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.serve(server, reqs, open_at=cell.params["warmup_s"],
                        seconds=seconds, trace_dir=trace_dir)
    setup_s = run["open"]["wall"] - t_start
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    rec = harness.window_record(cell, run)
    rec.update(weight_bytes=weight_bytes, device_kind=dev.device_kind,
               model=cell.model, trace=None)
    log("setup " + json.dumps({k: round(v, 3) if isinstance(v, float) else v
                               for k, v in setup.items()}))
    late = rec["lateness_s"]
    log(f"generator lateness over {len(late)} submits: mean "
        f"{1e3 * sum(late) / max(len(late), 1):.3f} ms, max "
        f"{1e3 * max(late, default=0.0):.3f} ms")
    d = rec["delta"]
    log(f"window {rec['window_s']:.3f} s: {rec['tokens']} tokens, "
        f"{int(d['admitted'])} admitted, {int(d['completed'])} completed, "
        f"{int(d['preemptions'])} preempted, {rec['steps_in_window']} steps, "
        f"queue {rec['queue'].get('open')} -> {rec['queue'].get('close')}")
    log("ttft ms p50/p80/p90/p95 " + "/".join(
        f"{1e3 * harness.pctl(rec['ttft_s'], q):.1f}" for q in (50, 80, 90, 95))
        + f" over {len(rec['ttft_s'])}; itl ms p50/p90/p95/p97.5/p99 "
        + "/".join(f"{1e3 * harness.pctl(rec['itl_s'], q):.1f}"
                   for q in (50, 90, 95, 97.5, 99))
        + f" over {len(rec['itl_s'])}")
    # Steps that carry admissions, by length: the ITL tail is made of these.
    bins: dict = {}
    for s in rec["step_s"]:
        if s >= 0.2:
            b = int(s * 20) * 50
            bins[b] = bins.get(b, 0) + 1
    log("steps >= 200 ms by 50 ms bin: " + " ".join(
        f"{b}:{bins[b]}" for b in sorted(bins)))
    if trace_dir is not None:
        prog = rec["program"]
        log(f"program spans: {len(prog['records'])} records, "
            f"{prog['dropped']} dropped")
        path = trace_reduce.find_trace(trace_dir)
        if path:
            planes = trace_reduce.load_planes(path)
            rec["trace"] = trace_reduce.reduce_planes(planes)
            named = program_spans.attribute(planes, prog)
            if rec["trace"] and named:
                rec["trace"].update(named)
                log("idle s by program span: " + json.dumps(
                    dict(list(named["idle_by_span"].items())[:8])))
            del planes
    del server, params
    gc.collect()

    t0 = time.perf_counter()
    sample = harness.sample_finished(run, cell.params["check_requests"], seed)
    chk = harness.check(cell, sample)
    bad = harness.unanswered(run)
    log(f"reference: {len(sample)} requests, {chk['tokens']} served tokens, "
        f"{chk.get('agree', 0)} first choices agree, "
        f"{time.perf_counter() - t0:.3f} s")
    limit = cell.params["gap_limit"]
    correct = chk["gap"] is not None and chk["gap"] <= limit and bad == 0

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = harness.load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = harness.end_to_end(rec, setup_s, peak)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.workload["chips"], "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": sum(1 for r in reqs if r.phase == "window"),
              "failed": bad, "metrics": metrics, "device": device}
    if trace:
        tr = rec["trace"] or {}
        device.update(busy_s=tr.get("busy_s", 0.0),
                      window_s=tr.get("window_s", 0.0))
        result["breakdown"] = {"device_ops": tr.get("device_ops", []),
                               "idle_gaps": tr.get("idle_gaps", [])}
    result["checks"] = {
        "max_logit_gap": {"value": chk["gap"], "limit": limit},
        "unanswered": {"value": bad, "limit": 0}}
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
        from chipbench import harness
    except ImportError as e:
        print(f"chipbench: the program under test is missing: {e}",
              file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.workload["chips"]:
        print(f"chipbench: {args.workload} needs {cell.workload['chips']} "
              f"accelerator chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 3
    result = execute(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
