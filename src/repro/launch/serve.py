"""Serving launcher (CLI): continuous-batching engine over a (optionally
Tiled-CSL sparse) model — the paper's end-to-end deployment path.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch tinyllama_1_1b --smoke --sparsity 0.8 --requests 8

Loads/creates weights, optionally prunes + reformats to Tiled-CSL (the
paper's weight reformatting tool), then serves a synthetic workload through
the session API (`serving.api.StreamingServer` over the slot-based
continuous batcher), reporting tokens/sec, TTFT/TPOT percentiles, and the
weight-bytes saving. Default is a closed-loop drain (submit everything,
run until done); ``--trace-rate R`` switches to an open-loop Poisson trace
(`serving.loadgen`) at R requests per engine step, where queueing delay
shows up in TTFT and ``--max-queue`` sheds load via backpressure.

Fault-tolerance knobs (DESIGN.md §14): ``--deadline-ms`` /
``--ttft-deadline-ms`` attach latency budgets to every request
(finish_reason="deadline" on a miss), ``--fault-plan plan.json`` injects a
saved `serving.faults.FaultPlan` (chaos replay from a file), and
``--snapshot-dir`` restores in-flight sessions from the newest snapshot at
startup and writes a crash-consistent one after the run drains.

Observability knobs (DESIGN.md §15): ``--metrics-port P`` exposes the live
scheduler counters at ``http://127.0.0.1:P/metrics`` (Prometheus text
exposition; ``/metrics.json`` for machines) with ``--digest-every S``
printing a one-line operator digest every S seconds; ``--trace-out t.json``
records every scheduler decision, the span tree of each engine step, every
kernel launch and every JAX compile into a Perfetto-loadable timeline;
``--profile-kernels`` measures each unique sparse-kernel launch after the
run drains and prints a predicted-vs-measured roofline drift table (pair
with ``--backend interpret`` off-TPU — the XLA reference path has no
schedulable launches to record).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.core import pruning, tiled_csl
from repro.distributed import fault_tolerance as ft
from repro.launch import compile_cache
from repro.models import transformer, nn
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace
from repro.serving import api, budget, faults, loadgen, speculative
from repro.serving.config import SLOSpec, ServeConfig
from repro.serving.scheduler import latency_summary

_EXAMPLES = """\
examples:
  # dense smoke serve with live Prometheus metrics + operator digest
  python -m repro.launch.serve --arch tinyllama_1_1b --smoke \\
      --metrics-port 9100 --digest-every 2

  # sparse paged serve, exporting a Perfetto timeline of the whole run
  python -m repro.launch.serve --arch tinyllama_1_1b --smoke --sparsity 0.8 \\
      --paged --trace-out serve_trace.json   # load at ui.perfetto.dev

  # roofline drift check for every kernel launch the serve dispatched
  python -m repro.launch.serve --arch tinyllama_1_1b --smoke --sparsity 0.8 \\
      --backend interpret --profile-kernels
"""


_SPARSE_PROJECTIONS = ("'wq'", "'wk'", "'wv'", "'wo'", "'gate'", "'up'",
                       "'down'")


def should_sparsify(path: str) -> bool:
    """Weights ``--sparsity`` prunes: the attention and FFN projection
    matrices. Their biases ([L, out] leaves) stay dense."""
    return path.endswith("['w']") and any(k in path
                                          for k in _SPARSE_PROJECTIONS)


def main() -> None:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=_EXAMPLES)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sparsity", type=float, default=None)
    ap.add_argument("--balanced", action="store_true",
                    help="tile-balanced pruning (zero pad overhead)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--ckpt", default=None, help="restore params from dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache with prefix sharing (DESIGN.md §10)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block positions (paged cache)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="usable KV blocks; default: dense byte-equivalent "
                         "or derived from --hbm-budget-gb")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="size the block pool from an HBM budget via "
                         "serving.budget.plan (weights + workspace + KV)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: drafts verified per step "
                         "(DESIGN.md §11; requires --paged)")
    ap.add_argument("--drafter", default="ngram", choices=("ngram", "model"),
                    help="draft source: the request's own n-gram history, "
                         "or a small draft model sharing the tokenizer")
    ap.add_argument("--draft-arch", default=None,
                    help="arch id for --drafter model (smoke-sized init)")
    ap.add_argument("--max-ngram", type=int, default=3,
                    help="longest suffix n-gram the ngram drafter matches")
    ap.add_argument("--trace-rate", type=float, default=None, metavar="R",
                    help="open-loop mode: Poisson arrivals at R requests "
                         "per engine step (default: closed-loop drain)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission queue bound; beyond it submissions are "
                         "shed with backpressure (open-loop mode)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="total latency budget per request; missing it ends "
                         "the session with finish_reason='deadline'")
    ap.add_argument("--ttft-deadline-ms", type=float, default=None,
                    help="first-token latency budget per request")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill: stream prompts into their slots "
                         "chunk-size positions per mixed step instead of "
                         "bucketed whole-prompt admission (DESIGN.md §16; "
                         "requires --paged)")
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="prompt positions per prefill chunk (--chunked)")
    ap.add_argument("--chunk-budget", type=int, default=32,
                    help="max prefill positions granted per mixed step "
                         "across all slots (--chunked)")
    ap.add_argument("--ttft-target-ms", type=float, default=None,
                    help="soft first-token SLO target per request: drives "
                         "EDF chunk ordering and attainment accounting "
                         "(never kills a request — see --ttft-deadline-ms)")
    ap.add_argument("--tpot-target-ms", type=float, default=None,
                    help="soft per-token SLO target: engages the decode "
                         "TPOT throttle on prefill grants (--chunked)")
    ap.add_argument("--priority", type=int, default=0,
                    help="SLO priority class (higher = scheduled first)")
    ap.add_argument("--fault-plan", default=None, metavar="PATH",
                    help="JSON FaultPlan (serving.faults) injected into the "
                         "run — chaos replay from a file")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="write a crash-consistent scheduler/session "
                         "snapshot here after the run drains (and restore "
                         "from it at startup when one exists)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "xla", "pallas", "interpret"),
                    help="kernel dispatch for sparse matmuls (kernels.ops); "
                         "'interpret' runs the Pallas kernels off-TPU and "
                         "is required for --profile-kernels on CPU")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="serve live scheduler metrics on "
                         "http://127.0.0.1:P/metrics (Prometheus text "
                         "exposition; /metrics.json for JSON)")
    ap.add_argument("--digest-every", type=float, default=None, metavar="S",
                    help="print a one-line operator digest of the key "
                         "metrics every S seconds while serving")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the run's structured trace (scheduler "
                         "decisions, engine step spans, kernel launches, "
                         "compiles) as Perfetto/Chrome trace_event JSON")
    ap.add_argument("--profile-kernels", action="store_true",
                    help="record every unique kernel launch, re-measure it "
                         "fenced after the run drains, and print the "
                         "predicted-vs-measured roofline drift table")
    args = ap.parse_args()
    compile_cache.enable()
    stop_compiles = None
    if args.trace_out:
        stop_compiles = obs_trace.record_compiles(
            obs_trace.get_tracer().enable())
    profiler = obs_profile.KernelProfiler() if args.profile_kernels else None
    if profiler is not None:
        obs_profile.set_profiler(profiler)

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = transformer.init_model(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt:
        mgr = ft.CheckpointManager(args.ckpt)
        params, _ = mgr.restore(params)

    n_dense = nn.count_params(params)
    if args.sparsity:
        t0 = time.time()
        params = pruning.sparsify_params(
            params, args.sparsity,
            should_sparsify=should_sparsify,
            balanced=args.balanced)
        params = pruning.group_projections(params)
        csl = [l for l in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))
            if isinstance(l, tiled_csl.TiledCSL)]
        grouped = sum(
            1 for p, l in jax.tree_util.tree_flatten_with_path(
                params, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))[0]
            if isinstance(l, tiled_csl.TiledCSL)
            and any(k in jax.tree_util.keystr(p)
                    for k in ("'gate_up'", "'wqkv'")))
        sp_bytes = sum(t.nbytes_sparse for t in csl)
        de_bytes = sum(t.nbytes_dense for t in csl)
        print(f"reformatted {len(csl)} weights to Tiled-CSL in "
              f"{time.time() - t0:.1f}s ({grouped} grouped): "
              f"{de_bytes / 2 ** 20:.1f} MiB dense "
              f"-> {sp_bytes / 2 ** 20:.1f} MiB sparse "
              f"({sp_bytes / de_bytes:.2f}x)")

    n_blocks = args.n_blocks
    if args.paged and args.hbm_budget_gb is not None and n_blocks is None:
        # Spend the Tiled-CSL weight savings on KV blocks: the sparse mode
        # provably affords a larger pool at equal budget (DESIGN.md §10).
        mode = "sparse_pallas" if args.sparsity else "dense"
        p = budget.plan(cfg, hbm_budget=int(args.hbm_budget_gb * 1e9),
                        weight_mode=mode, sparsity=args.sparsity or 0.8,
                        block=args.block_size)
        n_blocks = p.n_blocks
        print(f"budget: {args.hbm_budget_gb:.1f} GB -> weights "
              f"{p.weight_bytes / 1e9:.2f} GB ({mode}), "
              f"{p.n_blocks} KV blocks x {p.block} tok "
              f"({p.kv_bytes / 1e9:.2f} GB KV; dense-slot baseline "
              f"{p.n_dense_slots(args.max_len)} slots at max_len)")

    drafter = None
    if args.spec_k:
        draft_params = draft_cfg = None
        if args.drafter == "model":
            draft_cfg = configs.smoke(args.draft_arch or args.arch)
            draft_params = transformer.init_model(
                jax.random.PRNGKey(args.seed + 1), draft_cfg)
        drafter = speculative.make_drafter(
            args.drafter, max_ngram=args.max_ngram,
            draft_params=draft_params, draft_cfg=draft_cfg,
            vocab=cfg.vocab if args.drafter == "model" else None)
    plan = faults.FaultPlan.load(args.fault_plan) if args.fault_plan else None
    if plan is not None:
        print(f"fault plan: {len(plan)} events, "
              f"fingerprint {plan.fingerprint()[:12]}")
    config = ServeConfig.from_flags(args)
    if n_blocks != args.n_blocks:        # pool sized from --hbm-budget-gb
        config = dataclasses.replace(config, n_blocks=n_blocks).validate()
    live_kwargs = dict(drafter=drafter, fault_plan=plan)
    resume = None
    if args.snapshot_dir:
        resume = ft.SnapshotStore(args.snapshot_dir).latest_path()
    if resume is not None:
        server = api.StreamingServer.restore(
            args.snapshot_dir, params, cfg, config=config, **live_kwargs)
        print(f"restored {len(server.live_sessions())} in-flight "
              f"session(s) from {resume}")
    else:
        server = api.StreamingServer(params, cfg, config=config,
                                     **live_kwargs)
    # Per-request latency contract: soft targets (or a priority class)
    # promote the flat deadline flags into one typed SLOSpec; without
    # them the flags keep their legacy flat-field path.
    slo = None
    if (args.ttft_target_ms is not None or args.tpot_target_ms is not None
            or args.priority):
        slo = SLOSpec(ttft_target_ms=args.ttft_target_ms,
                      tpot_target_ms=args.tpot_target_ms,
                      priority=args.priority,
                      ttft_deadline_ms=args.ttft_deadline_ms,
                      deadline_ms=args.deadline_ms).validate()
    ttft_dl = (args.ttft_deadline_ms / 1e3
               if slo is None and args.ttft_deadline_ms is not None else None)
    total_dl = (args.deadline_ms / 1e3
                if slo is None and args.deadline_ms is not None else None)
    b = server.batcher
    registry = http_srv = stop_digest = None
    if args.metrics_port is not None or args.digest_every is not None:
        registry = obs_metrics.MetricsRegistry()
        obs_metrics.register_scheduler_metrics(registry, lambda: b.metrics)
    if args.metrics_port is not None:
        http_srv = obs_metrics.start_http_server(registry, args.metrics_port)
        print(f"metrics: http://127.0.0.1:{args.metrics_port}/metrics "
              f"(/metrics.json for JSON)")
    if args.digest_every is not None:
        import threading

        stop_digest = threading.Event()

        def _digest_loop():
            while not stop_digest.wait(args.digest_every):
                print("digest: "
                      + registry.digest(obs_metrics.DIGEST_KEYS))

        threading.Thread(target=_digest_loop, daemon=True).start()
    t0 = time.time()
    n_shed = 0
    if args.trace_rate is not None:
        # Open-loop: arrivals on their own (virtual-step) schedule; the
        # server's latency stamps stay wall-clock.
        lo = 4
        hi = max(lo + 1, min(16, args.max_len - args.max_new))
        trace = loadgen.make_trace(
            seed=args.seed, n_requests=args.requests,
            rate=args.trace_rate, vocab=cfg.vocab,
            tenants=[loadgen.TenantSpec(
                "cli", suffix_len=(lo, hi),
                max_new=(args.max_new, args.max_new + 1),
                ttft_deadline=ttft_dl, deadline=total_dl, slo=slo)])
        result = loadgen.replay(server, trace,
                                loadgen.StepClock(dt=1.0))
        responses, n_shed = result.responses, len(result.shed)
    else:
        rng = np.random.default_rng(args.seed)
        for uid in range(args.requests):
            plen = int(rng.integers(4, min(16, args.max_len - args.max_new)))
            server.submit(api.GenerationRequest(
                prompt=rng.integers(0, cfg.vocab, plen).astype(np.int64),
                max_new_tokens=args.max_new,
                ttft_deadline_s=ttft_dl, deadline_s=total_dl, slo=slo))
        responses = server.run_until_drained()
    dt = time.time() - t0
    done = {r.session_id: r.tokens for r in responses}
    n_tokens = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests / {n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens / dt:.1f} tok/s, params={n_dense / 1e6:.1f}M"
          + (f", {n_shed} shed by backpressure" if n_shed else "") + ")")
    m = b.metrics
    ttft = latency_summary([r.ttft_s for r in responses
                            if r.ttft_s is not None])
    tpot = latency_summary([r.tpot_s for r in responses
                            if r.tpot_s is not None])
    if ttft["n"]:
        print(f"latency: ttft p50/p99 = {ttft['p50'] * 1e3:.0f}/"
              f"{ttft['p99'] * 1e3:.0f} ms"
              + (f", tpot p50/p99 = {tpot['p50'] * 1e3:.0f}/"
                 f"{tpot['p99'] * 1e3:.0f} ms" if tpot["n"] else ""))
    print(f"scheduler: occupancy={m.occupancy:.2f} "
          f"queue_wait={m.mean_queue_wait_steps:.1f} steps "
          f"prefill/decode={m.prefill_tokens}/{m.decode_tokens} tok "
          f"prefill_shapes={b.prefill_compiles} "
          f"admit/decode time={m.admit_time_s:.2f}/{m.decode_time_s:.2f}s")
    if args.paged:
        print(f"paged: prefix_hit_rate={m.prefix_hit_rate:.2f} "
              f"peak_active={m.peak_active_slots} "
              f"preemptions={m.preemptions} "
              f"pool={b.pool.blocks_in_use}/{b.pool.n_blocks} in use")
    if args.chunked:
        print(f"chunked: mixed_steps={m.mixed_steps} "
              f"chunk_tokens={m.chunk_tokens} "
              f"compute_positions={m.compute_positions}")
    if m.slo_attainment:
        for tenant, c in sorted(m.slo_attainment.items()):
            print(f"slo[{tenant}]: ttft {c['ttft_ok']}/"
                  f"{c['ttft_ok'] + c['ttft_miss']} met, "
                  f"tpot {c['tpot_ok']}/{c['tpot_ok'] + c['tpot_miss']} met")
    if args.spec_k:
        print(f"speculative (k={args.spec_k}, {args.drafter}): "
              f"drafted={m.drafted} accepted={m.accepted} "
              f"accept_rate={m.accept_rate:.2f} "
              f"tokens_per_step={m.tokens_per_step:.2f}")
    if plan is not None:
        rep = b.faults.report()
        print(f"faults: {rep['fired']}/{rep['plan_events']} events fired "
              f"{rep['by_kind']}; retries={m.step_retries} "
              f"quarantined={m.quarantined} deadline={m.deadline_expired} "
              f"peak_degradation={m.peak_degradation_level}")
    if args.snapshot_dir:
        path = server.snapshot(args.snapshot_dir)
        print(f"snapshot: {path}")
    if registry is not None:
        print("digest: " + registry.digest(obs_metrics.DIGEST_KEYS))
    if stop_digest is not None:
        stop_digest.set()
    if http_srv is not None:
        http_srv.shutdown()
    if profiler is not None:
        obs_profile.set_profiler(None)
        rep = profiler.drift_report(reps=2)
        print(f"kernel drift ({rep['n_unique_launches']} unique launches):")
        print(obs_profile.render_drift_table(rep["rows"]))
    if args.trace_out:
        stop_compiles()
        tr = obs_trace.get_tracer()
        obs_export.write_chrome_trace(tr.records(), args.trace_out)
        print(f"wrote {args.trace_out}: {len(tr)} trace records "
              f"({tr.dropped} dropped)")
        tr.disable()
        tr.clear()
    for sid in sorted(done)[:3]:
        print(f"  {sid}: {done[sid][:8]}...")


if __name__ == "__main__":
    main()
