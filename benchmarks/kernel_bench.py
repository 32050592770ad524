"""Kernel benchmarks — paper Fig.3 / Fig.9 / Fig.12 analogues.

Fig.9: SpMM throughput on the paper's OPT MatMul shapes × batch sizes ×
sparsities {70%, 80%, 90%}, LSCD vs dense.

This container is CPU-only, so two measurement modes are reported per shape:

  * ``roofline`` — the TPU-v5e analytic terms (the paper's own Fig.5
    methodology, Eq.1/Eq.2): memory-bound step time for dense vs LSCD,
    using the *measured* encoding bytes (incl. real padding overhead) of an
    actually-encoded random-sparse matrix — not just the formula.
  * ``wall`` — measured CPU wall time of the XLA reference path (dense vs
    decompress+matmul), reported for completeness; kernel-level wall truth
    on TPU comes from the Pallas path which cannot lower here.

CSV columns: name,us_per_call,derived.

Run as a module for the JSON perf-trajectory mode (DESIGN.md §9):

  PYTHONPATH=src python -m benchmarks.kernel_bench --json BENCH_kernel.json
      [--full | --smoke]

``--json`` writes the schedule-sweep accounting (shapes x schedules,
``roofline.lscd_splitk_terms`` numbers + the selector's pick per cell) so
the repo accumulates a perf trajectory across PRs. ``--smoke`` restricts
to tiny shapes AND actually launches the split-K kernels in interpret
mode against the oracles — the CI bench-smoke step runs this so
kernel-entry regressions fail fast.
"""

from __future__ import annotations

import argparse
import json as json_mod
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import roofline, tiled_csl
from repro.kernels import ops, ref
from repro.kernels import schedule as schedule_mod

# The paper's four decoder MatMuls (M, K as multiples of hidden h):
#   QKV-proj: [3h, h]   O-proj: [h, h]   MLP1: [4h, h]   MLP2: [h, 4h]
_OPT_HIDDEN = {"opt-30b": 7168, "opt-66b": 9216, "opt-175b": 12288}


def paper_matmul_shapes(model: str) -> List[Tuple[str, int, int]]:
    h = _OPT_HIDDEN[model]
    return [("qkv", 3 * h, h), ("oproj", h, h),
            ("mlp1", 4 * h, h), ("mlp2", h, 4 * h)]


def _time_it(fn, *args, reps: int = 3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


_ENCODE_CACHE = {}


def _encoded(m: int, k: int, sparsity: float, rng):
    """Encode a row-subsampled stand-in (per-tile stats are M-invariant);
    cached by (m_enc, k, sparsity) — n and the full m reuse it."""
    m_enc = min(m, 2048)
    key = (m_enc, k, sparsity)
    if key in _ENCODE_CACHE:
        return _ENCODE_CACHE[key]
    a = rng.standard_normal((m_enc, k), dtype=np.float32)
    a[rng.random((m_enc, k)) < sparsity] = 0.0
    mp = -(-m_enc // 128) * 128
    kp = -(-k // 128) * 128
    ap = np.zeros((mp, kp), np.float32)
    ap[:m_enc, :k] = a
    t = tiled_csl.encode(ap)
    _ENCODE_CACHE[key] = (ap, t)
    return ap, t


def bench_shape(m: int, k: int, n: int, sparsity: float, *,
                measure_wall: bool, rng) -> List[str]:
    """One (shape, sparsity) cell -> CSV rows."""
    rows = []
    ap, t = _encoded(m, k, sparsity, rng)
    pad = t.pad_overhead

    dense = roofline.dense_gemm_terms(m, k, n)
    lscd = roofline.lscd_kernel_terms(m, k, n, sparsity, pad_overhead=pad)
    name = f"spmm_m{m}_k{k}_n{n}_s{int(sparsity * 100)}"
    # memory-bound step times (the binding term for skinny N) and effective
    # TFLOP/s — the paper's Fig.9 y-axis.
    t_dense = dense.step_time_s
    t_lscd = lscd.step_time_s
    rows.append(f"{name}_roofline_dense,{t_dense * 1e6:.3f},"
                f"tflops={2 * m * k * n / t_dense / 1e12:.2f}")
    rows.append(f"{name}_roofline_lscd,{t_lscd * 1e6:.3f},"
                f"tflops={2 * m * k * n / t_lscd / 1e12:.2f};"
                f"speedup={t_dense / t_lscd:.2f};pad={pad:.3f};"
                f"ci_dense={roofline.dense_gemm_ci(m, n):.1f};"
                f"ci_lscd={roofline.lscd_ci(m, n, sparsity):.1f}")

    if measure_wall:
        kp = ap.shape[1]
        b = jnp.asarray(rng.standard_normal((kp, n), dtype=np.float32))
        ad = jnp.asarray(ap)
        f_dense = jax.jit(lambda aa, bb: ref.spmm_dense_oracle(aa, bb))
        f_sparse = jax.jit(lambda words, nnz, slabs, bb: ref.spmm_ref(
            tiled_csl.TiledCSL(words, nnz, slabs, t.shape, t.m_tb, t.k_tb,
                               t.dtype), bb))
        us_d = _time_it(f_dense, ad, b)
        us_s = _time_it(f_sparse, t.words, t.nnz, t.slabs, b)
        rows.append(f"{name}_wall_dense_xla,{us_d:.1f},cpu_ref")
        rows.append(f"{name}_wall_sparse_xla,{us_s:.1f},cpu_ref")
    return rows


def bench_fused_group(m: int, k: int, n: int, sparsity: float, *,
                      group: int, epilogue: str, tag: str, rng) -> List[str]:
    """Grouped fused-epilogue call vs the pre-fusion execution (G separate
    kernel calls + an XLA pointwise pass): the HBM bytes the fusion removes
    and the roofline speedup it buys. Pad overhead is measured from a real
    encoding, as in :func:`bench_shape`."""
    _, t = _encoded(m, k, sparsity, rng)
    pad = t.pad_overhead
    fused = roofline.lscd_grouped_terms(
        m, k, n, sparsity, group=group, epilogue=epilogue, fused=True,
        pad_overhead=pad)
    unfused = roofline.lscd_grouped_terms(
        m, k, n, sparsity, group=group, epilogue=epilogue, fused=False,
        pad_overhead=pad)
    saved = unfused.hbm_bytes - fused.hbm_bytes
    name = f"{tag}_m{m}_k{k}_n{n}_s{int(sparsity * 100)}_g{group}"
    return [
        f"{name}_roofline_unfused,{unfused.step_time_s * 1e6:.3f},"
        f"hbm_bytes={unfused.hbm_bytes:.0f}",
        f"{name}_roofline_fused,{fused.step_time_s * 1e6:.3f},"
        f"hbm_bytes={fused.hbm_bytes:.0f};saved_bytes={saved:.0f};"
        f"speedup={unfused.step_time_s / fused.step_time_s:.3f};"
        f"epilogue={epilogue}",
    ]


# The ISSUE-3 acceptance cells: one decode-regime shape (skinny N, where
# the selector must find split_k > 1) and one prefill-regime shape (wide N,
# where split-K only adds partials traffic and must NOT be picked).
SCHEDULE_CELLS = [
    ("decode", 8192, 8192, 8, 0.8),
    ("prefill", 8192, 8192, 2048, 0.8),
]


def schedule_cell(tag: str, m: int, k: int, n: int, sparsity: float, *,
                  max_nnz: int | None = None) -> Tuple[List[str], dict]:
    """One shape's schedule sweep: lscd_splitk_terms for every candidate
    (n_tb x split_k at the launch-time 128x128 tile geometry) plus the
    selector's pick. Returns (CSV rows, JSON record)."""
    # cache=False: the committed JSON must reflect the analytic model, not
    # whatever REPRO_SCHEDULE_CACHE the generating machine happens to have.
    sel = schedule_mod.select(m, k, n, sparsity, m_tb=128, k_tb=128,
                              max_nnz=max_nnz, cache=False)
    sweep = []
    for cand in schedule_mod.candidates(m, k, n, m_tb=128, k_tb=128):
        terms = schedule_mod.predicted(m, k, n, sparsity, cand,
                                       max_nnz=max_nnz)
        sweep.append(terms.as_dict())
    sel_terms = schedule_mod.predicted(m, k, n, sparsity, sel,
                                       max_nnz=max_nnz)
    base = schedule_mod.predicted(
        m, k, n, sparsity,
        schedule_mod.Schedule(128, 128, sel.n_tb, 1), max_nnz=max_nnz)
    name = f"sched_{tag}_m{m}_k{k}_n{n}_s{int(sparsity * 100)}"
    rows = [
        f"{name}_selected,{sel_terms.effective_s * 1e6:.3f},"
        f"n_tb={sel.n_tb};split_k={sel.split_k};"
        f"util={sel_terms.utilization:.3f};"
        f"parallel_tiles={sel_terms.parallel_tiles};"
        f"partials_bytes={sel_terms.partials_bytes:.0f};"
        f"speedup_vs_s1={base.effective_s / sel_terms.effective_s:.3f}",
    ]
    record = {
        "name": name, "m": m, "k": k, "n": n, "sparsity": sparsity,
        "regime": tag,
        "selected": sel.as_dict(),
        "selected_terms": sel_terms.as_dict(),
        "schedules": sweep,
    }
    return rows, record


def _smoke_kernel_launches() -> List[dict]:
    """Tiny-shape interpret-mode launches of every kernel entry (single,
    split-K incl. ragged Kt/S, grouped unary + binary split-K) vs the ref
    oracles — the CI tripwire for kernel-entry regressions."""
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(0)
    results = []

    def _case(name, got, want, atol=1e-3):
        err = float(np.max(np.abs(np.asarray(got, np.float32)
                                  - np.asarray(want, np.float32))))
        results.append({"case": name, "max_abs_err": err, "ok": err < atol})
        return results[-1]["ok"]

    a = rng.standard_normal((256, 384), dtype=np.float32)
    a[rng.random((256, 384)) < 0.8] = 0.0
    t = tiled_csl.encode(a)
    b = jnp.asarray(rng.standard_normal((384, 8), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(256), jnp.float32)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32, epilogue="gelu",
                        bias=bias)
    _case("spmm_single_pass",
          spmm_mod.lscd_spmm(t, b, n_tb=8, interpret=True, epilogue="gelu",
                             bias=bias), want)
    for s in (2, 3):  # Kt == 3: s=2 exercises the ragged last slice
        _case(f"spmm_splitk_s{s}",
              spmm_mod.lscd_spmm_splitk(t, b, n_tb=8, split_k=s,
                                        interpret=True, epilogue="gelu",
                                        bias=bias),
              ref.spmm_splitk_ref(t, b, s, out_dtype=jnp.float32,
                                  epilogue="gelu", bias=bias))
    mats = []
    for sp_ in (0.7, 0.85):
        g = rng.standard_normal((256, 384), dtype=np.float32)
        g[rng.random((256, 384)) < sp_] = 0.0
        mats.append(g)
    tg = tiled_csl.encode_group(mats)
    _case("spmm_splitk_grouped_silu_mul",
          spmm_mod.lscd_spmm_splitk_grouped(tg, b, n_tb=8, split_k=2,
                                            interpret=True,
                                            epilogue="silu_mul"),
          ref.spmm_splitk_grouped_ref(tg, b, 2, out_dtype=jnp.float32,
                                      epilogue="silu_mul"))
    _case("ops_spmm_auto_schedule",
          ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32),
          ref.spmm_ref(t, b, out_dtype=jnp.float32))
    _case("ops_spmm_grouped_auto_schedule",
          ops.spmm_grouped(tg, b, backend="interpret",
                           out_dtype=jnp.float32),
          ref.spmm_splitk_grouped_ref(tg, b, 1, out_dtype=jnp.float32))
    return results


def run_json(full: bool = False, smoke: bool = False) -> dict:
    """Build the BENCH_kernel.json payload: schedule-sweep accounting per
    cell (+ smoke kernel-launch parity when ``smoke``)."""
    rng = np.random.default_rng(0)
    cells = []
    for tag, m, k, n, s in SCHEDULE_CELLS:
        # Measured max_nnz (real encoding incl. padding) outside smoke;
        # the analytic DESIGN.md §4 bound keeps CI smoke fast.
        max_nnz = None if smoke else _encoded(m, k, s, rng)[1].max_nnz
        _, record = schedule_cell(tag, m, k, n, s, max_nnz=max_nnz)
        cells.append(record)
    if full:
        for model in _OPT_HIDDEN:
            for mm_name, m, k in paper_matmul_shapes(model):
                for n in (8, 64, 512):
                    _, record = schedule_cell(f"{model}_{mm_name}", m, k, n,
                                              0.8)
                    cells.append(record)
    payload = {
        "bench": "kernel",
        "schema": 1,
        "mode": "smoke" if smoke else ("full" if full else "reduced"),
        "backend": jax.default_backend(),
        "latency_hiding_tiles": roofline.LATENCY_HIDING_TILES,
        "cells": cells,
    }
    if smoke:
        # Profile the auto-schedule dispatches: the recorded launches are
        # re-measured fenced, giving a predicted-vs-measured roofline drift
        # row per unique launch (obs/profile.py). Interpret-mode wall times
        # carry huge constant factors, so the drift values only anchor the
        # report shape — the gate never reads them (cells/smoke_ok only).
        from repro.obs import profile as obs_profile
        with obs_profile.profiled(obs_profile.KernelProfiler()) as prof:
            launches = _smoke_kernel_launches()
        payload["smoke_launches"] = launches
        payload["smoke_ok"] = all(r["ok"] for r in launches)
        payload["kernel_drift"] = prof.drift_report(reps=2)
    return payload


def run(full: bool = False) -> List[str]:
    """Fig.9 grid (reduced by default: one model + the paper's sparsities)."""
    rng = np.random.default_rng(0)
    rows = []
    models = list(_OPT_HIDDEN) if full else ["opt-30b"]
    batches = (8, 16, 32, 64) if full else (8, 32)
    for model in models:
        for mm_name, m, k in paper_matmul_shapes(model):
            for n in batches:
                for s in (0.7, 0.8, 0.9):
                    rows += bench_shape(m, k, n, s, measure_wall=False,
                                        rng=rng)
    # Fig.12 analogue: sparsity fixed 80%, sweep N to find the crossover.
    h = _OPT_HIDDEN["opt-30b"]
    for n in (8, 16, 32, 64, 128, 256, 512, 1024):
        rows += bench_shape(4 * h, h, n, 0.8, measure_wall=False, rng=rng)
    # Grouped fused-epilogue cells (DESIGN.md §8): SwiGLU gate+up with the
    # silu_mul binary epilogue (one C write-back instead of three C-sized
    # transfers) and a grouped QKV launch (B streamed once for G=3).
    for n in (8, 32) if not full else (8, 16, 32, 64):
        rows += bench_fused_group(4 * h, h, n, 0.8, group=2,
                                  epilogue="silu_mul", tag="swiglu", rng=rng)
        rows += bench_fused_group(h, h, n, 0.8, group=3, epilogue="none",
                                  tag="qkv", rng=rng)
        rows += bench_fused_group(4 * h, h, n, 0.8, group=1, epilogue="gelu",
                                  tag="mlp1_gelu", rng=rng)
    # Schedule-selection cells (DESIGN.md §9): decode picks split_k > 1,
    # prefill stays single-pass; the analytic terms behind the pick.
    for tag, m, k, n, s in SCHEDULE_CELLS:
        rows += schedule_cell(tag, m, k, n, s,
                              max_nnz=_encoded(m, k, s, rng)[1].max_nnz)[0]
    # Wall-clock sanity cell (small, CPU-measurable)
    rows += bench_shape(4096, 4096, 16, 0.8, measure_wall=True, rng=rng)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the schedule-sweep JSON payload here "
                         "(e.g. BENCH_kernel.json)")
    ap.add_argument("--full", action="store_true",
                    help="full paper shape grid in the JSON payload")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + real interpret-mode kernel "
                         "launches (the CI bench-smoke gate)")
    args = ap.parse_args()
    if args.json is None and not args.smoke:
        print("name,us_per_call,derived")
        for row in run(full=args.full):
            print(row)
        return
    # --smoke without --json still runs the kernel-parity launches (and
    # still fails loudly) — it just skips the file write.
    payload = run_json(full=args.full, smoke=args.smoke)
    if args.json is not None:
        with open(args.json, "w") as f:
            json_mod.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        n_sched = sum(len(c["schedules"]) for c in payload["cells"])
        print(f"wrote {args.json}: {len(payload['cells'])} cells, "
              f"{n_sched} schedules")
    if args.smoke:
        for r in payload["smoke_launches"]:
            print(f"  smoke {r['case']}: max_abs_err={r['max_abs_err']:.2e} "
                  f"{'ok' if r['ok'] else 'FAIL'}")
        from repro.obs import profile as obs_profile
        drift = payload["kernel_drift"]
        print(f"kernel drift ({drift['n_unique_launches']} unique "
              f"auto-schedule launches):")
        print(obs_profile.render_drift_table(drift["rows"]))
        if not payload["smoke_ok"]:
            raise SystemExit("bench smoke: kernel parity check FAILED")


if __name__ == "__main__":
    main()
