"""Opt-in kernel profiling: predicted-vs-measured roofline drift.

Under jit, a per-call host timer is meaningless — the dispatch site runs
once at trace time and the launch is async.  So profiling is split in two
honest halves (DESIGN §15):

1. **Collection** (free): when a profiler is active, ``kernels/ops.py``
   calls :meth:`KernelProfiler.note_dispatch` at trace time with the
   static launch facts — shape, sparsity, backend, selected
   :class:`~repro.kernels.schedule.Schedule`, and the roofline-predicted
   effective time.  One record per unique launch shape.

2. **Measurement** (explicit, outside the hot loop): :meth:`measure`
   replays each unique launch standalone on synthetic weights of the same
   shape/sparsity with ``jax.block_until_ready`` fencing (the same timing
   discipline as ``schedule.autotune``), yielding measured wall time and a
   ``drift = measured / predicted`` ratio per shape.

The drift report feeds back into the autotune cache as a **staleness
signal**: :meth:`apply_staleness` compares fresh measurements against the
``measured_us`` a cache entry was persisted with; entries whose stored
timing drifted beyond tolerance (different machine, changed kernels) are
invalidated so the next ``select()`` falls back to the analytic model or a
re-autotune.

jax is imported lazily — importing this module from host-only code costs
nothing.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from repro.kernels import schedule as schedule_mod

__all__ = ["KernelLaunch", "KernelProfiler", "active", "profiled"]


@dataclasses.dataclass(frozen=True)
class KernelLaunch:
    """Static facts of one unique SpMM dispatch (recorded at trace time)."""

    kind: str                     # "spmm" | "spmm_grouped"
    m: int
    k: int
    n: int
    sparsity: float
    group: int
    max_nnz: int
    m_tb: int
    k_tb: int
    backend: str
    schedule: schedule_mod.Schedule
    predicted_s: float            # roofline effective_s for this schedule

    @property
    def cache_key(self) -> str:
        return schedule_mod.cache_key(
            self.m, self.k, self.n, self.sparsity, group=self.group,
            backend=self.backend, m_tb=self.m_tb, k_tb=self.k_tb)


class KernelProfiler:
    """Collects unique kernel launches, measures them, reports drift."""

    def __init__(self) -> None:
        self.launches: Dict[str, KernelLaunch] = {}   # cache_key+kind -> rec
        self.dispatch_counts: Dict[str, int] = {}

    def note_dispatch(self, kind: str, m: int, k: int, n: int,
                      sparsity: float, group: int, max_nnz: int,
                      m_tb: int, k_tb: int, backend: str,
                      sched: schedule_mod.Schedule) -> None:
        terms = schedule_mod.predicted(m, k, n, sparsity, sched,
                                       group=group, max_nnz=max_nnz)
        rec = KernelLaunch(kind, m, k, n, round(float(sparsity), 4), group,
                           max_nnz, m_tb, k_tb, backend, sched,
                           terms.effective_s)
        key = f"{kind}:{rec.cache_key}_ntb{sched.n_tb}_sk{sched.split_k}"
        self.launches.setdefault(key, rec)
        self.dispatch_counts[key] = self.dispatch_counts.get(key, 0) + 1

    # -- measurement --------------------------------------------------------
    def measure(self, reps: int = 2, seed: int = 0) -> List[Dict[str, Any]]:
        """Time each unique launch standalone; returns drift-table rows.

        Runs outside any jitted step: build synthetic weights at the
        recorded shape/sparsity, warm once, then time ``reps`` fenced
        iterations — the ``block_until_ready`` calls live HERE, never in
        ``serving/step.py`` (OB-SYNC).
        """
        if not self.launches:
            return []
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.core import roofline, tiled_csl
        from repro.kernels import ops  # late import: ops imports obs.profile

        rows: List[Dict[str, Any]] = []
        for key in sorted(self.launches):
            rec = self.launches[key]
            # The prediction uses the analytic chip's peaks, the only entry
            # of roofline.DEVICE_PEAKS: a device measurement on any other
            # chip raises before anything runs instead of borrowing them.
            if rec.backend == "pallas":
                roofline.peaks_for(jax.devices()[0].device_kind)
            rng = np.random.default_rng(seed)

            def _sparse(r):
                a = r.standard_normal((rec.m, rec.k)).astype(np.float32)
                a[r.random((rec.m, rec.k)) < rec.sparsity] = 0.0
                return a
            if rec.kind == "spmm_grouped":
                t = tiled_csl.encode_group([_sparse(rng)
                                            for _ in range(rec.group)],
                                           rec.m_tb, rec.k_tb)
                run = ops.spmm_grouped
            else:
                t = tiled_csl.encode(_sparse(rng), rec.m_tb, rec.k_tb)
                run = ops.spmm
            b = jnp.asarray(rng.standard_normal(
                (rec.k, rec.n)).astype(np.float32))

            def fn():
                return run(t, b, backend=rec.backend,
                           n_tb=rec.schedule.n_tb,
                           split_k=rec.schedule.split_k,
                           out_dtype=jnp.float32)
            jax.block_until_ready(fn())          # compile/warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn())
            measured_us = (time.perf_counter() - t0) / reps * 1e6
            predicted_us = rec.predicted_s * 1e6
            rows.append({
                "key": key,
                "kind": rec.kind,
                "m": rec.m, "k": rec.k, "n": rec.n,
                "sparsity": rec.sparsity,
                "group": rec.group,
                "backend": rec.backend,
                "schedule": rec.schedule.as_dict(),
                "dispatches": self.dispatch_counts.get(key, 0),
                "predicted_us": predicted_us,
                "measured_us": measured_us,
                "drift": (measured_us / predicted_us
                          if predicted_us > 0 else None),
            })
        return rows

    # -- staleness feedback -------------------------------------------------
    def apply_staleness(self, cache: schedule_mod.ScheduleCache,
                        rows: List[Dict[str, Any]],
                        tol: float = 0.5) -> List[str]:
        """Invalidate autotune-cache entries whose stored timing drifted.

        For each measured row whose shape has a cache entry carrying
        ``measured_us``, compare stored vs fresh: a relative gap beyond
        ``tol`` means the entry was tuned on a world that no longer exists
        (other machine, other kernel revision) — drop it so ``select()``
        stops trusting it.  Returns the invalidated cache keys.
        """
        dropped: List[str] = []
        by_cache_key = {}
        for row in rows:
            launch = self.launches.get(row["key"])
            if launch is not None:
                by_cache_key.setdefault(launch.cache_key, row)
        for ckey, row in sorted(by_cache_key.items()):
            ent = cache.entry(ckey)
            if not ent or "measured_us" not in ent:
                continue
            stored = float(ent["measured_us"])
            fresh = float(row["measured_us"])
            if stored <= 0:
                continue
            gap = abs(fresh - stored) / stored
            if gap > tol:
                cache.invalidate(ckey)
                row["stale_cache_entry"] = {
                    "key": ckey, "stored_us": stored, "rel_gap": gap}
                dropped.append(ckey)
        return dropped

    def drift_report(self, reps: int = 2,
                     cache: Optional[schedule_mod.ScheduleCache] = None,
                     tol: float = 0.5) -> Dict[str, Any]:
        """measure() + optional staleness pass, as one JSON-able report."""
        rows = self.measure(reps=reps)
        stale = (self.apply_staleness(cache, rows, tol=tol)
                 if cache is not None else [])
        return {"rows": rows, "stale_keys": stale,
                "n_unique_launches": len(rows)}


def render_drift_table(rows: List[Dict[str, Any]]) -> str:
    """Fixed-width drift table for CLI output."""
    if not rows:
        return "(no schedulable kernel launches recorded)"
    hdr = (f"{'kind':<14}{'m':>6}{'k':>6}{'n':>6}  {'schedule':<18}"
           f"{'pred_us':>10} {'meas_us':>10} {'drift':>10}  stale")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        s = r["schedule"]
        sched = f"ntb{s['n_tb']}/sk{s['split_k']}"
        drift = f"{r['drift']:.2f}x" if r["drift"] is not None else "n/a"
        stale = "YES" if r.get("stale_cache_entry") else ""
        lines.append(f"{r['kind']:<14}{r['m']:>6}{r['k']:>6}{r['n']:>6}  "
                     f"{sched:<18}{r['predicted_us']:>10.1f} "
                     f"{r['measured_us']:>10.1f} {drift:>10}  {stale}")
    return "\n".join(lines)


# Process-wide active profiler (None => collection disabled; the dispatch
# site in ops.py pays one module-attr check when off).
_PROFILER: Optional[KernelProfiler] = None


def active() -> Optional[KernelProfiler]:
    return _PROFILER


def set_profiler(prof: Optional[KernelProfiler]) -> Optional[KernelProfiler]:
    global _PROFILER
    prev, _PROFILER = _PROFILER, prof
    return prev


class profiled:
    """Context manager: activate ``prof`` for the dynamic extent."""

    def __init__(self, prof: KernelProfiler) -> None:
        self.prof = prof
        self._prev: Optional[KernelProfiler] = None

    def __enter__(self) -> KernelProfiler:
        self._prev = set_profiler(self.prof)
        return self.prof

    def __exit__(self, *exc) -> None:
        set_profiler(self._prev)
