"""Shape-aware schedule selection for the LSCD SpMM kernels (DESIGN.md §9).

The decode hot path is a *skinny* GEMM (N = tokens in flight, 1-64): with
one N tile the only launch parallelism is Mt, and a 7B-scale projection
(M=8192, m_tb=128 -> Mt=64) cannot keep the chip's DMA engines and compute
units busy. Tile geometry and the split-K factor therefore have to be
chosen per *(M, K, N, sparsity)* — the same weights want different
schedules for decode (N=1-8) and prefill (N=512+), which
``sparse_linear.linear`` delivers by passing the activation's N through
``ops.spmm`` on every call. Speculative verification (DESIGN.md §11) rides
the same contract: a verify window flattens to N = B·(k+1) activation
rows, so the selector sees the widened N and can back off split-K exactly
where the extra verify compute already restores launch parallelism.

Components:

* :class:`Schedule` — the launch configuration ``(m_tb, k_tb, n_tb,
  split_k)``. ``m_tb``/``k_tb`` are fixed by the weight's Tiled-CSL
  encoding at launch time; sweeping them is only meaningful at
  reformat/encode time (both modes are supported — pass ``m_tb=None``).
* :func:`select` — analytic selection: enumerate the candidate grid,
  score each with ``roofline.lscd_splitk_terms`` (partials write+read
  traffic vs. the parallelism-utilization gain), minimise ``effective_s``
  with ties broken toward fewer bytes, then smaller split, then larger N
  tile. Memoised on the static key, so per-launch dispatch cost is a dict
  hit.
* :class:`ScheduleCache` / :func:`autotune` — optional *measured* mode:
  time the real kernels over the candidate grid and persist the winner to
  a JSON cache keyed by shape+backend (``REPRO_SCHEDULE_CACHE`` names a
  default cache file). ``select`` consults the cache first, so a tuned
  serving deployment pays the measurement once per shape.

``ops.spmm`` / ``ops.spmm_grouped`` dispatch through :func:`select`
(replacing the fixed N-tile ladder they used to hardcode) and route
``split_k > 1`` to the split-K kernels.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Dict, Optional, Sequence, Tuple

from repro.analysis import contracts
from repro.core import roofline

# Candidate ladders. N tiles follow the paper §5 batch ladder (TPU lane cap
# 128); split factors are powers of two — the ragged last slice the kernels
# tolerate makes exact divisibility unnecessary, but factors beyond 16 only
# add partials traffic for shapes this repo serves.
N_TB_LADDER = (8, 16, 32, 64, 128)
SPLIT_LADDER = (1, 2, 4, 8, 16)
MKTB_LADDER = (128, 64)

_ENV_CACHE_VAR = "REPRO_SCHEDULE_CACHE"


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One LSCD SpMM launch configuration.

    ``split_k == 1`` means the single-pass fused kernel; ``split_k > 1``
    the split-K pair (partials + reduce). ``m_tb``/``k_tb`` must match the
    weight's encoding at launch time.
    """

    m_tb: int
    k_tb: int
    n_tb: int
    split_k: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Schedule":
        return cls(m_tb=int(d["m_tb"]), k_tb=int(d["k_tb"]),
                   n_tb=int(d["n_tb"]), split_k=int(d["split_k"]))


def sparsity_from_max_nnz(max_nnz: int, m_tb: int, k_tb: int) -> float:
    """Trace-safe sparsity bound from static encoding metadata: ``max_nnz``
    over the tile size upper-bounds per-tile density, padding included —
    which is what the A-stream bytes term should charge. THE single
    definition: ops dispatch and autotune both key the schedule cache
    through this value, so they must round-trip bit-identically."""
    return 1.0 - min(1.0, max_nnz / float(m_tb * k_tb))


def cache_key(m: int, k: int, n: int, sparsity: float, *, group: int = 1,
              backend: str = "pallas", m_tb: Optional[int] = None,
              k_tb: Optional[int] = None) -> str:
    """Stable JSON-cache key: shape + backend (+ pinned tile geometry)."""
    tile = f"_mtb{m_tb}_ktb{k_tb}" if m_tb and k_tb else ""
    return (f"{backend}_m{m}_k{k}_n{n}_s{round(float(sparsity), 4)}"
            f"_g{group}{tile}")


def _read_entries(path: str) -> Dict[str, dict]:
    """Tolerant cache-file read: a missing, corrupt, or schema-drifted file
    yields {} instead of raising. Shared by ``ScheduleCache.__init__`` and
    the merge step of ``save`` so their semantics cannot diverge."""
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            return {str(k): dict(v) for k, v in json.load(f).items()}
    except (json.JSONDecodeError, OSError, TypeError, ValueError, AttributeError):
        return {}


class ScheduleCache:
    """JSON-file persistence for measured-autotune winners.

    Format: ``{key: {m_tb, k_tb, n_tb, split_k, measured_us?}}``. Loads
    lazily and tolerates a missing/corrupt file (starts empty); ``save``
    writes atomically (tmp + rename) so a crashed autotune run never
    truncates an existing cache.
    """

    def __init__(self, path: str):
        self.path = path
        self._data: Dict[str, dict] = _read_entries(path)
        self._dropped: set = set()     # staleness-invalidated keys

    def __len__(self) -> int:
        return len(self._data)

    def entry(self, key: str) -> Optional[dict]:
        """Raw cache record (incl. ``measured_us``), or None."""
        ent = self._data.get(key)
        return dict(ent) if ent else None

    def invalidate(self, key: str) -> bool:
        """Drop a stale entry (obs.profile drift feedback).  The drop
        survives ``save()``'s merge-on-save: next ``select()`` falls back
        to the analytic model instead of the stale measurement."""
        self._dropped.add(key)
        return self._data.pop(key, None) is not None

    def get(self, key: str) -> Optional[Schedule]:
        ent = self._data.get(key)
        if not ent:
            return None
        try:
            return Schedule.from_dict(ent)
        except (KeyError, TypeError, ValueError):
            return None   # schema-drifted entry: fall back to analytic

    def put(self, key: str, sched: Schedule,
            measured_us: Optional[float] = None) -> None:
        ent = sched.as_dict()
        if measured_us is not None:
            ent["measured_us"] = float(measured_us)
        self._dropped.discard(key)     # a fresh measurement un-drops the key
        self._data[key] = ent

    def save(self) -> None:
        # Merge-on-save: re-read the on-disk file so interleaved autotune
        # runs against one shared cache file keep each other's entries
        # (ours win on key collision); tmp + rename keeps the write atomic.
        merged = _read_entries(self.path)
        merged.update(self._data)
        for key in self._dropped:      # invalidations beat the disk copy
            merged.pop(key, None)
        self._data = merged
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_env_cache: Optional[ScheduleCache] = None


def _default_cache() -> Optional[ScheduleCache]:
    global _env_cache
    path = os.environ.get(_ENV_CACHE_VAR)
    if not path:
        return None
    if _env_cache is None or _env_cache.path != path:
        _env_cache = ScheduleCache(path)
    return _env_cache


def candidates(m: int, k: int, n: int, *,
               m_tb: Optional[int] = None, k_tb: Optional[int] = None,
               n_tb: Optional[int] = None,
               split_k: Optional[int] = None) -> Tuple[Schedule, ...]:
    """Enumerate the feasible schedule grid; pinned fields are kept as-is.

    Tile candidates honour the encoding constraints: the dense dims must
    tile evenly (encode pads to the tile multiple, so launch-time fixed
    geometry always divides) and ``m_tb`` must fit the 16-bit row field
    (KC-LOC). Split candidates are capped at Kt —
    a slice with zero real K tiles is legal but pure waste.
    """
    m_opts = (m_tb,) if m_tb else tuple(x for x in MKTB_LADDER if m % x == 0)
    k_opts = (k_tb,) if k_tb else tuple(x for x in MKTB_LADDER if k % x == 0)
    if not m_opts or not k_opts:
        raise ValueError(f"no tile geometry divides (M={m}, K={k})")
    out = []
    for mtb in m_opts:
        for ktb in k_opts:
            if not contracts.tile_loc_ok(mtb):   # KC-LOC
                continue
            kt = -(-k // ktb)
            n_opts = (n_tb,) if n_tb else N_TB_LADDER
            s_opts = ((split_k,) if split_k
                      else tuple(s for s in SPLIT_LADDER if s <= kt))
            for ntb in n_opts:
                for s in s_opts:
                    out.append(Schedule(mtb, ktb, ntb, s))
    return tuple(out)


def predicted(m: int, k: int, n: int, sparsity: float, sched: Schedule, *,
              group: int = 1, max_nnz: Optional[int] = None
              ) -> roofline.SplitKTerms:
    """Cost-model terms for one concrete schedule (bench/report helper)."""
    return roofline.lscd_splitk_terms(
        m, k, n, sparsity, m_tb=sched.m_tb, k_tb=sched.k_tb,
        n_tb=sched.n_tb, split_k=sched.split_k, group=group, max_nnz=max_nnz)


@functools.lru_cache(maxsize=4096)
def _select_analytic(m: int, k: int, n: int, sparsity: float,
                     m_tb: Optional[int], k_tb: Optional[int],
                     n_tb: Optional[int], split_k: Optional[int],
                     group: int, max_nnz: Optional[int],
                     backend: str = "pallas") -> Schedule:
    best = None
    best_key = None
    rejected: list = []
    for cand in candidates(m, k, n, m_tb=m_tb, k_tb=k_tb, n_tb=n_tb,
                           split_k=split_k):
        # A pinned max_nnz only describes the encoding the caller holds;
        # when sweeping tile geometry, re-estimate per candidate.
        nnz = max_nnz if (m_tb and k_tb) else None
        # Contract filter (KC-*, DESIGN.md §12): an unlaunchable candidate
        # must never win, whatever the cost model says about it.
        bad = contracts.check_schedule(
            m, k, n, m_tb=cand.m_tb, k_tb=cand.k_tb, n_tb=cand.n_tb,
            split_k=cand.split_k, group=group, max_nnz=nnz,
            sparsity=sparsity, backend=backend,
            path=f"select({m},{k},{n})")
        if bad:
            rejected.extend(bad)
            continue
        t = predicted(m, k, n, sparsity, cand, group=group, max_nnz=nnz)
        key = (t.effective_s, t.terms.hbm_bytes, cand.split_k, -cand.n_tb)
        if best_key is None or key < best_key:
            best, best_key = cand, key
    if best is None:
        raise contracts.ScheduleContractError(rejected)
    return best


def select(m: int, k: int, n: int, sparsity: float, *,
           m_tb: Optional[int] = None, k_tb: Optional[int] = None,
           n_tb: Optional[int] = None, split_k: Optional[int] = None,
           group: int = 1, max_nnz: Optional[int] = None,
           backend: str = "pallas",
           cache: "Optional[ScheduleCache] | bool" = None) -> Schedule:
    """Pick the launch schedule for one SpMM shape.

    Resolution order: fully-pinned overrides win outright; otherwise a
    measured-autotune cache entry (``cache`` arg or the
    ``REPRO_SCHEDULE_CACHE`` file) wins when its geometry is compatible
    with the pins; otherwise the analytic cost model decides. The analytic
    path is memoised — repeated dispatches for one shape are a dict hit.
    ``cache=False`` forces the pure analytic pick, ignoring the env cache
    (benchmarks and selection tests use this so a tuned developer cache
    cannot skew their output).

    ``sparsity``/``max_nnz`` feed the A-stream bytes term; pass the
    encoding's real ``TiledCSL.max_nnz`` when available (``ops.spmm``
    does) so the model charges exactly what the kernel DMAs.

    Every resolution path is validated against the launch contracts
    (``analysis.contracts``, rules KC-*): a fully-pinned invalid schedule
    raises :class:`~repro.analysis.contracts.ScheduleContractError` before
    any ``pallas_call``; an invalid *cache* entry (stale file, foreign
    machine, schema drift) is ignored and falls back to the analytic pick,
    so a poisoned cache can never produce an unlaunchable winner.
    """
    if n_tb is not None and split_k is not None and m_tb and k_tb:
        contracts.require_schedule(
            m, k, n, m_tb=m_tb, k_tb=k_tb, n_tb=n_tb, split_k=split_k,
            group=group, max_nnz=max_nnz, sparsity=sparsity,
            backend=backend, path=f"select({m},{k},{n})")
        return Schedule(m_tb, k_tb, n_tb, split_k)
    if cache is False:
        cache = None
    elif cache is None or cache is True:   # NB: an *empty* cache is falsy
        cache = _default_cache()           # too, so no truthiness tests
    if cache is not None:
        hit = cache.get(cache_key(m, k, n, sparsity, group=group,
                                  backend=backend, m_tb=m_tb, k_tb=k_tb))
        # A hit must be compatible with EVERY pin, tile geometry included —
        # a winner stored from an unpinned geometry sweep must not leak
        # into a launch whose encoding fixes different tiles.
        if hit is not None and (n_tb is None or hit.n_tb == n_tb) \
                and (split_k is None or hit.split_k == split_k) \
                and (m_tb is None or hit.m_tb == m_tb) \
                and (k_tb is None or hit.k_tb == k_tb) \
                and not contracts.check_schedule(
                    m, k, n, m_tb=hit.m_tb, k_tb=hit.k_tb, n_tb=hit.n_tb,
                    split_k=hit.split_k, group=group, max_nnz=max_nnz,
                    sparsity=sparsity, backend=backend):
            return hit
    return _select_analytic(m, k, n, round(float(sparsity), 4),
                            m_tb, k_tb, n_tb, split_k, group, max_nnz,
                            backend)


def autotune(t, n: int, *, backend: str,
             cache: Optional[ScheduleCache] = None, reps: int = 2,
             epilogue: str = "none",
             splits: Optional[Sequence[int]] = None,
             n_tbs: Optional[Sequence[int]] = None
             ) -> Tuple[Schedule, Dict[Schedule, float]]:
    """Measured schedule selection: time the real kernels per candidate.

    ``t`` is an encoded (possibly grouped) TiledCSL — its tile geometry is
    fixed, so the sweep covers ``n_tb`` x ``split_k`` only. The winner is
    persisted to ``cache`` (or the ``REPRO_SCHEDULE_CACHE`` file) under the
    shape+backend key, where :func:`select` finds it on the next dispatch.
    ``backend`` has no default: ``"pallas"`` times the TPU kernels, while
    ``"interpret"`` ranks schedules by traced work on the CPU, not by TPU
    wall time.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops  # late import: ops imports this module

    m, k = t.shape
    group = t.group or 1
    sparsity = sparsity_from_max_nnz(t.max_nnz, t.m_tb, t.k_tb)
    run = ops.spmm_grouped if t.group is not None else ops.spmm
    b = jnp.asarray(np.random.default_rng(0).standard_normal(
        (k, n)).astype(np.float32))

    timings: Dict[Schedule, float] = {}
    kt = t.grid[1]
    split_opts = tuple(splits) if splits else tuple(
        s for s in SPLIT_LADDER if s <= kt)
    for ntb in tuple(n_tbs) if n_tbs else N_TB_LADDER:
        for s in split_opts:
            sched = Schedule(t.m_tb, t.k_tb, ntb, s)
            # Contract filter (KC-*): never time — and so never persist —
            # a candidate that select() would refuse to launch.
            if contracts.check_schedule(
                    m, k, n, m_tb=t.m_tb, k_tb=t.k_tb, n_tb=ntb, split_k=s,
                    group=group, max_nnz=t.max_nnz, sparsity=sparsity,
                    backend=backend, path="autotune"):
                continue
            fn = functools.partial(run, t, b, backend=backend, n_tb=ntb,
                                   split_k=s, epilogue=epilogue,
                                   out_dtype=jnp.float32)
            jax.block_until_ready(fn())  # compile/warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn())
            timings[sched] = (time.perf_counter() - t0) / reps * 1e6
    if not timings:
        raise contracts.ScheduleContractError(contracts.check_schedule(
            m, k, n, m_tb=t.m_tb, k_tb=t.k_tb,
            n_tb=(tuple(n_tbs) if n_tbs else N_TB_LADDER)[0],
            split_k=split_opts[0], group=group, max_nnz=t.max_nnz,
            sparsity=sparsity, backend=backend, path="autotune"))
    best = min(timings, key=timings.get)
    # Belt and braces: the winner re-validates before it is persisted —
    # the JSON cache must never hold an unlaunchable schedule.
    contracts.require_schedule(
        m, k, n, m_tb=best.m_tb, k_tb=best.k_tb, n_tb=best.n_tb,
        split_k=best.split_k, group=group, max_nnz=t.max_nnz,
        sparsity=sparsity, backend=backend, path="autotune")
    if cache is None:           # NB: not `or` — an empty cache is falsy
        cache = _default_cache()
    if cache is not None:
        cache.put(cache_key(m, k, n, sparsity, group=group, backend=backend,
                            m_tb=t.m_tb, k_tb=t.k_tb),
                  best, measured_us=timings[best])
        cache.save()
    return best, timings
