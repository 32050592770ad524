"""Continuous batching: the compatibility facade over scheduler + stepper.

The paper's throughput win comes from freeing GPU memory (sparse weights) so
*more* requests fit in flight (Table 1: batch 64 on one GPU vs OOM for
dense). The serving loop converts that memory headroom into
tokens/GPU-second: a fixed pool of B decode slots; finished/empty slots are
refilled from a request queue without stopping the decode loop.

Since the DESIGN.md §13 layer split, the loop itself lives in two modules:

* `serving/scheduler.py` — the scheduling-policy core: bucketed FIFO
  admission, block-availability gating, preemption, speculative staging,
  cancellation, metrics. Pure host state machine; plans work, commits
  results, never touches a device array.
* `serving/step.py` — the device layer: params, K/V cache, and the jitted
  prefill / decode / verify entry points that execute those plans.

:class:`ContinuousBatcher` composes the two behind the original monolith's
interface (submit / step / run_to_completion, plus the introspection
attributes the tests and benches rely on: ``slots``, ``queue``, ``pos``,
``tables``, ``pool``, ``metrics``…). New code that wants streaming,
cancellation, or backpressure should sit on `serving/api.py`, which wraps
this facade with session-oriented request/response schemas.

Admission path (the part traffic diversity stresses):

* **Bucketed prefill** — prompts are right-padded to a small set of static
  power-of-two length buckets (``engine.length_buckets``), so the jitted
  prefill compiles at most ``ceil(log2(max_len))`` times no matter how many
  distinct prompt lengths arrive. Pure-attention stacks only; recurrent
  stacks (ssm/rglru) degrade to exact-length buckets because pad tokens
  would pollute the carried state.
* **In-slot prefill** — ``engine.prefill_into_slots`` computes the prompt
  K/V in a small ``[k, bucket]`` scratch cache and scatter-writes it into
  the shared ``[n_slots, max_len]`` cache at the target slots *inside the
  jit* — no throwaway ``[1, max_len]`` cache, no host-side tree splice.
* **Batched admission** — up to ``admit_k`` queued requests from the same
  bucket are prefillled in one call; groups are padded to a static ``k`` by
  duplicating a real row (duplicate slot scatter with identical data is
  well-defined), so ``k`` never adds compile shapes.

Decode is the ordinary batched ``serve_step`` regime: one token for every
slot per engine step, each slot at its own absolute position. Requests
terminate on EOS / stop tokens, on their ``max_new_tokens`` budget, or when
the slot's cache region is exhausted (``max_len`` truncation).
``SchedulerMetrics`` counts what the loop did (occupancy, queue wait,
prefill vs decode tokens, padding overhead, TTFT/TPOT, compile count) —
surfaced by ``benchmarks/serving_load.py`` and ``examples/serve_batched.py``.

Cache kinds (DESIGN.md §7 vs §10):

* ``cache_kind="dense"`` — the original shared ``[n_slots, max_len]``
  cache; a slot pre-reserves ``max_len`` positions whether used or not.
* ``cache_kind="paged"`` — the paged block-pool cache: requests hold only
  the KV blocks they have filled (`serving.paged_cache.BlockPool`), full
  prompt blocks are prefix-shared by content chain-hash, and admission is
  gated on *block availability* (prompt blocks + a reservation margin)
  instead of free-slot counting. On pool exhaustion mid-decode the
  youngest request is preempted and re-queued head-of-line (recompute
  resume: its prompt+generated tokens re-prefill on re-admission, which
  regenerates an identical stream for greedy and for the per-slot folded
  sampling keys alike) — the loop never deadlocks. ``n_slots`` remains
  the decode batch width; memory admission is the block pool, sized by
  `serving.budget.plan` from the Tiled-CSL weight savings.

Sampling matches `engine.generate` semantics (temperature / top-k via
`engine.sample`): each slot draws with a key folded by (request uid, token
index), so streams are independent of admission order and preemption.
"""

from __future__ import annotations

import time
import warnings
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core import tiled_csl
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs.trace import Tracer, get_tracer
from repro.serving import engine, faults, speculative
from repro.serving.config import ServeConfig, SLOSpec
from repro.serving.scheduler import (DegradationPolicy,  # noqa: F401
                                     Request, Scheduler, SchedulerMetrics)
from repro.serving.step import DeviceStepper


class ContinuousBatcher:
    """Slot-based continuous batching over a fixed decode batch B.

    eos_id / stop_ids: generation stops when the model emits any of these
    (the stop token is kept in ``generated``). ``admit_k`` is the static
    admission batch — up to that many same-bucket requests prefill in one
    call. ``min_bucket`` floors the bucket ladder so tiny prompts share one
    compile.

    ``cache_kind="paged"`` swaps the dense per-slot cache for the block
    pool (module docstring): ``block_size`` positions per block,
    ``n_blocks`` usable blocks (default: the dense cache's exact byte
    equivalent, n_slots * blocks_per_seq — pass the `budget.plan` output to
    spend a real HBM budget), ``reserve_blocks`` held back at admission as
    the decode-growth margin, ``prefix_sharing`` dedupes full prompt blocks
    by content (disabled for sliding-window rings, whose blocks are
    overwritten cyclically). ``temperature`` / ``top_k`` / ``seed`` select
    per-slot sampling (0.0 = exact greedy, the default).

    ``spec_k > 0`` turns on speculative decoding (DESIGN.md §11, paged
    cache only — rollback rides the block machinery): each step the
    ``drafter`` (default `speculative.NgramDrafter`) proposes up to
    ``spec_k`` tokens per slot from the slot's own history, one
    ``engine.verify_step`` scores all k+1 window positions, and the slot
    advances by 1 + accepted tokens. Greedy streams are bitwise the
    non-speculative ones; sampled streams match too because the verify
    columns draw with the same (uid, token-index)-folded keys.

    ``clock`` injects the wall-clock source for the per-request latency
    stamps (default ``time.monotonic``; `serving.loadgen.StepClock` makes
    replayed traces deterministic).

    Configuration (DESIGN.md §16): pass ``config=ServeConfig(...)``. The
    legacy flat keyword set still works — the facade maps it onto a
    ServeConfig via ``ServeConfig.from_kwargs`` and emits a
    ``DeprecationWarning``. Live collaborators (``drafter``, ``clock``,
    ``fault_plan``, ``degradation``, ``tracer``) stay explicit arguments.
    """

    def __init__(self, params, cfg: ModelConfig, *,
                 config: Optional[ServeConfig] = None,
                 drafter=None,
                 clock: Optional[Callable[[], float]] = None,
                 fault_plan=None, degradation=None,
                 tracer: Optional[Tracer] = None, **legacy):
        if config is None:
            if legacy:
                warnings.warn(
                    "flat ContinuousBatcher/StreamingServer kwargs are "
                    "deprecated; pass config=ServeConfig(...) "
                    "(serving/config.py)", DeprecationWarning, stacklevel=3)
            config = ServeConfig.from_kwargs(**legacy)
        elif legacy:
            raise TypeError(f"pass config=ServeConfig(...) OR legacy "
                            f"kwargs, not both: {sorted(legacy)}")
        config.validate()
        sc = config.scheduler
        if cfg.n_codebooks:
            raise ValueError("codebook (audio) archs need [n_cb, S] prompts; "
                             "drive engine.generate directly")
        self.config = config
        self.params = params
        self.cfg = cfg
        self.n_slots = sc.n_slots
        self.max_len = sc.max_len
        self.backend = config.backend
        self.paged = config.cache_kind == "paged"
        self.temperature = float(config.temperature)
        self.top_k = int(config.top_k)
        stop = frozenset(([] if sc.eos_id is None else [int(sc.eos_id)])
                         + [int(t) for t in sc.stop_ids])
        self.admit_k = max(1, min(sc.admit_k or min(sc.n_slots, 4),
                                  sc.n_slots))
        # Recurrent state (ssm/rglru) cannot absorb pad tokens — bucket
        # padding is exact only for pure-attention stacks. Others degrade to
        # exact-length "buckets" (one compile per distinct length, as before
        # this scheduler existed — never worse, attention archs far better).
        self._pure_attn = all(cfg.layer_kind(i) == "attn"
                              for i in range(cfg.n_layers))
        buckets = (engine.length_buckets(sc.max_len, sc.min_bucket)
                   if self._pure_attn else None)
        # Ring length for sliding-window configs (positions live at
        # ``pos % ring_len``; None for ordinary causal stacks).
        self.ring_len = (min(sc.max_len, cfg.local_window)
                         if cfg.local_window is not None else None)
        self.spec_k = int(config.spec_k)
        self.drafter = drafter
        self.chunked = bool(sc.chunked_prefill)
        if self.chunked and self.ring_len is not None:
            raise ValueError(
                "chunked_prefill does not support sliding-window (ring) "
                "stacks: chunk windows assume monotone cache positions; "
                "use bucketed admission for this arch")
        if self.spec_k:
            if not self.paged:
                raise ValueError(
                    "speculative decoding (spec_k > 0) requires "
                    "cache_kind='paged': rejected-window rollback rides "
                    "the block machinery (DESIGN.md §11)")
            if self.ring_len is not None and self.spec_k + 1 > self.ring_len:
                raise ValueError(
                    f"verify window {self.spec_k + 1} exceeds the sliding-"
                    f"window ring ({self.ring_len}); lower spec_k")
            if self.drafter is None:
                self.drafter = speculative.NgramDrafter()
        n_blocks = config.n_blocks
        if self.paged:
            self.block_size = config.block_size
            self.max_blocks = transformer.paged_blocks_per_seq(
                cfg, sc.max_len, config.block_size)
            if n_blocks is None:
                n_blocks = sc.n_slots * self.max_blocks  # dense byte-equiv
        self.max_step_retries = int(config.max_step_retries)
        self.retry_backoff_s = float(config.retry_backoff_s)
        self.faults = (fault_plan if isinstance(fault_plan,
                                                faults.FaultInjector)
                       else faults.FaultInjector(fault_plan)
                       if fault_plan is not None else None)
        self.tracer = tracer if tracer is not None else get_tracer()
        if self.faults is not None:
            self.faults.tracer = self.tracer    # one timeline per server
        self.sched = Scheduler(
            n_slots=sc.n_slots, max_len=sc.max_len, stop_ids=stop,
            admit_k=self.admit_k, buckets=buckets, ring_len=self.ring_len,
            paged=self.paged, block_size=config.block_size,
            n_blocks=n_blocks,
            max_blocks=self.max_blocks if self.paged else 0,
            reserve_blocks=sc.reserve_blocks,
            prefix_sharing=config.prefix_sharing,
            request_history=sc.request_history, spec_k=self.spec_k,
            drafter=self.drafter, sampled=self.temperature != 0.0,
            chunked=self.chunked, chunk_size=sc.chunk_size,
            chunk_budget=sc.chunk_budget,
            clock=clock, degradation=degradation, tracer=self.tracer)
        self.sched.metrics.lscd_slab_share = (
            tiled_csl.slab_share(params) or 0.0)
        self.stepper = DeviceStepper(
            params, cfg, n_slots=sc.n_slots, max_len=sc.max_len,
            backend=config.backend,
            physical_blocks=(self.sched.pool.physical_blocks
                             if self.paged else None),
            block_size=config.block_size, ring_len=self.ring_len,
            temperature=config.temperature, top_k=config.top_k,
            seed=config.seed, spec_k=self.spec_k,
            chunk_size=sc.chunk_size if self.chunked else 0,
            faults=self.faults, tracer=self.tracer)

    # -- delegation: the monolith's introspection surface -------------------
    @property
    def buckets(self):
        return self.sched.buckets

    @property
    def stop_ids(self):
        return self.sched.stop_ids

    @property
    def queue(self):
        return self.sched.queue

    @property
    def requests(self):
        return self.sched.requests

    @property
    def slots(self):
        return self.sched.slots

    @property
    def pos(self):
        return self.sched.pos

    @property
    def last_token(self):
        return self.sched.last_token

    @property
    def tables(self):
        return self.sched.tables

    @property
    def pool(self):
        return self.sched.pool

    @property
    def metrics(self) -> SchedulerMetrics:
        return self.sched.metrics

    @metrics.setter
    def metrics(self, value: SchedulerMetrics) -> None:
        self.sched.metrics = value

    @property
    def cache(self):
        return self.stepper.cache

    @cache.setter
    def cache(self, value) -> None:
        self.stepper.cache = value

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes compiled so far (one per bucket hit)."""
        n = self.stepper.prefill_compiles
        if n is None:  # jit internals moved — fall back to buckets seen
            return len(self.metrics.bucket_admits)
        return n

    @property
    def busy(self) -> bool:
        """Anything queued or decoding — ``run_to_completion``'s (and the
        session API's) drain condition."""
        return self.sched.busy

    # -- public API ---------------------------------------------------------
    def submit(self, uid: int, prompt: np.ndarray, max_new_tokens: int, *,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None,
               slo: Optional[SLOSpec] = None) -> Request:
        return self.sched.submit(uid, prompt, max_new_tokens,
                                 ttft_deadline_s=ttft_deadline_s,
                                 deadline_s=deadline_s, slo=slo)

    def cancel(self, uid: int) -> Optional[Request]:
        """Cancel a live request in any state (queued, active, preempted);
        see :meth:`Scheduler.cancel`."""
        return self.sched.cancel(uid)

    def _launch(self, op: str, fn):
        """Run one device launch, retrying injected (or wrapped-real)
        transient failures with bounded exponential backoff. A
        ``TransientStepError`` raises *before* anything touches the device,
        so re-running ``fn`` is bitwise the launch that should have
        happened; each backoff advances the virtual clock (deadlines see
        the lost time). Exhausting the budget raises ``StepFault`` —
        scheduler state is still consistent, the step just never ran."""
        delay = self.retry_backoff_s
        attempt = 0
        while True:
            try:
                return fn()
            except faults.TransientStepError as e:
                attempt += 1
                self.sched.metrics.step_retries += 1
                self.sched.note_fault()
                tr = self.tracer
                if tr.enabled:
                    tr.event("fault", "retry", "engine", op=op,
                             attempt=attempt, backoff_s=delay)
                if attempt > self.max_step_retries:
                    raise faults.StepFault(op, attempt, e) from e
                self.sched.advance_clock(delay)
                delay *= 2.0

    def step(self) -> Dict[int, List[int]]:
        """Admit + decode one token for all active slots (1 + accepted
        drafts with ``spec_k``). Returns finished — which under fault
        injection may include sessions ended by deadline expiry or slot
        quarantine, each with its explicit ``finish_reason``."""
        sched = self.sched
        m = sched.metrics
        tr = self.tracer
        t_step = tr.clock() if tr.enabled else 0.0
        finished: Dict[int, List[int]] = {}
        inj = self.faults
        if inj is not None:
            inj.begin_step(m.steps)
            delay = inj.delay_s()
            if delay:
                sched.advance_clock(delay)       # latency spike → deadlines
            sched.inject_drafter_fault = inj.drafter_fails()
            if self.paged:
                for ev in inj.storms():
                    sched.seize_blocks(ev.blocks, ev.duration)
        if self.paged:
            sched.release_seized()               # expired storms give back
        sched.expire_deadlines(finished)
        sched.update_degradation()
        t0 = time.monotonic()
        t_admit = tr.clock() if tr.enabled else 0.0
        calls, admitted = m.prefill_calls, m.admitted
        buckets = [] if tr.enabled else None
        if self.chunked:
            # §16 admission: slot assignment + block mapping only — the
            # prompt K/V streams in through the mixed step's chunks below
            if not sched.shedding:
                sched.admit_chunked()
        else:
            while not sched.shedding:
                plan = sched.plan_admission()
                if plan is None:
                    break
                logits = self._launch(
                    "prefill", lambda: self.stepper.prefill(
                        plan.tokens, plan.targets, plan.lens))
                m.compute_positions += plan.tokens.size
                nxt, ok = self.stepper.sample_admitted(logits, plan.uids,
                                                       plan.counts)
                sched.commit_admission(plan, nxt, finished, ok=ok)
                if buckets is not None:
                    buckets.append(plan.bucket)
        m.admit_time_s += time.monotonic() - t0
        launches = m.prefill_calls - calls
        if tr.enabled:
            tr.span("step", "admit", "engine", t_admit, launches=launches,
                    rows=m.admitted - admitted, buckets=buckets)
        staged: Dict[int, np.ndarray] = {}
        mixed_plan = None
        if self.paged:
            # Growth / copy-on-write / preemption happen before the step,
            # so the jitted decode sees fully-valid tables.
            t_stage = tr.clock() if tr.enabled else 0.0
            if self.chunked:
                mixed_plan, copies = sched.stage_mixed()
            elif self.spec_k and sched.effective_spec_k:
                staged, copies = sched.stage_spec()
            else:
                copies = sched.prepare_decode()
            self.stepper.apply_copies(copies)
            if tr.enabled:
                tr.span("step", "stage", "engine", t_stage,
                        copies=len(copies))
            m.blocks_in_use = sched.pool.blocks_in_use
            m.peak_blocks_in_use = max(m.peak_blocks_in_use, m.blocks_in_use)
        active = sched.active_slot_ids()
        m.steps += 1
        m.slot_steps += self.n_slots
        m.active_slot_steps += len(active)
        m.peak_active_slots = max(m.peak_active_slots, len(active))
        if not active:
            self._trace_step(t_step, 0, len(finished), launches)
            return finished
        t0 = time.monotonic()
        if mixed_plan is not None and mixed_plan.chunks:
            tok, ok = self._launch("mixed", lambda: self.stepper.mixed(
                mixed_plan.tokens, sched.pos, sched.table_arr,
                mixed_plan.n_tokens, mixed_plan.uids, mixed_plan.counts))
            m.compute_positions += mixed_plan.tokens.size
            m.mixed_steps += 1
            t_commit = tr.clock() if tr.enabled else 0.0
            if tr.enabled:
                # paired with the mixed_steps counter (obs pass OB-EVENT)
                tr.event("sched", "chunk", "scheduler",
                         slots=len(mixed_plan.chunks),
                         tokens=int(sum(mixed_plan.chunks.values())))
            for s in mixed_plan.decode_slots + list(mixed_plan.chunks):
                if not ok[s]:                    # non-finite logits: contain
                    sched.quarantine_slot(s, finished)
            good = [s for s in mixed_plan.decode_slots if ok[s]]
            if good:
                sched.commit_decode(good, tok, finished)
            chunks = {s: n for s, n in mixed_plan.chunks.items() if ok[s]}
            sched.commit_chunks(chunks, tok, finished)
            committed = len(good) + len(chunks)
        elif self.spec_k and any(len(staged.get(s, ())) for s in active):
            vb = sched.build_verify(active, staged)
            tgt, n_acc = self._launch("verify", lambda: self.stepper.verify(
                vb.tokens, sched.pos, sched.table_arr, vb.draft_lens,
                vb.uids, vb.counts))
            m.compute_positions += vb.tokens.size
            t_commit = tr.clock() if tr.enabled else 0.0
            sched.commit_verify(active, tgt, n_acc, finished)
            committed = len(active)
        else:
            # No drafts anywhere (or spec off): ordinary one-token decode —
            # the drafter contract's degradation path, at window width 1
            # instead of a wasted (k+1)-wide verify.
            uids, counts = sched.decode_folds(active)
            nxt, ok = self._launch("decode", lambda: self.stepper.decode(
                sched.last_token, sched.pos,
                sched.table_arr if self.paged else None, uids, counts))
            m.compute_positions += self.n_slots
            t_commit = tr.clock() if tr.enabled else 0.0
            good = [s for s in active if ok[s]]
            for s in active:
                if not ok[s]:                    # non-finite logits: contain
                    sched.quarantine_slot(s, finished)
            if good:
                sched.commit_decode(good, nxt, finished)
            committed = len(good)
        if tr.enabled:
            tr.span("step", "commit", "engine", t_commit, committed=committed)
        m.decode_time_s += time.monotonic() - t0
        if self.paged:
            # refresh after completions freed their tables (the pre-decode
            # sample above is the high-water mark)
            m.blocks_in_use = sched.pool.blocks_in_use
        self._trace_step(t_step, len(active), len(finished), launches)
        return finished

    def _trace_step(self, t0: float, n_active: int, n_finished: int,
                    launches: int) -> None:
        """The ``step`` span over the whole server step — the timeline's
        heartbeat, paired with ``metrics.steps`` (fault firings are traced
        at the source, ``FaultInjector._fire``)."""
        tr = self.tracer
        if not tr.enabled:
            return
        sched = self.sched
        tr.span("step", "step", "engine", t0, step=sched.metrics.steps,
                active=n_active, finished=n_finished,
                queue=sched.queue_depth, admit_launches=launches,
                blocks_in_use=sched.pool.blocks_in_use if self.paged else 0,
                degradation=sched.degradation.level)

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for _ in range(max_steps):
            out.update(self.step())
            if not self.busy:
                break
        return out
