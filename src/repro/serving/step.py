"""Device-stepping layer of the serving stack (DESIGN.md §13).

The other half of the old ``serving/batching.py`` monolith: everything that
touches a device array lives here. :class:`DeviceStepper` owns the model
params, the K/V cache (dense slots or the paged block pool's physical
blocks), and the three jitted entry points — bucketed prefill
(`engine.prefill_into_slots` / `engine.prefill_into_pages`), per-slot-
position batched decode, and the speculative verify window
(`engine.verify_step`). It executes whatever the scheduling core
(`serving/scheduler.py`) planned, verbatim: a stepper call never changes
scheduling state, and the scheduler never sees a device array — numpy in,
numpy out across the boundary.

Sampling matches `engine.generate` semantics (temperature / top-k via
`engine.sample`): each slot draws with a key folded by (request uid, token
index), so streams are independent of admission order and preemption. The
scheduler supplies the (uid, count) folds; the key material and the fold
itself stay on this side of the boundary.

Fault surface (DESIGN.md §14): an optional `serving.faults.FaultInjector`
hooks every launch — ``check_launch`` may raise a ``TransientStepError``
*before* anything touches the device (the facade retries; no state moved,
so the retried launch is bitwise the original), and ``poison_mask`` rows
get their logits overwritten with NaN *inside the computation*, so the
per-step non-finite scan (``ok`` masks returned by decode /
sample_admitted) exercises the same detection path a real numerical fault
would take. Injection off ⇒ both hooks are dead code.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.obs.trace import Tracer, get_tracer
from repro.serving import engine


class DeviceStepper:
    """Owns params + cache + jitted prefill/decode/verify for one server.

    ``physical_blocks`` selects the paged cache (pass the pool's physical
    block count, i.e. usable blocks + the trash block); None selects the
    dense ``[n_slots, max_len]`` cache. ``spec_k > 0`` additionally builds
    the verify-window jit.
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 max_len: int, backend: str = "auto",
                 physical_blocks: Optional[int] = None, block_size: int = 16,
                 ring_len: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 spec_k: int = 0, chunk_size: int = 0, faults=None,
                 tracer: Optional[Tracer] = None):
        self.params = params
        self.cfg = cfg
        self.backend = backend
        self.tracer = tracer if tracer is not None else get_tracer()
        self.ring_len = ring_len
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._base_key = jax.random.PRNGKey(seed)
        self.faults = faults                    # serving.faults.FaultInjector
        self._no_poison = np.zeros(n_slots, bool)
        self.paged = physical_blocks is not None
        if self.paged:
            self.cache = transformer.init_paged_cache(
                cfg, physical_blocks, block_size)
            self._prefill = jax.jit(
                lambda p, c, t, bm, l: engine.prefill_into_pages(
                    p, c, t, bm, l, self.cfg, backend=self.backend))
        else:
            self.cache = transformer.init_cache(cfg, n_slots, max_len)
            self._prefill = jax.jit(
                lambda p, c, t, s, l: engine.prefill_into_slots(
                    p, c, t, s, l, self.cfg, backend=self.backend))
        self._decode = jax.jit(
            lambda p, c, t, pos, tab, u, n, poison: self._decode_step(
                p, c, t, pos, tab, u, n, poison))
        if spec_k:
            self._verify = jax.jit(
                lambda p, c, t, pos, tab, dl, u, n: engine.verify_step(
                    p, c, t, pos, tab, dl, u, n, self.cfg,
                    ring_len=self.ring_len, temperature=self.temperature,
                    top_k=self.top_k, base_key=self._base_key,
                    backend=self.backend))
        if chunk_size:
            # mixed prefill-chunk + decode step (DESIGN.md §16): one static
            # [n_slots, chunk_size] shape regardless of the per-step chunk
            # grant — exactly ONE compile for the server's lifetime
            # (budgets.COMPILE_BUDGETS["batcher_mixed"])
            self._mixed = jax.jit(
                lambda p, c, t, pos, tab, nt, u, n, poison:
                self._mixed_step(p, c, t, pos, tab, nt, u, n, poison))

    # -- jitted per-slot-position decode: positions differ per slot --------
    def _decode_step(self, params, cache, token, pos_vec, tables, uids,
                     counts, poison):
        """token: [B,1]; pos_vec: [B] — per-slot absolute positions.

        The decode path accepts a position *vector*: each slot's K/V is
        written at its own cache index and masked by its own causal bound,
        so one batched step serves slots at heterogeneous progress.
        ``tables`` routes the paged block-pool path; ``uids``/``counts``
        fold the per-slot sampling keys (unused — and dead-code-eliminated
        — for greedy decoding). ``poison`` ([B] bool) overwrites injected
        rows' logits with NaN before the non-finite scan — chaos testing
        exercises the same ``ok`` detection a real numerical fault hits.
        """
        logits, cache, _ = transformer.forward(
            params, {"tokens": token}, self.cfg, mode="decode",
            cache=cache, pos=pos_vec, block_tables=tables,
            ring_len=self.ring_len if tables is not None else None,
            backend=self.backend)
        logits = logits[:, -1]
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if self.temperature == 0.0:
            tok = jnp.argmax(logits, axis=-1)
        else:
            keys = engine.fold_slot_keys(self._base_key, uids, counts)
            tok = engine.sample_per_slot(logits, keys,
                                         temperature=self.temperature,
                                         top_k=self.top_k)
        return tok, ok, cache

    def _mixed_step(self, params, cache, tokens, pos_vec, tables, n_tokens,
                    uids, counts, poison):
        """Mixed prefill-chunk/decode launch (DESIGN.md §16): tokens
        [B, chunk_size], per-slot real-column counts ``n_tokens`` (1 for a
        decode slot, 0 idle). The sampled token is each slot's *last real
        column's* distribution — meaningful for decode slots and slots
        whose final chunk just completed, drawn with the identical folded
        (uid, token-index) key plain decode / sample_admitted would use,
        so chunked streams are bitwise the bucketed ones."""
        logits, cache = engine.prefill_chunk_into_pages(
            params, cache, tokens, pos_vec, tables, n_tokens, self.cfg,
            ring_len=self.ring_len, backend=self.backend)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if self.temperature == 0.0:
            tok = jnp.argmax(logits, axis=-1)
        else:
            keys = engine.fold_slot_keys(self._base_key, uids, counts)
            tok = engine.sample_per_slot(logits, keys,
                                         temperature=self.temperature,
                                         top_k=self.top_k)
        return tok, ok, cache

    # -- execution surface the facade drives --------------------------------
    @property
    def prefill_compiles(self) -> Optional[int]:
        """Distinct prefill shapes compiled so far (one per bucket hit);
        None if the jit internals moved and the count is unavailable."""
        try:
            return int(self._prefill._cache_size())
        except (AttributeError, TypeError):   # jit internals moved
            return None

    def prefill(self, tokens: np.ndarray, targets: np.ndarray,
                lens: np.ndarray):
        """Run one admission plan's prefill; ``targets`` is the slot vector
        (dense) or the scratch block map (paged). Returns last-position
        logits [k, V] (device array — fed straight to sample_admitted)."""
        if self.faults is not None:
            self.faults.check_launch("prefill")
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        logits, self.cache = self._prefill(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(targets), jnp.asarray(lens))
        if tr.enabled:   # the enqueue only: sample_admitted awaits it
            tr.span("step", "prefill", "engine", t0,
                    rows=int(tokens.shape[0]), bucket=int(tokens.shape[1]),
                    real_tokens=int(np.sum(lens)))
        if self.faults is not None:
            mask = self.faults.poison_mask("prefill", logits.shape[0])
            if mask is not None:
                logits = jnp.where(jnp.asarray(mask)[:, None], jnp.nan,
                                   logits)
        return logits

    def sample_admitted(self, logits, uids: np.ndarray, counts: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """First token of each admitted request, via the same per-slot key
        folding as decode ((uid, token index) -> key), so a preempted
        request's re-prefill redraws its identical next token. Also
        returns the rows' non-finite scan ([k] bool ``ok``) — the
        scheduler quarantines rows that fail it."""
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if self.temperature == 0.0:
            tok = jnp.argmax(logits, axis=-1)
        else:
            keys = engine.fold_slot_keys(self._base_key, jnp.asarray(uids),
                                         jnp.asarray(counts))
            tok = engine.sample_per_slot(logits, keys,
                                         temperature=self.temperature,
                                         top_k=self.top_k)
        tok, ok = self._to_host(tok, ok)
        if tr.enabled:
            tr.span("step", "sample", "engine", t0, rows=int(len(tok)))
        return tok, ok

    def _to_host(self, a, b) -> Tuple[np.ndarray, np.ndarray]:
        """Block until the host holds both device results; the blocking
        read is the ``wait`` span (host time spent blocked on the
        device)."""
        tr = self.tracer
        if not tr.enabled:
            return np.asarray(a), np.asarray(b)
        t0 = tr.clock()
        out = np.asarray(a), np.asarray(b)
        tr.span("step", "wait", "engine", t0)
        return out

    def apply_copies(self, copies: Iterable[Tuple[int, int]]) -> None:
        """Apply the scheduler's queued copy-on-write block copies (device
        gather/scatter) before the decode/verify launch reads them."""
        for src, dst in copies:
            self.cache = transformer.copy_cache_block(
                self.cfg, self.cache, src, dst)

    def decode(self, last_token: np.ndarray, pos: np.ndarray,
               table_arr: Optional[np.ndarray],
               uids: Optional[np.ndarray],
               counts: Optional[np.ndarray]
               ) -> Tuple[np.ndarray, np.ndarray]:
        """One batched decode token for every slot (inactive slots produce
        garbage the scheduler ignores). Returns (next tokens [n_slots],
        non-finite-scan ``ok`` [n_slots] — False rows get quarantined)."""
        if self.faults is not None:
            self.faults.check_launch("decode")
            poison = self.faults.poison_mask("decode", len(self._no_poison))
        else:
            poison = None
        if poison is None:
            poison = self._no_poison
        tables = jnp.asarray(table_arr) if table_arr is not None else None
        if uids is not None:
            uids, counts = jnp.asarray(uids), jnp.asarray(counts)
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        tok, ok, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(last_token[:, None]),
            jnp.asarray(pos), tables, uids, counts, jnp.asarray(poison))
        tok, ok = self._to_host(tok, ok)
        if tr.enabled:
            args = {"batch": int(len(self._no_poison))}
            if table_arr is not None:
                from repro.serving import paged_cache
                args["blocks_touched"] = int(
                    np.sum(table_arr != paged_cache.TRASH_BLOCK))
            tr.span("step", "decode", "engine", t0, **args)
        return tok, ok

    def mixed(self, tokens: np.ndarray, pos: np.ndarray,
              table_arr: np.ndarray, n_tokens: np.ndarray,
              uids: np.ndarray, counts: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
        """One mixed prefill-chunk + decode launch over every slot; returns
        (next tokens [n_slots], non-finite-scan ``ok`` [n_slots]). Fault
        hooks mirror decode: ``check_launch``/``poison_mask`` fire on op
        "mixed" (and "any"), feeding the same quarantine path."""
        if self.faults is not None:
            self.faults.check_launch("mixed")
            poison = self.faults.poison_mask("mixed", len(self._no_poison))
        else:
            poison = None
        if poison is None:
            poison = self._no_poison
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        tok, ok, self.cache = self._mixed(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(pos), jnp.asarray(table_arr),
            jnp.asarray(n_tokens), jnp.asarray(uids),
            jnp.asarray(counts), jnp.asarray(poison))
        tok, ok = self._to_host(tok, ok)
        if tr.enabled:
            tr.span("step", "mixed", "engine", t0,
                    batch=int(tokens.shape[0]), window=int(tokens.shape[1]),
                    real_positions=int(np.sum(n_tokens)))
        return tok, ok

    def verify(self, tokens: np.ndarray, pos: np.ndarray,
               table_arr: np.ndarray, draft_lens: np.ndarray,
               uids: np.ndarray, counts: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative verify window over every slot; returns the
        target-emitted tokens [n_slots, k+1] and per-slot accept counts.
        (NaN injection targets the prefill/decode launches; under repeated
        faults the degradation ladder turns speculation off, so the scanned
        decode path is the one that keeps running.)"""
        if self.faults is not None:
            self.faults.check_launch("verify")
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        tgt, n_acc, self.cache = self._verify(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(pos), jnp.asarray(table_arr),
            jnp.asarray(draft_lens), jnp.asarray(uids),
            jnp.asarray(counts))
        tgt, n_acc = self._to_host(tgt, n_acc)
        if tr.enabled:
            tr.span("step", "verify", "engine", t0,
                    batch=int(tokens.shape[0]), window=int(tokens.shape[1]),
                    drafted=int(np.sum(draft_lens)))
        return tgt, n_acc
