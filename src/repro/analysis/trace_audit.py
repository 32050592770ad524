"""Trace auditor: jit-trace registered entry points and vet the jaxprs.

The serving hot path (DESIGN.md §7/§10/§11) is a handful of jitted step
functions; three classes of regression hide inside their traces rather
than their outputs, so tests keep missing them:

TA-CALLBACK  a host callback / infeed / outfeed primitive in a step trace
             forces a device->host sync every step — a silent 10-100x
             decode-latency cliff. (``jax.debug.print`` left in by a
             debugging session is the classic case.)
TA-UPCAST    a large bf16->f32 ``convert_element_type`` in a bf16 path
             doubles the HBM traffic of the very tensors Flash-LLM exists
             to shrink. Small converts (sampling temps, norms, f32
             softmax accumulations under :data:`UPCAST_MIN_ELEMS`
             elements) are idiomatic and ignored; Pallas kernel bodies are
             skipped outright — their f32 accumulators are the KC-ACC
             *requirement*.
TA-RETRACE   an entry point compiling more jit-cache entries than its
             budget (``analysis.budgets.compile_budget``) — e.g. a Python
             float sneaking into a traced signature recompiles per value.
             This is the shared-table version of the ``jax.monitoring``
             assertion ``tests/test_serving.py`` runs.

Entry points are *registered* here (:func:`default_entries`): bucketed
slot prefill, the decode step, the speculative verify step, and the spmm
dispatch — each built on the tinyllama smoke config at canonical shape
buckets, mirroring the batcher's jitted lambdas. Audits run on CPU; the
jaxpr is backend-independent, so hygiene holds for the TPU build too.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis import budgets
from repro.analysis.findings import Finding

#: bf16->f32 converts at or above this element count are flagged (rule
#: TA-UPCAST). 64Ki elements = 256 KiB of f32 — weight/cache scale, far
#: above sampling scalars and per-row norm statistics.
UPCAST_MIN_ELEMS = 65536

#: primitive names that force host synchronization in a step path.
CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "callback", "debug_callback",
    "debug_print", "infeed", "outfeed", "host_callback_call",
}

#: primitives whose inner jaxpr is intentionally NOT audited.
_SKIP_INNER = {"pallas_call"}


@dataclasses.dataclass
class EntryPoint:
    """One audited entry: ``build()`` returns ``(fn, calls)`` where ``fn``
    is the un-jitted callable and ``calls`` the canonical argument tuples
    (one per shape bucket). ``budget_params`` feed
    ``budgets.compile_budget(name_in_table, **budget_params)``."""

    name: str
    build: Callable[[], Tuple[Callable, List[tuple]]]
    budget_params: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def path(self) -> str:
        return f"trace:{self.name}"


def _walk_eqns(jaxpr, visit) -> None:
    """Depth-first over eqns, recursing into sub-jaxprs (scan/while/cond/
    pjit bodies) but not into :data:`_SKIP_INNER` primitives."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        if eqn.primitive.name in _SKIP_INNER:
            continue
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _walk_eqns(sub, visit)


def _sub_jaxprs(val):
    import jax.extend.core as jcore
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v


def audit_jaxpr(jaxpr, path: str, *,
                upcast_min_elems: int = UPCAST_MIN_ELEMS) -> List[Finding]:
    """TA-CALLBACK + TA-UPCAST over one (closed) jaxpr."""
    import jax.numpy as jnp

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Finding] = []

    def visit(eqn):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS:
            out.append(Finding(
                "TA-CALLBACK", path, 0,
                f"host primitive {name!r} in the step trace",
                hint="remove debug callbacks / host syncs from jitted "
                     "step functions"))
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (getattr(src, "dtype", None) == jnp.bfloat16
                    and getattr(dst, "dtype", None) == jnp.float32
                    and src.size >= upcast_min_elems):
                out.append(Finding(
                    "TA-UPCAST", path, 0,
                    f"bf16->f32 convert of shape {tuple(src.shape)} "
                    f"({src.size} elems) in a bf16 path",
                    hint="keep bulk tensors in bf16; upcast only reductions "
                         "(or suppress via the allowlist with a reason)"))

    _walk_eqns(inner, visit)
    return out


def audit_retrace(fn, calls: Sequence[tuple], entry: EntryPoint
                  ) -> List[Finding]:
    """TA-RETRACE: jit ``fn``, replay every bucket twice, compare the
    jit-cache entry count to the shared budget table."""
    import jax

    jf = jax.jit(fn)
    for args in list(calls) + list(calls):   # second pass must be free
        jax.block_until_ready(jax.tree_util.tree_leaves(jf(*args)))
    try:
        compiled = int(jf._cache_size())
    except Exception:      # jit internals moved; skip rather than lie
        return []
    budget = budgets.compile_budget(entry.name, **entry.budget_params)
    if compiled > budget:
        return [Finding(
            "TA-RETRACE", entry.path, 0,
            f"{compiled} compiled shapes exceed the budget of {budget}",
            hint="a traced-signature leak (python scalar / weak type?) "
                 "is recompiling per call; see budgets.COMPILE_BUDGETS")]
    return []


def audit_entry(entry: EntryPoint) -> List[Finding]:
    import jax

    fn, calls = entry.build()
    out: List[Finding] = []
    seen_shapes = set()
    for args in calls:
        shapes = tuple(getattr(a, "shape", None) for a in args)
        if shapes in seen_shapes:
            continue
        seen_shapes.add(shapes)
        out.extend(audit_jaxpr(jax.make_jaxpr(fn)(*args), entry.path))
    # one finding per (rule, message) — buckets repeat the same graph
    uniq: Dict[tuple, Finding] = {}
    for f in out:
        uniq.setdefault((f.rule, f.message), f)
    return list(uniq.values()) + audit_retrace(fn, calls, entry)


# ---------------------------------------------------------------------------
# registered entries (tinyllama smoke config — the tier-1 serving arch)
# ---------------------------------------------------------------------------

_SMOKE_ARCH = "tinyllama_1_1b"
_MAX_LEN = 32


def _smoke_model():
    import jax

    from repro import configs
    from repro.models import transformer

    cfg = configs.smoke(_SMOKE_ARCH)
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _build_prefill() -> Tuple[Callable, List[tuple]]:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer
    from repro.serving import engine

    cfg, params = _smoke_model()
    cache = transformer.init_cache(cfg, 2, _MAX_LEN)

    def fn(tokens, slots, lengths):
        return engine.prefill_into_slots(params, cache, tokens, slots,
                                         lengths, cfg)

    calls = []
    for S in engine.length_buckets(_MAX_LEN):
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab)
        calls.append((toks, jnp.asarray([0, 1], jnp.int32),
                      jnp.asarray([S - 1, S], jnp.int32)))
    return fn, calls


def _build_decode() -> Tuple[Callable, List[tuple]]:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer
    from repro.serving import engine

    cfg, params = _smoke_model()
    cache = transformer.init_cache(cfg, 2, _MAX_LEN)

    def fn(token, pos):
        return engine.serve_step(params, cache, token, pos, cfg)

    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, cfg.vocab)
    return fn, [(tok, jnp.asarray(3, jnp.int32))]


def _build_verify() -> Tuple[Callable, List[tuple]]:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer
    from repro.serving import engine

    cfg, params = _smoke_model()
    block = 8
    cache = transformer.init_paged_cache(cfg, 10, block)
    B, W = 2, 4
    base_key = jax.random.PRNGKey(0)

    def fn(tokens, pos_vec, tables, draft_lens, uids, counts):
        # sampled path (temperature > 0) so the folded-key machinery is in
        # the audited trace — greedy would dead-code-eliminate it
        return engine.verify_step(params, cache, tokens, pos_vec, tables,
                                  draft_lens, uids, counts, cfg,
                                  temperature=0.7, top_k=0,
                                  base_key=base_key)

    toks = jax.random.randint(jax.random.PRNGKey(3), (B, W), 0, cfg.vocab)
    calls = [(toks,
              jnp.asarray([8, 9], jnp.int32),
              jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
              jnp.asarray([2, 1], jnp.int32),
              jnp.asarray([7, 9], jnp.uint32),
              jnp.asarray([8, 9], jnp.uint32))]
    return fn, calls


def _build_mixed() -> Tuple[Callable, List[tuple]]:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer
    from repro.serving import engine

    cfg, params = _smoke_model()
    block = 8
    cache = transformer.init_paged_cache(cfg, 10, block)
    B, W = 2, 4
    base_key = jax.random.PRNGKey(0)

    def fn(tokens, pos_vec, tables, n_tokens, uids, counts):
        # the chunked-prefill mixed step (§16): a prefill-chunk slot and a
        # decode slot share one launch; sampled path so the folded-key
        # machinery is in the audited trace
        last, cache2 = engine.prefill_chunk_into_pages(
            params, cache, tokens, pos_vec, tables, n_tokens, cfg)
        keys = engine.fold_slot_keys(base_key, uids, counts)
        tok = engine.sample_per_slot(last, keys, temperature=0.7, top_k=0)
        return tok, cache2

    toks = jax.random.randint(jax.random.PRNGKey(4), (B, W), 0, cfg.vocab)
    calls = [(toks,
              jnp.asarray([0, 9], jnp.int32),
              jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
              jnp.asarray([4, 1], jnp.int32),
              jnp.asarray([7, 9], jnp.uint32),
              jnp.asarray([0, 8], jnp.uint32))]
    return fn, calls


def _build_spmm() -> Tuple[Callable, List[tuple]]:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import tiled_csl
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    dense = rng.standard_normal((256, 256)).astype(np.float32)
    dense[rng.random((256, 256)) < 0.8] = 0.0
    t = tiled_csl.encode(dense, 128, 128)

    def fn(b):
        return ops.spmm(t, b, backend="interpret")

    b = jnp.asarray(rng.standard_normal((256, 8)).astype(np.float32))
    return fn, [(b,)]


def default_entries() -> List[EntryPoint]:
    return [
        EntryPoint("engine_prefill_buckets", _build_prefill,
                   {"max_len": _MAX_LEN}),
        EntryPoint("engine_decode_step", _build_decode),
        EntryPoint("engine_verify_step", _build_verify),
        EntryPoint("engine_mixed_step", _build_mixed),
        EntryPoint("spmm_dispatch", _build_spmm),
    ]


def run_trace_audit(entries: Optional[Sequence[EntryPoint]] = None
                    ) -> List[Finding]:
    out: List[Finding] = []
    for e in entries if entries is not None else default_entries():
        out.extend(audit_entry(e))
    return out
