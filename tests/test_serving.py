"""Serving: generation, sampling, continuous batching scheduler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.analysis import budgets
from repro.core import pruning, tiled_csl
from repro.launch import serve
from repro.models import transformer
from repro.serving import batching, engine


def test_generate_greedy_deterministic():
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab)
    out1 = engine.generate(params, prompt, cfg, max_new_tokens=5, jit=False)
    out2 = engine.generate(params, prompt, cfg, max_new_tokens=5, jit=False)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 11)


def test_generate_matches_stepwise_full_forward():
    """Greedy generate == argmax over repeated full forwards (no cache)."""
    cfg = configs.smoke("qwen2_1_5b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab)
    out = engine.generate(params, prompt, cfg, max_new_tokens=4, jit=False)
    # reference: recompute from scratch each step
    cur = prompt
    for _ in range(4):
        logits, _, _ = transformer.forward(params, {"tokens": cur}, cfg,
                                           mode="train")
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        cur = jnp.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))


def test_sampling_modes():
    logits = jnp.asarray([[1.0, 5.0, 2.0, 0.1]])
    assert int(engine.sample(logits, jax.random.PRNGKey(0))[0]) == 1
    tok = engine.sample(logits, jax.random.PRNGKey(0), temperature=1.0,
                        top_k=2)
    assert int(tok[0]) in (1, 2)


def test_continuous_batching_matches_sequential():
    """The batcher must produce exactly what one-request-at-a-time greedy
    generation produces."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, rng.integers(3, 7)).astype(np.int64)
               for _ in range(5)]
    want = {}
    for uid, p in enumerate(prompts):
        out = engine.generate(params, jnp.asarray(p[None]), cfg,
                              max_new_tokens=4, jit=False)
        want[uid] = np.asarray(out)[0, len(p):].tolist()

    b = batching.ContinuousBatcher(params, cfg, n_slots=2, max_len=32)
    for uid, p in enumerate(prompts):
        b.submit(uid, p, max_new_tokens=4)
    got = b.run_to_completion()
    assert set(got) == set(want)
    for uid in want:
        assert got[uid] == want[uid], (uid, got[uid], want[uid])


def test_batcher_slot_reuse():
    cfg = configs.smoke("qwen2_1_5b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    b = batching.ContinuousBatcher(params, cfg, n_slots=1, max_len=24)
    rng = np.random.default_rng(1)
    for uid in range(3):
        b.submit(uid, rng.integers(0, cfg.vocab, 4).astype(np.int64), 3)
    out = b.run_to_completion()
    assert len(out) == 3
    assert all(len(v) == 3 for v in out.values())
    # the single slot was reused for every request, back to back
    assert b.metrics.admitted == 3 and b.metrics.completed == 3
    assert b.slots == [None]


def test_mixed_bucket_admission_matches_sequential():
    """Ragged prompts spanning several length buckets produce exactly the
    sequential greedy outputs (bucket padding must be numerically inert)."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    lengths = [3, 9, 14, 5, 12, 4]          # buckets 8 and 16 (max_len 32)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int64)
               for L in lengths]
    want = {}
    for uid, p in enumerate(prompts):
        out = engine.generate(params, jnp.asarray(p[None]), cfg,
                              max_new_tokens=4, jit=False)
        want[uid] = np.asarray(out)[0, len(p):].tolist()

    b = batching.ContinuousBatcher(params, cfg, n_slots=3, max_len=32)
    for uid, p in enumerate(prompts):
        b.submit(uid, p, max_new_tokens=4)
    got = b.run_to_completion()
    assert got == want
    assert set(b.metrics.bucket_admits) == {8, 16}


def test_bucketed_admission_compile_count():
    """N distinct prompt lengths compile at most ceil(log2(max_len)) prefill
    shapes — and once every bucket is warm, new lengths compile NOTHING
    (asserted via a jax.monitoring compile-event listener)."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    max_len = 32
    b = batching.ContinuousBatcher(params, cfg, n_slots=2, max_len=max_len)
    rng = np.random.default_rng(4)
    # phase 1: one request per bucket (8, 16, 32) warms every prefill shape
    for uid, L in enumerate((5, 12, 20)):
        b.submit(uid, rng.integers(0, cfg.vocab, L).astype(np.int64), 2)
    b.run_to_completion()
    bound = budgets.compile_budget("batcher_prefill", max_len=max_len)
    assert b.prefill_compiles <= bound, (b.prefill_compiles, bound)

    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        # phase 2: 12 new distinct lengths — zero fresh compiles
        for uid, L in enumerate(range(3, 15), start=100):
            b.submit(uid, rng.integers(0, cfg.vocab, L).astype(np.int64), 2)
        out = b.run_to_completion()
    finally:
        jax.monitoring.clear_event_listeners()
    assert len(out) == 12
    compile_events = [e for e in events if "compil" in e]
    assert not compile_events, compile_events
    assert b.prefill_compiles <= bound


def test_batcher_eos_termination():
    """Generation stops at the stop token (kept in the output)."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, 6).astype(np.int64)
    # learn what greedy decoding emits, then replay with eos = 3rd token
    probe = batching.ContinuousBatcher(params, cfg, n_slots=1, max_len=32)
    probe.submit(0, prompt, max_new_tokens=6)
    free_run = probe.run_to_completion()[0]
    eos = free_run[2]

    b = batching.ContinuousBatcher(params, cfg, n_slots=1, max_len=32,
                                   eos_id=eos)
    b.submit(0, prompt, max_new_tokens=6)
    out = b.run_to_completion()
    stop_at = free_run.index(eos)            # eos may repeat earlier too
    assert out[0] == free_run[:stop_at + 1]  # stops AT the stop token
    assert out[0][-1] == eos
    assert len(out[0]) < len(free_run)
    assert b.requests[0].finish_reason == "stop"
    assert b.metrics.eos_terminated == 1


def test_batcher_max_len_truncation():
    """A request whose budget exceeds the slot's cache region is truncated
    at max_len instead of scribbling out of bounds."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    b = batching.ContinuousBatcher(params, cfg, n_slots=1, max_len=16)
    b.submit(0, rng.integers(0, cfg.vocab, 6).astype(np.int64), 100)
    out = b.run_to_completion()
    # prefill gives 1 token at pos 6; decode fills positions 6..15
    assert len(out[0]) == 1 + (16 - 6)
    assert b.requests[0].finish_reason == "max_len"
    assert b.metrics.truncated == 1
    # over-long prompts are rejected up front
    with pytest.raises(ValueError):
        b.submit(1, rng.integers(0, cfg.vocab, 16).astype(np.int64), 1)


def test_batcher_metrics_accounting():
    """Counter invariants: every generated token is either the prefill's
    first token or one decode token; queue-wait and occupancy move."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, rng.integers(3, 12)).astype(np.int64)
               for _ in range(7)]
    b = batching.ContinuousBatcher(params, cfg, n_slots=2, max_len=32)
    for uid, p in enumerate(prompts):
        b.submit(uid, p, max_new_tokens=4)
    out = b.run_to_completion()
    m = b.metrics
    assert m.admitted == m.completed == len(prompts)
    assert sum(len(v) for v in out.values()) == m.admitted + m.decode_tokens
    assert m.prefill_tokens == sum(len(p) for p in prompts)
    assert m.padded_prefill_tokens >= m.prefill_tokens
    assert 0.0 < m.occupancy <= 1.0
    assert m.queue_wait_steps > 0        # 7 requests over 2 slots must wait
    assert m.prefill_calls >= 1 and m.decode_time_s >= 0.0
    d = m.as_dict()
    assert d["occupancy"] == m.occupancy
    assert d["completed"] == len(prompts)


def test_metrics_padding_overhead_zero_before_prefill():
    """Regression: a fresh SchedulerMetrics used to report 100% prefill
    padding overhead (1.0) because of the max(denominator, 1) guard."""
    m = batching.SchedulerMetrics()
    assert m.prefill_padding_overhead == 0.0
    assert m.as_dict()["prefill_padding_overhead"] == 0.0
    m.prefill_tokens, m.padded_prefill_tokens = 6, 8
    assert m.prefill_padding_overhead == pytest.approx(0.25)


def test_server_gauges_the_lscd_slab_share_of_its_params():
    """``lscd_slab_share`` is set once from the served tree: 100 x the
    tiles' summed slab counts over tiles x slots / 8, every Tiled-CSL leaf
    (grouped and scan-stacked ones included); 0 with dense weights."""
    cfg = configs.smoke("tinyllama_1_1b")
    dense = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params = pruning.group_projections(pruning.sparsify_params(
        dense, 0.8, should_sparsify=serve.should_sparsify))
    csl = [x for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))
        if isinstance(x, tiled_csl.TiledCSL)]
    assert any(x.words.ndim == 6 for x in csl)       # [L, G, ...] leaves
    run = sum(int(np.asarray(x.slabs).sum()) for x in csl)
    full = sum(int(np.prod(x.slabs.shape)) * x.slots // 8 for x in csl)
    b = batching.ContinuousBatcher(params, cfg, n_slots=2, max_len=32)
    assert b.metrics.lscd_slab_share == pytest.approx(100.0 * run / full)
    assert 0.0 < b.metrics.lscd_slab_share < 100.0
    assert batching.ContinuousBatcher(
        dense, cfg, n_slots=2, max_len=32).metrics.lscd_slab_share == 0.0
