"""End-to-end behaviour tests: the paper's system claims at container scale.

1. LSCD serving equivalence: a model served with Tiled-CSL weights produces
   the same logits as the same pruned model served dense (the paper's
   correctness contract for Flash-LLM inside FasterTransformer).
2. Memory claim: the Tiled-CSL params are materially smaller than dense
   at 80% sparsity.
3. Throughput claim structure: LSCD roofline step time beats dense for
   skinny N at >=70% sparsity and loses for huge N (paper Fig.12).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import pruning, roofline, tiled_csl
from repro.models import transformer
from repro.serving import engine
from repro.training import optimizer as opt_mod


@pytest.fixture(scope="module")
def pruned_model():
    cfg = configs.smoke("tinyllama_1_1b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    # prune the MLP + attention mats to 80%, keep everything else dense
    masks = jax.tree_util.tree_map_with_path(
        lambda p, x: (pruning.unstructured_mask(jnp.abs(x), 0.8)
                      if x.ndim == 3 and any(
                          k in jax.tree_util.keystr(p) for k in
                          ("'gate'", "'up'", "'down'", "'wq'", "'wk'",
                           "'wv'", "'wo'"))
                      else None),
        params)
    pruned = opt_mod.apply_masks(params, masks)
    return cfg, pruned


def _sparsify(pruned, names):
    return pruning.sparsify_params(
        pruned, 0.0,  # weights already pruned; encode as-is
        should_sparsify=lambda n: any(k in n for k in names))


ALL_MATS = ("'gate'", "'up'", "'down'", "'wq'", "'wk'", "'wv'", "'wo'")


def test_lscd_serving_matches_dense_pruned(pruned_model):
    cfg, pruned = pruned_model
    sparse_params = _sparsify(pruned, ALL_MATS)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    logits_dense, _, _ = transformer.forward(
        pruned, {"tokens": tokens}, cfg, mode="train")
    logits_sparse, _, _ = transformer.forward(
        sparse_params, {"tokens": tokens}, cfg, mode="train")
    # bf16 encoding rounding is the only allowed difference
    np.testing.assert_allclose(np.asarray(logits_dense, np.float32),
                               np.asarray(logits_sparse, np.float32),
                               rtol=0.05, atol=0.05)


def test_sparse_memory_is_smaller(pruned_model):
    cfg, pruned = pruned_model
    sparse_params = _sparsify(pruned, ALL_MATS)
    csl = [l for l in jax.tree.leaves(
        sparse_params, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))
        if isinstance(l, tiled_csl.TiledCSL)]
    assert csl, "no TiledCSL leaves produced"
    total_sparse = sum(t.nbytes_sparse for t in csl)
    total_dense = sum(t.nbytes_dense for t in csl)
    # smoke-scale weights are single-tile; padding dilutes the win
    assert total_sparse < 0.75 * total_dense

    # at representative size: the column-slotted words pad every tile
    # column to the fullest one (DESIGN.md §2), so the stream is 1.33x
    # smaller than dense bf16 at 80% sparsity and 2x at 90%
    for sparsity, bound in ((0.8, 0.76), (0.9, 0.51)):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((1024, 1024), dtype=np.float32)
        w[rng.random(w.shape) < sparsity] = 0.0
        t = tiled_csl.encode(w)
        assert t.nbytes_sparse < bound * t.nbytes_dense


@pytest.mark.parametrize("sparsity,slots,ratio", [(0.8, 48, 0.7501220703125),
                                                   (0.9, 32, 0.5001220703125)])
def test_column_slot_padding_cost_pinned(sparsity, slots, ratio):
    """Today's cost of padding every tile column to the fullest one, pinned
    so a layout that pads less must move it on purpose. The paper's
    flat per-tile lists stream ~0.42x of dense bf16 at 80% sparsity; the
    column-slotted words stream 0.75x (ROADMAP design debt)."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((1024, 1024), dtype=np.float32)
    w[rng.random(w.shape) < sparsity] = 0.0
    t = tiled_csl.encode(w)
    assert t.slots == slots
    assert t.nbytes_sparse / t.nbytes_dense == pytest.approx(ratio)


def test_generation_runs_with_sparse_weights(pruned_model):
    cfg, pruned = pruned_model
    sparse_params = _sparsify(pruned, ("'gate'", "'up'", "'down'"))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab)
    out = engine.generate(pruned, prompt, cfg, max_new_tokens=4, jit=False)
    out_s = engine.generate(sparse_params, prompt, cfg, max_new_tokens=4,
                            jit=False)
    assert out.shape == (2, 12)
    # greedy decode over the same (bf16-rounded) weights: tokens match
    assert (np.asarray(out) == np.asarray(out_s)).mean() > 0.9


def test_fig12_crossover_structure():
    """LSCD wins at skinny N / >=70% sparsity, loses by huge N (Fig.12)."""
    m = k = 9216
    for n in (8, 16, 32, 64):
        d = roofline.dense_gemm_terms(m, k, n)
        s = roofline.lscd_kernel_terms(m, k, n, 0.8, pad_overhead=0.04)
        assert s.step_time_s < d.step_time_s, n
    # huge N: compute-bound, LSCD's extra bytes no longer help
    d = roofline.dense_gemm_terms(m, k, 4096)
    s = roofline.lscd_kernel_terms(m, k, 4096, 0.8, pad_overhead=0.04)
    assert s.step_time_s >= d.step_time_s * 0.95


def test_ci_formulas_match_paper():
    """Eq.1 / Eq.2 sanity: CI bounded by N; LSCD multiplies CI ~1/(1-beta)."""
    assert roofline.dense_gemm_ci(48 * 1024, 16) < 16.0
    ci_d = roofline.dense_gemm_ci(48 * 1024, 16)
    ci_s = roofline.lscd_ci(48 * 1024, 16, 0.8)
    assert 4.0 < ci_s / ci_d < 5.01   # ~1/(1-0.8) for M >> N


def test_compile_cache_dir_placeable_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the fixed .jax_cache/ of this checkout."""
    import os

    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert compile_cache.enable() == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
