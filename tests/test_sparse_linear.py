"""sparse_linear dispatch: out-dim contract, grouped/fused layer routing.

The acceptance contract for the grouped fused-epilogue pipeline:
``swiglu_mlp``/``gelu_mlp`` with TiledCSL weights route through ONE grouped
fused kernel call and match the unfused composition within 1e-5 rtol in
interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import pruning, sparse_linear, tiled_csl
from repro.kernels import ops
from repro.models import attention, layers


def _enc(rng, m, k, s=0.7):
    a = rng.standard_normal((m, k), dtype=np.float32)
    a[rng.random((m, k)) < s] = 0.0
    return a, tiled_csl.encode(a)


# ---------------------------------------------------------------------------
# linear(): declared_out contract
# ---------------------------------------------------------------------------

def test_declared_out_slices_without_bias():
    """Regression: with a TiledCSL weight and b=None, linear() used to
    return the tile-padded out dim while the bias path sliced."""
    rng = np.random.default_rng(0)
    a = np.zeros((128, 128), np.float32)          # logical out dim 100
    a[:100] = rng.standard_normal((100, 128), dtype=np.float32)
    t = tiled_csl.encode(a)
    x = jnp.asarray(rng.standard_normal((2, 3, 128), dtype=np.float32))
    y = sparse_linear.linear(t, x, declared_out=100, backend="interpret")
    assert y.shape == (2, 3, 100)
    b = jnp.asarray(rng.standard_normal(100), jnp.float32)
    yb = sparse_linear.linear(t, x, b, declared_out=100, backend="interpret")
    assert yb.shape == (2, 3, 100)                # both paths slice
    np.testing.assert_allclose(np.asarray(yb), np.asarray(y + b),
                               rtol=1e-5, atol=1e-5)
    # declared_out defaults to the bias length when a bias is present
    assert sparse_linear.linear(t, x, b, backend="interpret").shape == (2, 3, 100)
    # linear_logical_out delegates to the same contract
    np.testing.assert_allclose(
        np.asarray(sparse_linear.linear_logical_out(t, 100, x,
                                                    backend="interpret")),
        np.asarray(y), atol=0.0)


def test_dense_path_unchanged_and_sliceable():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((64, 32), dtype=np.float32))
    x = jnp.asarray(rng.standard_normal((5, 32), dtype=np.float32))
    y = sparse_linear.linear(w, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w.T),
                               rtol=1e-6, atol=1e-6)
    assert sparse_linear.linear(w, x, declared_out=60).shape == (5, 60)


def test_linear_rejects_grouped_weight():
    rng = np.random.default_rng(2)
    a, _ = _enc(rng, 128, 128)
    tg = tiled_csl.encode_group([a, a])
    with pytest.raises(ValueError, match="grouped"):
        sparse_linear.linear(tg, jnp.ones((2, 128)), backend="interpret")


# ---------------------------------------------------------------------------
# linear_grouped
# ---------------------------------------------------------------------------

def test_linear_grouped_matches_per_weight_linear():
    rng = np.random.default_rng(3)
    (a0, t0), (a1, t1), (a2, t2) = (_enc(rng, 128, 128) for _ in range(3))
    x = jnp.asarray(rng.standard_normal((2, 4, 128), dtype=np.float32))
    outs = sparse_linear.linear_grouped((t0, t1, t2), x,
                                        declared_outs=(128, 100, 128),
                                        backend="interpret")
    assert [o.shape[-1] for o in outs] == [128, 100, 128]
    for t, do, got in zip((t0, t1, t2), (128, 100, 128), outs):
        want = sparse_linear.linear(t, x, declared_out=do,
                                    backend="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_linear_grouped_dense_fallback_matches_baseline():
    """Dense weights keep the exact baseline XLA math (no f32 re-rounding)."""
    rng = np.random.default_rng(4)
    w0 = jnp.asarray(rng.standard_normal((64, 32), dtype=np.float32))
    w1 = jnp.asarray(rng.standard_normal((64, 32), dtype=np.float32))
    x = jnp.asarray(rng.standard_normal((3, 32), dtype=np.float32))
    h = sparse_linear.linear_grouped((w0, w1), x, declared_outs=(64, 64),
                                     epilogue="silu_mul")
    want = jax.nn.silu(x @ w0.T) * (x @ w1.T)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_groupable_predicate():
    rng = np.random.default_rng(5)
    _, t0 = _enc(rng, 128, 128)
    _, t1 = _enc(rng, 128, 128)
    _, t_other = _enc(rng, 256, 128)
    dense = jnp.ones((128, 128))
    assert sparse_linear.groupable((t0, t1))
    assert not sparse_linear.groupable((t0, t_other))   # shape mismatch
    assert not sparse_linear.groupable((t0, dense))     # mixed
    assert not sparse_linear.groupable(())


# ---------------------------------------------------------------------------
# fused MLP / QKV acceptance: one grouped call, parity with unfused
# ---------------------------------------------------------------------------

def _call_counter(monkeypatch):
    calls = {"grouped": 0, "single": 0}
    orig_g, orig_s = ops.spmm_grouped, ops.spmm

    def counting_grouped(*a, **k):
        calls["grouped"] += 1
        calls["grouped_epilogue"] = k.get("epilogue", "none")
        return orig_g(*a, **k)

    def counting_single(*a, **k):
        calls["single"] += 1
        return orig_s(*a, **k)

    monkeypatch.setattr(ops, "spmm_grouped", counting_grouped)
    monkeypatch.setattr(ops, "spmm", counting_single)
    return calls


def test_swiglu_mlp_routes_one_grouped_fused_call(monkeypatch):
    rng = np.random.default_rng(6)
    d_model, d_ff = 128, 256
    params = {"gate": {"w": _enc(rng, d_ff, d_model)[1]},
              "up": {"w": _enc(rng, d_ff, d_model)[1]},
              "down": {"w": _enc(rng, d_model, d_ff)[1]}}
    x = jnp.asarray(rng.standard_normal((2, 4, d_model), dtype=np.float32))

    calls = _call_counter(monkeypatch)
    y_fused = layers.swiglu_mlp(params, x, d_ff=d_ff, d_model=d_model,
                                backend="interpret")
    # gate+up ride ONE grouped silu_mul launch; down is the only single call
    assert calls == {"grouped": 1, "single": 1,
                     "grouped_epilogue": "silu_mul"}

    g = sparse_linear.linear(params["gate"]["w"], x, declared_out=d_ff,
                             backend="interpret")
    u = sparse_linear.linear(params["up"]["w"], x, declared_out=d_ff,
                             backend="interpret")
    y_unfused = sparse_linear.linear(params["down"]["w"],
                                     jax.nn.silu(g) * u,
                                     declared_out=d_model,
                                     backend="interpret")
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_unfused),
                               rtol=1e-5, atol=1e-5)


def test_gelu_mlp_fuses_bias_and_activation(monkeypatch):
    rng = np.random.default_rng(7)
    d_model, d_ff = 128, 256
    params = {"up": {"w": _enc(rng, d_ff, d_model)[1],
                     "b": jnp.asarray(rng.standard_normal(d_ff), jnp.float32)},
              "down": {"w": _enc(rng, d_model, d_ff)[1],
                       "b": jnp.asarray(rng.standard_normal(d_model),
                                        jnp.float32)}}
    x = jnp.asarray(rng.standard_normal((2, 4, d_model), dtype=np.float32))

    calls = _call_counter(monkeypatch)
    y = layers.gelu_mlp(params, x, d_ff=d_ff, d_model=d_model,
                        backend="interpret")
    assert calls["single"] == 2 and calls["grouped"] == 0

    h = jax.nn.gelu(
        sparse_linear.linear(params["up"]["w"], x, declared_out=d_ff,
                             backend="interpret") + params["up"]["b"])
    want = sparse_linear.linear(params["down"]["w"], h,
                                declared_out=d_model,
                                backend="interpret") + params["down"]["b"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_qkv_projection_groups_tiled_csl(monkeypatch):
    """Smoke-scale GQA: tile padding makes wq/wk/wv shapes coincide, but wq
    carries ~8x the non-zeros of the mostly-padding wk/wv — the max_nnz
    balance cap must refuse the G=3 group (it would bloat the shared
    stream) and group the balanced k/v pair instead."""
    cfg = configs.smoke("tinyllama_1_1b")
    params = attention.init_attention(jax.random.PRNGKey(0), cfg)
    sp = pruning.sparsify_params(params, 0.7,
                                 should_sparsify=lambda n: "'w'" in n)
    assert not sparse_linear.groupable(
        tuple(sp[n]["w"] for n in ("wq", "wk", "wv")))
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((2, 4, cfg.d_model),
                                        dtype=np.float32))
    calls = _call_counter(monkeypatch)
    q, k, v = attention._project_qkv(sp, x, cfg, "interpret")
    assert calls["grouped"] == 1 and calls["single"] == 1   # q alone, k+v
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    assert q.shape == (2, 4, h, hd) and k.shape == (2, 4, kv, hd)

    # parity vs per-weight projections
    qs = sparse_linear.linear(sp["wq"]["w"], x, declared_out=h * hd,
                              backend="interpret")
    np.testing.assert_allclose(np.asarray(q.reshape(2, 4, -1)),
                               np.asarray(qs), rtol=1e-5, atol=1e-5)


def test_qkv_projection_groups_balanced_mha(monkeypatch):
    """True MHA (equal-occupancy wq/wk/wv) passes the balance cap → one
    G=3 launch, parity vs per-weight projections."""
    import dataclasses
    cfg = dataclasses.replace(configs.smoke("tinyllama_1_1b"),
                              n_kv=configs.smoke("tinyllama_1_1b").n_heads)
    params = attention.init_attention(jax.random.PRNGKey(0), cfg)
    sp = pruning.sparsify_params(params, 0.7,
                                 should_sparsify=lambda n: "'w'" in n)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 4, cfg.d_model),
                                        dtype=np.float32))
    calls = _call_counter(monkeypatch)
    q, k, v = attention._project_qkv(sp, x, cfg, "interpret")
    assert calls["grouped"] == 1 and calls["single"] == 0
    for name, got in (("wq", q), ("wk", k), ("wv", v)):
        want = sparse_linear.linear(sp[name]["w"], x,
                                    declared_out=cfg.n_heads * cfg.head_dim,
                                    backend="interpret")
        np.testing.assert_allclose(np.asarray(got.reshape(2, 4, -1)),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# reformat-time pre-grouping (pruning.group_projections)
# ---------------------------------------------------------------------------

def test_group_projections_rewrites_and_matches(monkeypatch):
    """group_projections pre-groups gate+up once at reformat time; the MLP
    consumes the grouped key (no call-time group_stack) and matches the
    per-weight composition."""
    rng = np.random.default_rng(10)
    d_model, d_ff = 128, 256
    params = {"mlp": {"gate": {"w": _enc(rng, d_ff, d_model)[1]},
                      "up": {"w": _enc(rng, d_ff, d_model)[1]},
                      "down": {"w": _enc(rng, d_model, d_ff)[1]}}}
    gp = pruning.group_projections(params)
    assert "gate_up" in gp["mlp"] and "gate" not in gp["mlp"]
    assert gp["mlp"]["gate_up"]["w"].group == 2

    x = jnp.asarray(rng.standard_normal((2, 4, d_model), dtype=np.float32))
    calls = _call_counter(monkeypatch)
    y = layers.swiglu_mlp(gp["mlp"], x, d_ff=d_ff, d_model=d_model,
                          backend="interpret")
    assert calls == {"grouped": 1, "single": 1,
                     "grouped_epilogue": "silu_mul"}
    y_ref = layers.swiglu_mlp(params["mlp"], x, d_ff=d_ff, d_model=d_model,
                              backend="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_group_projections_scan_stacked_forward_parity():
    """Scan-stacked trees group along axis 1 (lax.scan slices the layer
    axis back off) — whole-model logits match the ungrouped sparse path."""
    from repro.models import transformer
    cfg = configs.smoke("tinyllama_1_1b")
    assert cfg.scan_layers
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    sp = pruning.sparsify_params(
        params, 0.8,
        should_sparsify=lambda n: any(
            k in n for k in ("'gate'", "'up'", "'down'"))
        and n.endswith("['w']"))
    gp = pruning.group_projections(sp)
    leaves = jax.tree_util.tree_flatten_with_path(
        gp, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))[0]
    grouped = [l for p, l in leaves
               if "gate_up" in jax.tree_util.keystr(p)]
    assert len(grouped) == 1 and grouped[0].words.ndim == 6
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    lg, _, _ = transformer.forward(gp, {"tokens": tokens}, cfg, mode="train")
    ls, _, _ = transformer.forward(sp, {"tokens": tokens}, cfg, mode="train")
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(ls, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_group_projections_skips_unbalanced_and_dense():
    rng = np.random.default_rng(11)
    # dense weights: untouched
    dense = {"gate": {"w": jnp.ones((128, 128))},
             "up": {"w": jnp.ones((128, 128))}}
    assert "gate_up" not in pruning.group_projections(dense)
    # wildly uneven occupancy (one member mostly padding): skipped
    heavy = np.zeros((128, 128), np.float32)
    heavy[:, :] = rng.standard_normal((128, 128))
    light = np.zeros((128, 128), np.float32)
    light[:4] = rng.standard_normal((4, 128))
    uneven = {"gate": {"w": tiled_csl.encode(heavy)},
              "up": {"w": tiled_csl.encode(light)}}
    assert "gate_up" not in pruning.group_projections(uneven)


def test_epilogue_validated_on_dense_paths():
    """The op-boundary validation must hold for DENSE weights too: unknown
    names raise ValueError (not a registry KeyError) and a binary epilogue
    with the wrong group arity never silently drops a projection."""
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.standard_normal((16, 16), dtype=np.float32))
    x = jnp.asarray(rng.standard_normal((2, 16), dtype=np.float32))
    with pytest.raises(ValueError, match="unknown epilogue"):
        sparse_linear.linear(w, x, epilogue="gelu_typo")
    with pytest.raises(ValueError, match="binary epilogue"):
        sparse_linear.linear(w, x, epilogue="silu_mul")
    with pytest.raises(ValueError, match="binary epilogue"):
        sparse_linear.linear_grouped((w, w, w), x, declared_outs=(16, 16, 16),
                                     epilogue="silu_mul")
    with pytest.raises(ValueError, match="unknown epilogue"):
        sparse_linear.linear_grouped((w, w), x, declared_outs=(16, 16),
                                     epilogue="nope")
