"""The plain reference against the program at a size the CPU holds, and
the float8 control that every cell's correctness limit must catch."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import harness, limits, reference, weights


def test_a_leaf_has_the_same_bits_alone_or_in_one_jitted_tree():
    alone = weights.leaf(7, "mlp.up.w", 1, (256, 128))
    tree = jax.jit(lambda: jnp.stack([
        weights.leaf(7, "mlp.up.w", layer, (256, 128))
        for layer in range(3)]))()
    assert alone.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(tree[1], np.float32),
                                  np.asarray(alone, np.float32))
    assert not np.array_equal(np.asarray(tree[0], np.float32),
                              np.asarray(alone, np.float32))
    std = float(jnp.std(alone.astype(jnp.float32)))
    assert std == pytest.approx(128 ** -0.5, rel=0.05)


@pytest.mark.parametrize("sparsity", [0.5, 0.8])
def test_reference_pruning_keeps_the_programs_weights(sparsity):
    from repro.core import pruning
    w = weights.leaf(3, "attn.wq.w", 0, (384, 256))
    ours = reference.prune(w, sparsity)
    theirs = pruning.prune(jnp.asarray(w, jnp.float32), sparsity)
    np.testing.assert_array_equal(np.asarray(ours, np.float32) != 0,
                                  np.asarray(theirs) != 0)
    kept = float(jnp.mean(ours != 0))
    assert kept == pytest.approx(1.0 - sparsity, abs=0.01)


def _bits(x):
    return np.asarray(x).view(np.uint16)


def test_a_list_of_layers_draws_the_scanned_stacks_bits():
    scanned = chipbench_tiny.cell("gelu")
    listed = chipbench_tiny.cell("gelu_list")
    stack = harness.dense_params(
        scanned, harness._param_shapes(harness.model_config(scanned)))
    shapes = harness._param_shapes(harness.model_config(listed))
    assert isinstance(shapes["layers"], list) and len(shapes["layers"]) == 2
    harness.check_layout(listed, shapes)
    tree = harness.dense_params(listed, shapes)
    for i, layer in enumerate(tree["layers"]):
        jax.tree.map(lambda s, x: np.testing.assert_array_equal(
            _bits(s[i]), _bits(x)), stack["layers"], layer)
    jax.tree.map(lambda s, x: np.testing.assert_array_equal(
        _bits(s), _bits(x)), {k: v for k, v in stack.items() if k != "layers"},
        {k: v for k, v in tree.items() if k != "layers"})
    np.testing.assert_array_equal(
        _bits(tree["layers"][1]["mlp"]["up"]["w"]),
        _bits(weights.leaf(7, "mlp.up.w", 1, (256, 128))))


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    return tmp_path


@pytest.mark.parametrize("kind", ["gelu", "swiglu", "gelu_list"])
def test_served_tokens_pass_and_the_float8_control_fails(kind, tmp_cache):
    cell = chipbench_tiny.cell(kind)
    (row,) = limits.readings(cell, [11], {11}, 3.0)
    assert row["unanswered"] == 0 and row["tokens"] > 20
    assert row["gap"] <= chipbench_tiny.GAP_LIMIT
    assert row["control_gap"] > chipbench_tiny.GAP_LIMIT
    assert row["control_gap"] >= 3 * max(row["gap"], 1e-6)
