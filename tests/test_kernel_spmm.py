"""Pallas LSCD SpMM kernel: interpret-mode sweeps vs the pure-jnp oracle.

Per assignment: sweep shapes/dtypes/sparsities/tile geometries and
assert_allclose against ref.py. Plus vjp correctness of the public op.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiled_csl
from repro.kernels import ops, ref


def _make(rng, m, k, sparsity, m_tb=128, k_tb=128):
    a = rng.standard_normal((m, k), dtype=np.float32)
    a[rng.random((m, k)) < sparsity] = 0.0
    return a, tiled_csl.encode(a, m_tb=m_tb, k_tb=k_tb)


# ---------------------------------------------------------------------------
# grid sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (128, 128, 8),       # single tile, skinny
    (256, 384, 16),      # multi-tile, skinny (paper's regime)
    (512, 256, 64),      # batch 64 (paper's largest N_TB)
    (128, 512, 128),     # wide-N
    (384, 128, 7),       # ragged N -> padding path
])
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.8, 0.95])
def test_kernel_matches_ref(m, k, n, sparsity):
    rng = np.random.default_rng(hash((m, k, n, int(sparsity * 100))) % 2 ** 31)
    a, t = _make(rng, m, k, sparsity)
    b = jnp.asarray(rng.standard_normal((k, n), dtype=np.float32))
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    a, t = _make(rng, 256, 256, 0.8)
    b = jnp.asarray(rng.standard_normal((256, 16), dtype=np.float32)).astype(dtype)
    got = ops.spmm(t, b, backend="interpret", out_dtype=dtype)
    want = ref.spmm_ref(t, b, out_dtype=dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("m_tb,k_tb", [(128, 128), (64, 128), (128, 64),
                                       (64, 64)])
def test_kernel_tile_geometries(m_tb, k_tb):
    rng = np.random.default_rng(7)
    a, t = _make(rng, 256, 256, 0.7, m_tb=m_tb, k_tb=k_tb)
    b = jnp.asarray(rng.standard_normal((256, 8), dtype=np.float32))
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_kernel_vs_dense_oracle():
    """Against the ORIGINAL dense matrix: only bf16 value rounding may
    differ. Output scale is ~sqrt(K*density) ~ 7, so the rounding-error
    budget is absolute (per-element relative error explodes on
    near-cancelling sums)."""
    rng = np.random.default_rng(3)
    a, t = _make(rng, 256, 256, 0.8)
    b = jnp.asarray(rng.standard_normal((256, 8), dtype=np.float32))
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32)
    want = ref.spmm_dense_oracle(jnp.asarray(a), b)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.0, atol=0.01 * scale)


@pytest.mark.parametrize("kt,split_k", [
    (2, None),   # the schedule's pick
    (6, 1),      # one 6-tile step per M row: zero tiles inside the block
    (6, 2),      # two 3-tile slices
])
def test_empty_tiles_fast_path(kt, split_k):
    """All-zero tiles (0 slabs) exercise the pl.when skip branch, also when
    they sit between non-zero tiles of one multi-tile grid step; an M row
    of zero tiles comes out as exact zeros."""
    rng = np.random.default_rng(0)
    a = np.zeros((256, kt * 128), np.float32)
    a[:128, :128] = rng.standard_normal((128, 128))
    if kt > 3:
        a[:128, 384:512] = rng.standard_normal((128, 128))
    t = tiled_csl.encode(a)
    assert int(np.asarray(t.nnz)[1, 1]) == 0
    assert int(np.asarray(t.nnz)[0, 1]) == 0
    assert ((np.asarray(t.slabs) == 0) == (np.asarray(t.nnz) == 0)).all()
    b = jnp.ones((kt * 128, 8), jnp.float32)
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32,
                   split_k=split_k)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got)[128:], 0.0)


def test_vjp_through_spmm_diff():
    """Custom VJP == autodiff of the reference path (exact, no numeric
    differentiation — f32 central differences on a sum-of-squares loss
    cancel catastrophically)."""
    rng = np.random.default_rng(5)
    a, t = _make(rng, 128, 128, 0.7)
    b = jnp.asarray(rng.standard_normal((128, 4), dtype=np.float32))

    def f_custom(b_):
        return jnp.sum(ops.spmm_diff(t, b_) ** 2)

    def f_ref(b_):
        return jnp.sum(ref.spmm_ref(t, b_, out_dtype=jnp.float32) ** 2)

    g_custom = jax.grad(f_custom)(b)
    g_ref = jax.grad(f_ref)(b)
    np.testing.assert_allclose(np.asarray(g_custom), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# property sweep (deterministic; formerly hypothesis-driven)
# ---------------------------------------------------------------------------

# Same space the hypothesis sweep drew from — mt x kt x n x sparsity with a
# seeded RNG per case — pinned to a fixed 12-case grid so the tier-1 suite
# needs no optional deps (see requirements-dev.txt for the extras). The
# rows with a split_k pin the K tiles per grid step d (the largest divisor
# of Kt and of the slice's tile count, at most 16): d = 1 at Kt = 17, a
# proper divisor (9) at Kt = 18, Kt itself at Kt = 4, and ragged split-K
# slices (Kt % S != 0) at Kt = 16, S = 3 (d = 2; the last slice holds one
# block past the end of K) and Kt = 12, S = 5 (d = 3; a whole slice past it).
@pytest.mark.parametrize("mt,kt,n,sparsity,seed,split_k", [
    (1, 1, 1, 0.0, 101, None),
    (1, 1, 8, 0.37, 202, None),
    (1, 2, 24, 0.5, 303, None),
    (1, 3, 64, 0.62, 404, None),
    (2, 1, 1, 0.75, 505, None),
    (2, 1, 64, 0.8, 606, None),
    (2, 2, 8, 0.9, 707, None),
    (2, 3, 24, 0.95, 808, None),
    (1, 2, 1, 0.99, 909, None),
    (2, 3, 64, 0.99, 1010, None),
    (1, 3, 8, 0.13, 1111, None),
    (2, 2, 24, 0.88, 1212, None),
    (1, 17, 8, 0.8, 1313, 1),
    (1, 18, 8, 0.8, 1414, 1),
    (2, 4, 8, 0.8, 1515, 1),
    (1, 16, 8, 0.8, 1616, 3),
    (1, 12, 8, 0.8, 1717, 5),
])
def test_kernel_property(mt, kt, n, sparsity, seed, split_k):
    rng = np.random.default_rng(seed)
    a, t = _make(rng, mt * 128, kt * 128, sparsity)
    b = jnp.asarray(rng.standard_normal((kt * 128, n), dtype=np.float32))
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32,
                   split_k=split_k)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_fused_epilogue_variants():
    """Beyond-paper: bias + activation fused into the flush stage."""
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(11)
    a, t = _make(rng, 256, 256, 0.8)
    b = jnp.asarray(rng.standard_normal((256, 16), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(256), jnp.float32)
    base = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    for epi, fn in [("silu", jax.nn.silu), ("gelu", jax.nn.gelu),
                    ("relu", lambda x: jnp.maximum(x, 0.0))]:
        got = spmm_mod.lscd_spmm(t, b, n_tb=16, interpret=True,
                                 epilogue=epi, bias=bias)
        want = fn(base + bias[:, None])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
    # epilogue without bias
    got = spmm_mod.lscd_spmm(t, b, n_tb=16, interpret=True, epilogue="relu")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.maximum(base, 0.0)),
                               rtol=1e-5, atol=1e-4)


def test_dense_gemm_baseline_kernel():
    """The cuBLAS-analogue Pallas GEMM (paper's dense baseline) vs jnp."""
    from repro.kernels import gemm
    rng = np.random.default_rng(21)
    a = jnp.asarray(rng.standard_normal((256, 384), dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((384, 128), dtype=np.float32))
    got = gemm.dense_gemm(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                               rtol=1e-5, atol=1e-4)


def test_spmm_equals_dense_gemm_on_same_matrix():
    """LSCD SpMM and the dense baseline agree on the same pruned matrix —
    the kernel-level apples-to-apples the paper's Fig.9 relies on."""
    from repro.kernels import gemm
    rng = np.random.default_rng(22)
    a, t = _make(rng, 256, 256, 0.8)
    # dense path sees the bf16-rounded values the encoding stores
    a_rounded = tiled_csl.decode(t)
    b = jnp.asarray(rng.standard_normal((256, 128), dtype=np.float32))
    dense = gemm.dense_gemm(jnp.asarray(a_rounded), b, interpret=True)
    sparse = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-4)


def test_moe_experts_with_tiled_csl_weights():
    """Stacked (per-expert) Tiled-CSL weights through the MoE block."""
    from repro import configs
    from repro.core import pruning
    from repro.models import moe, transformer
    cfg = configs.smoke("qwen3_moe_30b_a3b")
    params = transformer.init_model(jax.random.PRNGKey(0), cfg)
    moe_p = params["layers"]["moe"]
    # take layer 0's expert stacks [E, f, d] and sparsify per expert
    one_layer = {k: (v[0] if hasattr(v, "ndim") and v.ndim >= 3 else v)
                 for k, v in moe_p.items() if k in ("gate", "up", "down")}
    one_layer["router"] = {"w": moe_p["router"]["w"][0]}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          jnp.float32)
    y_dense, _ = moe.moe_block(one_layer, x, cfg)
    sparse = dict(one_layer)
    for k in ("gate", "up", "down"):
        sparse[k] = pruning.sparsify_params(
            {"w": one_layer[k]}, 0.5,
            should_sparsify=lambda n: True)["w"]
    y_sparse, _ = moe.moe_block(sparse, x, cfg)
    # 50% pruning changes values; just verify shape/finiteness + that the
    # sparse path runs the vmapped CSL decode end to end
    assert y_sparse.shape == y_dense.shape
    assert bool(jnp.isfinite(y_sparse).all())


# ---------------------------------------------------------------------------
# grouped SpMM + fused epilogues (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _make_group(rng, g, m, k, sparsities):
    mats = []
    for s in sparsities[:g]:
        a = rng.standard_normal((m, k), dtype=np.float32)
        a[rng.random((m, k)) < s] = 0.0
        mats.append(a)
    return mats, tiled_csl.encode_group(mats)


@pytest.mark.parametrize("m,k,n,split_k", [
    (128, 128, 8, None),       # single tile, skinny
    (256, 384, 16, None),      # multi-tile, skinny (paper's regime)
    (384, 128, 7, None),       # ragged N -> padding path
    (128, 2304, 8, 1),         # Kt = 18: two 9-tile grid steps
])
@pytest.mark.parametrize("g", [1, 2, 3])
@pytest.mark.parametrize("epilogue", ["none", "relu"])
def test_grouped_kernel_matches_ref(m, k, n, split_k, g, epilogue):
    rng = np.random.default_rng(hash((m, k, n, g)) % 2 ** 31)
    _, tg = _make_group(rng, g, m, k, (0.5, 0.8, 0.95))
    b = jnp.asarray(rng.standard_normal((k, n), dtype=np.float32))
    got = ops.spmm_grouped(tg, b, backend="interpret", out_dtype=jnp.float32,
                           epilogue=epilogue, split_k=split_k)
    want = ref.spmm_grouped_ref(tg, b, out_dtype=jnp.float32,
                                epilogue=epilogue)
    assert got.shape == (g, m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_grouped_matches_per_matrix_single_calls():
    """A grouped launch computes exactly what G separate launches do."""
    rng = np.random.default_rng(70)
    _, tg = _make_group(rng, 3, 256, 256, (0.6, 0.8, 0.9))
    b = jnp.asarray(rng.standard_normal((256, 16), dtype=np.float32))
    got = ops.spmm_grouped(tg, b, backend="interpret", out_dtype=jnp.float32)
    for g in range(3):
        single = ops.spmm(tiled_csl.group_slice(tg, g), b,
                          backend="interpret", out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got[g]), np.asarray(single),
                                   rtol=0.0, atol=0.0)


@pytest.mark.parametrize("epilogue", ["silu_mul", "gelu_mul"])
@pytest.mark.parametrize("n", [16, 7])   # 7 exercises the N-padding slice
def test_binary_epilogue_matches_ref(epilogue, n):
    """silu_mul/gelu_mul combine the G=2 pair into ONE output; epilogues
    must commute with the N-padding slice ops.spmm_grouped applies."""
    rng = np.random.default_rng(71)
    mats, tg = _make_group(rng, 2, 256, 128, (0.8, 0.8))
    b = jnp.asarray(rng.standard_normal((128, n), dtype=np.float32))
    got = ops.spmm_grouped(tg, b, backend="interpret", out_dtype=jnp.float32,
                           epilogue=epilogue)
    want = ref.spmm_grouped_ref(tg, b, out_dtype=jnp.float32,
                                epilogue=epilogue)
    assert got.shape == (256, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)
    # the ref itself equals the composed unfused math
    y0 = ref.spmm_ref(tiled_csl.group_slice(tg, 0), b, out_dtype=jnp.float32)
    y1 = ref.spmm_ref(tiled_csl.group_slice(tg, 1), b, out_dtype=jnp.float32)
    act = jax.nn.silu if epilogue == "silu_mul" else jax.nn.gelu
    np.testing.assert_allclose(np.asarray(want), np.asarray(act(y0) * y1),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("k,split_k", [
    (128, None),
    (1536, 1),           # Kt = 12: one 12-tile grid step
    (2176, 1),           # Kt = 17: no divisor up to 16, one tile a step
])
@pytest.mark.parametrize("epilogue", ["none", "silu", "silu_mul"])
def test_grouped_bias_fused(epilogue, k, split_k):
    rng = np.random.default_rng(72)
    _, tg = _make_group(rng, 2, 128, k, (0.7, 0.7))
    # B scaled by 1/sqrt(Kt) keeps the outputs at the single-tile case's
    # magnitude, for which the f32 tolerance below is set.
    b = jnp.asarray(rng.standard_normal((k, 8), dtype=np.float32)
                    / np.sqrt(k // 128, dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal((2, 128)), jnp.float32)
    got = ops.spmm_grouped(tg, b, backend="interpret", out_dtype=jnp.float32,
                           epilogue=epilogue, bias=bias, split_k=split_k)
    want = ref.spmm_grouped_ref(tg, b, out_dtype=jnp.float32,
                                epilogue=epilogue, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_single_spmm_fused_epilogue_with_n_padding():
    """ops.spmm pads N to the tile and slices after the fused flush — the
    epilogue (elementwise) must commute with that slice."""
    rng = np.random.default_rng(73)
    a, t = _make(rng, 256, 256, 0.8)
    b = jnp.asarray(rng.standard_normal((256, 5), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(256), jnp.float32)
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32,
                   epilogue="gelu", bias=bias)
    want = jax.nn.gelu(ref.spmm_ref(t, b, out_dtype=jnp.float32)
                       + bias[:, None])
    assert got.shape == (256, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_unknown_epilogue_raises_at_op_boundary():
    """Regression: a typo'd epilogue used to surface as a KeyError deep in
    the Pallas trace (or be silently dropped by ops.spmm)."""
    rng = np.random.default_rng(74)
    _, t = _make(rng, 128, 128, 0.8)
    b = jnp.ones((128, 8), jnp.float32)
    with pytest.raises(ValueError, match="unknown epilogue"):
        ops.spmm(t, b, backend="interpret", epilogue="gelu_typo")
    with pytest.raises(ValueError, match="unknown epilogue"):
        ref.spmm_ref(t, b, epilogue="gelu_typo")
    # binary epilogues need the grouped op with G == 2
    with pytest.raises(ValueError, match="binary epilogue"):
        ops.spmm(t, b, backend="interpret", epilogue="silu_mul")
    _, tg3 = _make_group(rng, 3, 128, 128, (0.8, 0.8, 0.8))
    with pytest.raises(ValueError, match="binary epilogue"):
        ops.spmm_grouped(tg3, b, backend="interpret", epilogue="silu_mul")
    # grouped/ungrouped ops reject the other encoding
    with pytest.raises(ValueError, match="grouped"):
        ops.spmm(tg3, b, backend="interpret")
    with pytest.raises(ValueError, match="ungrouped"):
        ops.spmm_grouped(t, b, backend="interpret")


# ---------------------------------------------------------------------------
# split-K SpMM: partials + global reduce (DESIGN.md §9)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 8])                 # decode regime
@pytest.mark.parametrize("m_tb,k_tb", [(128, 128), (64, 128), (128, 64)])
@pytest.mark.parametrize("split_k", [1, 2, 3])
def test_splitk_decode_parity_sweep(n, m_tb, k_tb, split_k):
    """The ISSUE-3 sweep: N in {1, 2, 8} x tile geometries x split factors
    through the public op (padding + dispatch). k_tb=128 gives Kt=3, so
    split_k=2 exercises the ragged last slice (Kt % S != 0) and split_k=3
    the one-tile-per-slice extreme; S=1 routes to the single-pass kernel.
    """
    m, k = 256, 384
    rng = np.random.default_rng(
        hash((n, m_tb, k_tb, split_k)) % 2 ** 31)
    a, t = _make(rng, m, k, 0.8, m_tb=m_tb, k_tb=k_tb)
    b = jnp.asarray(rng.standard_normal((k, n), dtype=np.float32))
    got = ops.spmm(t, b, backend="interpret", out_dtype=jnp.float32,
                   split_k=split_k)
    want = ref.spmm_ref(t, b, out_dtype=jnp.float32)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_splitk_s1_bitmatches_single_pass():
    """split_k == 1 is the identical computation (same accumulation order,
    same flush rounding points) in two launches — bit-exact, epilogue and
    bias included."""
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(80)
    a, t = _make(rng, 256, 384, 0.8)
    b = jnp.asarray(rng.standard_normal((384, 8), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(256), jnp.float32)
    base = spmm_mod.lscd_spmm(t, b, n_tb=8, interpret=True,
                              epilogue="gelu", bias=bias)
    s1 = spmm_mod.lscd_spmm_splitk(t, b, n_tb=8, split_k=1, interpret=True,
                                   epilogue="gelu", bias=bias)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(s1))


@pytest.mark.parametrize("split_k", [2, 3])
def test_splitk_matches_splitk_ref_association(split_k):
    """spmm_splitk_ref replicates the kernel's per-slice partial-sum
    association (partials summed over S, then bias + epilogue once)."""
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(81)
    a, t = _make(rng, 256, 384, 0.8)
    b = jnp.asarray(rng.standard_normal((384, 16), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(256), jnp.float32)
    got = spmm_mod.lscd_spmm_splitk(t, b, n_tb=16, split_k=split_k,
                                    interpret=True, epilogue="silu",
                                    bias=bias)
    want = ref.spmm_splitk_ref(t, b, split_k, out_dtype=jnp.float32,
                               epilogue="silu", bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # and the split association itself equals the plain oracle to roundoff
    plain = ref.spmm_ref(t, b, out_dtype=jnp.float32, epilogue="silu",
                         bias=bias)
    np.testing.assert_allclose(np.asarray(want), np.asarray(plain),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("kt,split_k", [
    (3, 2),      # ragged, d = 1
    (16, 3),     # ragged, d = 2: the last slice holds a block past K
])
@pytest.mark.parametrize("g", [2, 3])
@pytest.mark.parametrize("epilogue", ["none", "relu"])
def test_splitk_grouped_matches_ref(g, epilogue, kt, split_k):
    rng = np.random.default_rng(82 + g)
    _, tg = _make_group(rng, g, 256, kt * 128, (0.5, 0.8, 0.95))
    b = jnp.asarray(rng.standard_normal((kt * 128, 8), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal((g, 256)), jnp.float32)
    got = ops.spmm_grouped(tg, b, backend="interpret",
                           out_dtype=jnp.float32, split_k=split_k,
                           epilogue=epilogue, bias=bias)
    want = ref.spmm_splitk_grouped_ref(tg, b, split_k, out_dtype=jnp.float32,
                                       epilogue=epilogue, bias=bias)
    assert got.shape == (g, 256, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("epilogue", ["silu_mul", "gelu_mul"])
@pytest.mark.parametrize("n", [16, 7])   # 7 exercises the N-padding slice
@pytest.mark.parametrize("k", [256, 2048])   # d = 1; d = 8 (Kt 16, S 2)
def test_splitk_binary_epilogue_matches_ref(epilogue, n, k):
    """Binary epilogues combine the G=2 pair at the split-K reduce flush;
    they must commute with the N-padding slice as in the fused path."""
    rng = np.random.default_rng(83)
    _, tg = _make_group(rng, 2, 256, k, (0.8, 0.8))
    b = jnp.asarray(rng.standard_normal((k, n), dtype=np.float32))
    got = ops.spmm_grouped(tg, b, backend="interpret",
                           out_dtype=jnp.float32, split_k=2,
                           epilogue=epilogue)
    want = ref.spmm_grouped_ref(tg, b, out_dtype=jnp.float32,
                                epilogue=epilogue)
    assert got.shape == (256, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_splitk_grouped_s1_bitmatches_grouped():
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(84)
    _, tg = _make_group(rng, 2, 128, 256, (0.7, 0.9))
    b = jnp.asarray(rng.standard_normal((256, 8), dtype=np.float32))
    base = spmm_mod.lscd_spmm_grouped(tg, b, n_tb=8, interpret=True,
                                      epilogue="silu_mul")
    s1 = spmm_mod.lscd_spmm_splitk_grouped(tg, b, n_tb=8, split_k=1,
                                           interpret=True,
                                           epilogue="silu_mul")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(s1))


def test_splitk_invalid_split_raises():
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(85)
    _, t = _make(rng, 128, 256, 0.8)     # Kt = 2
    b = jnp.ones((256, 8), jnp.float32)
    with pytest.raises(ValueError, match="split_k"):
        spmm_mod.lscd_spmm_splitk(t, b, n_tb=8, split_k=0, interpret=True)
    with pytest.raises(ValueError, match="split_k"):
        spmm_mod.lscd_spmm_splitk(t, b, n_tb=8, split_k=3, interpret=True)


# ---------------------------------------------------------------------------
# spmm_diff: explicit epilogue/bias forwarding
# ---------------------------------------------------------------------------

def test_spmm_diff_forwards_epilogue_and_bias():
    rng = np.random.default_rng(86)
    _, t = _make(rng, 128, 128, 0.7)
    b = jnp.asarray(rng.standard_normal((128, 4), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(128), jnp.float32)
    got = ops.spmm_diff(t, b, epilogue="silu", bias=bias)
    want = ref.spmm_ref(t, b, out_dtype=b.dtype, epilogue="silu", bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    with pytest.raises(ValueError, match="unknown epilogue"):
        ops.spmm_diff(t, b, epilogue="nope")


def test_spmm_diff_bias_grad_matches_ref():
    rng = np.random.default_rng(87)
    _, t = _make(rng, 128, 128, 0.7)
    b = jnp.asarray(rng.standard_normal((128, 4), dtype=np.float32))
    bias = jnp.asarray(rng.standard_normal(128), jnp.float32)

    def f_custom(b_, bb):
        return jnp.sum(ops.spmm_diff(t, b_, bias=bb) ** 2)

    def f_ref(b_, bb):
        return jnp.sum(ref.spmm_ref(t, b_, out_dtype=jnp.float32,
                                    bias=bb) ** 2)

    gb, gbias = jax.grad(f_custom, argnums=(0, 1))(b, bias)
    gb_ref, gbias_ref = jax.grad(f_ref, argnums=(0, 1))(b, bias)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gbias), np.asarray(gbias_ref),
                               rtol=1e-4, atol=1e-4)
    # works under jit as well (the None-bias structure stays static)
    g_jit = jax.jit(jax.grad(lambda b_: jnp.sum(ops.spmm_diff(t, b_))))(b)
    assert g_jit.shape == b.shape


def test_spmm_diff_epilogue_grad_raises():
    """Regression: the bwd must refuse fused epilogues loudly instead of
    silently differentiating the pre-activation function."""
    rng = np.random.default_rng(88)
    _, t = _make(rng, 128, 128, 0.7)
    b = jnp.asarray(rng.standard_normal((128, 4), dtype=np.float32))
    # forward-only use is fine...
    _ = ops.spmm_diff(t, b, epilogue="gelu")
    # ...but differentiating through it raises
    with pytest.raises(ValueError, match="epilogue"):
        jax.grad(lambda b_: jnp.sum(ops.spmm_diff(t, b_, epilogue="gelu")))(b)


def test_grouped_xla_backend_matches_interpret():
    """The xla (CPU full-model) grouped path and the Pallas interpret path
    agree — the backend-dispatch contract of ops.spmm_grouped."""
    rng = np.random.default_rng(75)
    _, tg = _make_group(rng, 2, 256, 128, (0.8, 0.9))
    b = jnp.asarray(rng.standard_normal((128, 12), dtype=np.float32))
    for epi in ("none", "silu_mul"):
        xla = ops.spmm_grouped(tg, b, backend="xla", out_dtype=jnp.float32,
                               epilogue=epi)
        itp = ops.spmm_grouped(tg, b, backend="interpret",
                               out_dtype=jnp.float32, epilogue=epi)
        np.testing.assert_allclose(np.asarray(xla), np.asarray(itp),
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# per-tile slab bound: bit-exact against the full expansion
# ---------------------------------------------------------------------------

def _hetero_tiles(rng, mt, kt, member=0):
    """A matrix whose tiles need different slab counts: every third tile
    empty, the rest ~6% dense (1-2 slabs), and one column of one tile
    (moved by ``member``) 45 deep, which sets the encoding's slots."""
    a = rng.standard_normal((mt * 128, kt * 128), dtype=np.float32)
    a[rng.random(a.shape) < 0.94] = 0.0
    for i in range(mt * kt):
        if i % 3 == 1:
            mi, ki = divmod(i, kt)
            a[mi * 128:(mi + 1) * 128, ki * 128:(ki + 1) * 128] = 0.0
    mi, ki = divmod((mt * kt - 1 - 2 * member) % (mt * kt), kt)
    if (mi * kt + ki) % 3 == 1:
        ki = (ki + 1) % kt
    col = a[mi * 128:(mi + 1) * 128, ki * 128 + 7]
    col[:] = 0.0
    col[:45] = rng.standard_normal(45) + 3.0
    return a


def _full_expansion(t):
    """The same encoding expanding every non-empty tile over all its
    slots, as the kernel did before each tile carried its own bound."""
    import dataclasses
    full = jnp.where(t.nnz > 0, t.slots // tiled_csl.SLOT_QUANTUM, 0)
    return dataclasses.replace(t, slabs=full.astype(jnp.int32))


@pytest.mark.parametrize("entry,kt,split_k,g,epilogue", [
    ("lscd_spmm", 4, 1, None, "gelu"),                # d = 4
    ("lscd_spmm_splitk", 16, 3, None, "silu"),        # ragged, d = 2
    ("lscd_spmm_grouped", 4, 1, 3, "relu"),           # members differ
    ("lscd_spmm_splitk_grouped", 16, 3, 2, "silu_mul"),
])
def test_slab_bound_bitmatches_full_expansion(entry, kt, split_k, g,
                                              epilogue):
    """Stopping each tile's expansion at its own last slab changes no bit:
    the slabs skipped hold only padding. One launch mixes empty tiles,
    1-2-slab tiles and one 6-slab tile; grouped members differ."""
    from repro.kernels import spmm as spmm_mod
    rng = np.random.default_rng(90 + kt + (g or 0))
    mt, n = 2, 8
    if g is None:
        t = tiled_csl.encode(_hetero_tiles(rng, mt, kt))
        bias = jnp.asarray(rng.standard_normal(mt * 128), jnp.float32)
    else:
        t = tiled_csl.encode_group([_hetero_tiles(rng, mt, kt, i)
                                    for i in range(g)])
        bias = jnp.asarray(rng.standard_normal((g, mt * 128)), jnp.float32)
    slabs = np.asarray(t.slabs)
    assert t.slots == 48 and slabs.max() == 6 and (slabs == 0).any()
    assert set(np.unique(slabs[slabs > 0])) - {6}
    if g is not None:
        assert not np.array_equal(slabs[0], slabs[1])
    _, d = spmm_mod.launch_grid(t, n, n_tb=n, split_k=split_k,
                                b_dtype=jnp.float32, out_dtype=jnp.float32)
    assert d > 1
    b = jnp.asarray(rng.standard_normal((kt * 128, n), dtype=np.float32))
    kw = dict(n_tb=n, interpret=True, epilogue=epilogue, bias=bias)
    if split_k > 1:
        kw["split_k"] = split_k
    fn = getattr(spmm_mod, entry)
    got = fn(t, b, **kw)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(fn(_full_expansion(t), b, **kw)))
    oracle = ref.spmm_ref if g is None else ref.spmm_grouped_ref
    want = oracle(t, b, out_dtype=jnp.float32, epilogue=epilogue, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
