"""Roofline model + HLO collective parser unit tests."""

import numpy as np
import pytest

from repro.core import roofline


def test_parse_collective_bytes_basic():
    hlo = """
  %ag = f32[1024,512]{1,0} all-gather(f32[64,512] %x), dimensions={0}
  %ar.1 = bf16[256,256]{1,0} all-reduce(bf16[256,256] %y), to_apply=%add
  %rs = f32[32,128]{1,0} reduce-scatter(f32[512,128] %z), dimensions={0}
  %a2a = (f32[8,128]{1,0}, f32[8,128]{1,0}) all-to-all(f32[8,128] %p, f32[8,128] %q)
  %cp = f32[16,16]{1,0} collective-permute(f32[16,16] %w), source_target_pairs={{0,1}}
  %dot = f32[128,128]{1,0} dot(f32[128,64] %a, f32[64,128] %b)
"""
    got = roofline.parse_collective_bytes(hlo)
    assert got["all-gather"] == 1024 * 512 * 4
    assert got["all-reduce"] == 256 * 256 * 2
    assert got["reduce-scatter"] == 32 * 128 * 4
    assert got["all-to-all"] == 2 * 8 * 128 * 4
    assert got["collective-permute"] == 16 * 16 * 4
    assert "dot" not in got


def test_parse_collective_start_done_dedup():
    hlo = """
  %ags = f32[64,64]{1,0} all-gather-start(f32[4,64] %x), dimensions={0}
  %agd = f32[64,64]{1,0} all-gather-done(f32[64,64] %ags)
"""
    got = roofline.parse_collective_bytes(hlo)
    assert got["all-gather"] == 64 * 64 * 4  # counted once (at -start)


def test_roofline_terms_bounds():
    t = roofline.RooflineTerms(flops=197e12, hbm_bytes=819e9,
                               collective_bytes=50e9, chips=1,
                               model_flops=98.5e12)
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(1.0)
    assert t.collective_s == pytest.approx(1.0)
    assert t.useful_flops_ratio == pytest.approx(0.5)
    assert t.step_time_s == pytest.approx(1.0)


def test_dense_vs_lscd_terms():
    m = k = 9216
    for n in (8, 64):
        d = roofline.dense_gemm_terms(m, k, n)
        s = roofline.lscd_kernel_terms(m, k, n, 0.8)
        assert d.bound == "memory"
        # LSCD reduces only the A-bytes term
        assert s.hbm_bytes < d.hbm_bytes
        assert s.flops == d.flops  # compute-as-dense
    # index overhead makes low sparsity LOSE (paper: crossover ~60%)
    s40 = roofline.lscd_kernel_terms(m, k, 8, 0.4)
    d = roofline.dense_gemm_terms(m, k, 8)
    assert s40.hbm_bytes > d.hbm_bytes


def test_ci_eq1_eq2():
    # Eq.1: CI <= min(M, N)
    assert roofline.dense_gemm_ci(1 << 20, 16) <= 16
    # Eq.2 at beta=0 reduces to Eq.1
    assert roofline.lscd_ci(4096, 16, 0.0) == pytest.approx(
        roofline.dense_gemm_ci(4096, 16))


def test_grouped_fused_terms_reduce_bytes():
    """The grouped fused path removes (a) per-call B re-streaming and
    (b) the pointwise epilogue's C round-trips; FLOPs stay dense."""
    m, k, n = 4 * 9216, 9216, 16
    # SwiGLU pair: fused silu_mul writes ONE C instead of 2 preacts + a
    # read-read-write pointwise pass.
    fused = roofline.lscd_grouped_terms(m, k, n, 0.8, group=2,
                                        epilogue="silu_mul", fused=True)
    unfused = roofline.lscd_grouped_terms(m, k, n, 0.8, group=2,
                                          epilogue="silu_mul", fused=False)
    assert fused.hbm_bytes < unfused.hbm_bytes
    assert fused.flops == unfused.flops
    saved = roofline.fused_epilogue_saved_bytes(m, k, n, 0.8, group=2,
                                                epilogue="silu_mul")
    # B once saves (G-1)*2kn; epilogue fusion saves 4 C-sized transfers
    expect = 2 * k * n + 4 * (2 * m * n)
    assert saved == pytest.approx(expect)
    # G=1 consistency: fused 'none' == the single-kernel terms
    t1 = roofline.lscd_grouped_terms(m, k, n, 0.8, group=1, fused=True)
    t0 = roofline.lscd_kernel_terms(m, k, n, 0.8)
    assert t1.hbm_bytes == pytest.approx(t0.hbm_bytes)
    assert t1.flops == pytest.approx(t0.flops)


def test_splitk_terms_partials_accounting():
    """Split-K charges exactly the f32 partials write+read on top of the
    S=1 schedule-level bytes; utilization crosses 1.0 at Mt*Nt*S >= 128."""
    m = k = 8192
    t1 = roofline.lscd_splitk_terms(m, k, 8, 0.8, n_tb=8, split_k=1)
    t2 = roofline.lscd_splitk_terms(m, k, 8, 0.8, n_tb=8, split_k=2)
    assert t1.partials_bytes == 0.0
    assert t2.partials_bytes == 2 * 4 * 2 * m * 8        # write + read, f32
    assert t2.terms.hbm_bytes == pytest.approx(
        t1.terms.hbm_bytes + t2.partials_bytes)
    # Mt = 64, Nt = 1: S=1 leaves half the latency-hiding budget unfilled
    assert t1.parallel_tiles == 64 and t1.utilization == pytest.approx(0.5)
    assert t2.parallel_tiles == 128 and t2.utilization == pytest.approx(1.0)
    # the decode-regime verdict: split-K wins effective time...
    assert t2.effective_s < t1.effective_s
    # ...but never raw roofline time (it strictly adds traffic)
    assert t2.terms.step_time_s >= t1.terms.step_time_s


def test_splitk_terms_prefill_penalty():
    """At N=2048 the launch saturates without splitting: S=2 is a pure
    partials-traffic loss."""
    m = k = 8192
    t1 = roofline.lscd_splitk_terms(m, k, 2048, 0.8, n_tb=128, split_k=1)
    t2 = roofline.lscd_splitk_terms(m, k, 2048, 0.8, n_tb=128, split_k=2)
    assert t1.utilization == 1.0
    assert t2.effective_s >= t1.effective_s


def test_splitk_terms_restream_accounting():
    """Schedule-level bytes charge A per N tile and B per M tile — the
    grid's real revisit pattern, not the streamed-once ideal."""
    m, k, n = 1024, 2048, 256
    max_nnz = 512
    t = roofline.lscd_splitk_terms(m, k, n, 0.8, n_tb=128, split_k=1,
                                   max_nnz=max_nnz)
    mt, kt, nt = m // 128, k // 128, n // 128
    a_once = mt * kt * max_nnz * 4.0
    expect = nt * a_once + mt * 2.0 * k * n + 2.0 * m * n
    assert t.terms.hbm_bytes == pytest.approx(expect)


def test_splitk_terms_validation_and_max_nnz():
    with pytest.raises(ValueError, match="split_k"):
        roofline.lscd_splitk_terms(128, 128, 8, 0.8, split_k=0)
    # analytic per-tile stream bound: whole 8-slot quanta of k_tb-word
    # slots, at least one quantum, at most the dense tile, and monotone in
    # density
    cols = 12 * 8960                      # one 1536x8960 matrix
    q = roofline.analytic_max_nnz(128, 128, 0.8, columns=cols)
    assert q % (8 * 128) == 0 and q >= 8 * 128
    assert roofline.analytic_max_nnz(128, 128, 0.5, columns=cols) > q
    assert roofline.analytic_max_nnz(128, 128, 1.0, columns=cols) == 8 * 128
    assert roofline.analytic_max_nnz(128, 128, 0.0, columns=cols) == 128 * 128


@pytest.mark.parametrize("layers,encoded_slots", [(1, 48), (28, 56)])
def test_analytic_slots_match_encoded_column_maximum(layers, encoded_slots):
    """The slot model takes the fullest column over every column sharing
    one encoding: a whole 28-layer scan stack pads further than a single
    matrix. ``encoded_slots`` is what encoding an 80%-sparse random mask
    gives (the column maximum simulated here, rounded to 8)."""
    cols = layers * 12 * 8960             # 1536x8960 on 128x128 tiles
    rng = np.random.default_rng(0)
    top = int(rng.binomial(128, 0.2, size=cols).max())
    assert -(-top // 8) * 8 == encoded_slots
    assert roofline.analytic_max_nnz(
        128, 128, 0.8, columns=cols) == encoded_slots * 128


def test_grouped_unary_terms_and_validation():
    m = k = 9216
    # G=3 QKV with no epilogue: the only saving is streaming B once.
    saved = roofline.fused_epilogue_saved_bytes(m, k, 8, 0.8, group=3,
                                                epilogue="none")
    assert saved == pytest.approx(2 * (2 * k * 8))
    # unary epilogue at G=1: fusion saves one C round-trip (read + write)
    saved1 = roofline.fused_epilogue_saved_bytes(m, k, 8, 0.8, group=1,
                                                 epilogue="gelu")
    assert saved1 == pytest.approx(2 * (2 * m * 8))
    with pytest.raises(ValueError, match="group=2"):
        roofline.lscd_grouped_terms(m, k, 8, 0.8, group=3,
                                    epilogue="silu_mul")


def test_device_peaks_keyed_by_device_kind():
    """One table keyed by jax device_kind; the analytic constants are the
    v5e entry; an unknown kind is an error, never a default."""
    v5e = roofline.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    assert roofline.PEAK_FLOPS_BF16 == v5e.bf16_flops
    assert roofline.HBM_BW == v5e.hbm_bw
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("cpu")
