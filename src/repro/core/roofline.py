"""Three-term roofline model (compute / memory / collective) for the TPU.

Terms (per step, per the assignment spec):

  compute_s    = HLO_FLOPs / (chips * PEAK_FLOPS)
  memory_s     = HLO_bytes / (chips * HBM_BW)
  collective_s = collective_bytes / (chips * ICI_BW)

``from_cost_analysis`` builds the terms from a compiled executable's
``cost_analysis()`` + HLO text (collective bytes are parsed from the HLO —
they are not in cost_analysis). ``lscd_kernel_terms`` gives the analytic
roofline of the Pallas SpMM (compressed-A bytes), cross-checked at kernel
level by the benchmarks.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Dict, Optional

# ---- per-chip peaks, keyed by jax ``Device.device_kind`` --------------------


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float            # FLOP/s, bf16 MXU
    hbm_bw: float                # bytes/s
    hbm_bytes: float             # HBM capacity
    ici_bw: float                # bytes/s per chip-to-chip link


#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
#: of HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip (4 links).
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bw=819e9,
                               hbm_bytes=16e9, ici_bw=50e9),
}

#: The chip the analytic (device-free) roofline describes. Every model in
#: this module reads these constants; a second chip in the table needs its
#: peaks threaded through first.
ANALYTIC_PEAKS = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = ANALYTIC_PEAKS.bf16_flops
HBM_BW = ANALYTIC_PEAKS.hbm_bw
ICI_BW = ANALYTIC_PEAKS.ici_bw


def peaks_for(device_kind: str) -> DevicePeaks:
    """Peaks of a measured device. A kind missing from the table is an
    error, never a default: a roofline share against another chip's peaks
    is wrong by construction."""
    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to roofline.DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# matches e.g.  f32[256,1024]{1,0}  or bf16[8,128]
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # total HLO (or analytic) FLOPs per step
    hbm_bytes: float             # total HBM bytes per step
    collective_bytes: float      # per-chip collective bytes per step
    chips: int
    label: str = ""
    model_flops: float = 0.0     # 6·N·D (or 2·N_active·tokens for serving)
    collective_breakdown: Optional[Dict[str, float]] = None

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS_BF16)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def collective_s(self) -> float:
        # collective_bytes is already per-chip link traffic.
        return self.collective_bytes / ICI_BW

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    model_bytes: float = 0.0     # irreducible HBM bytes (weights+cache)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the ideal roofline achieved.

        ideal step time = max(model_flops / peak, model_bytes / bw): the
        time the *useful* work needs on the binding resource. A memory-bound
        decode step that streams only the weights+cache once scores 1.0; a
        step whose HLO moves 3x the irreducible bytes scores ~0.33. When
        model_bytes is unknown (0), falls back to the compute-only ideal
        (an MFU-at-roofline number)."""
        if self.step_time_s == 0:
            return 0.0
        ideal_c = self.model_flops / (self.chips * PEAK_FLOPS_BF16)
        ideal_m = self.model_bytes / (self.chips * HBM_BW)
        ideal = max(ideal_c, ideal_m)
        return min(ideal / self.step_time_s, 1.0) if ideal else 0.0

    def as_dict(self) -> dict:
        return {
            "label": self.label, "chips": self.chips,
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "step_time_s": self.step_time_s,
            "collective_breakdown": self.collective_breakdown,
        }


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in an HLO dump.

    Matches lines like
      ``%ar = f32[1024,512]{1,0} all-reduce(...)`` and tuple-shaped results
      ``(f32[8,128], f32[8,128]) all-to-all(...)``.
    The result size of a collective equals its operand size for these ops,
    so this is the per-chip ICI traffic estimate (all-gather result is the
    gathered size — bytes received per chip, the right roofline quantity).
    """
    out: Dict[str, float] = {op: 0.0 for op in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # find " = <shape(s)> <op>(" — op name right before the open paren
        m = re.search(r"=\s+(.+?)\s+([\w-]+)(?:-start|-done)?\(", stripped)
        if not m:
            continue
        shapes_str, op = m.group(1), m.group(2)
        base = None
        for coll in _COLLECTIVE_OPS:
            if op == coll or op == coll + "-start" or op == coll + "-done":
                base = coll
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        nbytes = 0.0
        for dt, dims in _SHAPE_RE.findall(shapes_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[base] += nbytes
    return {k: v for k, v in out.items() if v > 0}


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def from_cost_analysis(cost: dict, hlo_text: str, chips: int, *,
                       label: str = "", model_flops: float = 0.0
                       ) -> RooflineTerms:
    """Build roofline terms from compiled.cost_analysis() + HLO text.

    cost_analysis flops/bytes are *global* (whole-program across the SPMD
    partition as reported per module); with SPMD partitioning XLA reports
    the per-device module, so multiply by ``chips`` for totals.
    """
    breakdown = parse_collective_bytes(hlo_text)
    flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    return RooflineTerms(
        flops=flops * chips,
        hbm_bytes=raw_bytes * chips,
        collective_bytes=sum(breakdown.values()),
        chips=chips,
        label=label,
        model_flops=model_flops,
        collective_breakdown=breakdown,
    )


# ---------------------------------------------------------------------------
# analytic kernel roofline (the LSCD claim, paper Eq.1 / Eq.2)
# ---------------------------------------------------------------------------

def dense_gemm_ci(m: int, n: int) -> float:
    """Paper Eq.1: CI = M·N/(M+N) FLOP/(half-word); bf16 2-byte elements."""
    return (m * n) / (m + n)


def lscd_ci(m: int, n: int, sparsity: float) -> float:
    """Paper Eq.2: CI under Load-as-Sparse (index overhead excluded there;
    we report the honest version including the 32-bit word overhead in
    ``lscd_kernel_terms``)."""
    return (m * n) / (m * (1.0 - sparsity) + n)


def dense_gemm_terms(m: int, k: int, n: int, *, chips: int = 1,
                     dtype_bytes: int = 2, label: str = "dense") -> RooflineTerms:
    flops = 2.0 * m * k * n
    bytes_ = dtype_bytes * (m * k + k * n + m * n)
    return RooflineTerms(flops=flops, hbm_bytes=bytes_, collective_bytes=0.0,
                         chips=chips, label=label, model_flops=flops)


def lscd_kernel_terms(m: int, k: int, n: int, sparsity: float, *,
                      pad_overhead: float = 0.0, chips: int = 1,
                      label: str = "lscd") -> RooflineTerms:
    """Analytic roofline of the Pallas LSCD kernel.

    A-traffic = nnz·4 bytes (32-bit packed words, incl. measured padding),
    B/C dense bf16. FLOPs stay dense (compute-as-dense). This is what the
    fused kernel streams on real hardware; the kernel benchmark cross-checks
    the byte count against the format's ``nbytes_sparse``.
    """
    nnz = m * k * (1.0 - sparsity)
    a_bytes = nnz * 4.0 / max(1.0 - pad_overhead, 1e-9)
    bytes_ = a_bytes + 2.0 * (k * n + m * n)
    flops = 2.0 * m * k * n
    return RooflineTerms(flops=flops, hbm_bytes=bytes_, collective_bytes=0.0,
                         chips=chips, label=label, model_flops=flops)


def _epilogue_is_binary(name: str) -> bool:
    """Single-source the epilogue registry from the kernel (lazy import so
    this module stays numpy-only at import time); unknown names raise the
    same ValueError the op layer would."""
    from repro.kernels import spmm as _spmm
    if name in _spmm._BINARY_EPILOGUES:
        return True
    if name in _spmm._EPILOGUES:
        return False
    _spmm.epilogue_kind(name)  # raises with the known-names message
    return False


def lscd_grouped_terms(m: int, k: int, n: int, sparsity: float, *,
                       group: int = 1, epilogue: str = "none",
                       fused: bool = True, pad_overhead: float = 0.0,
                       chips: int = 1, label: str = "lscd_grouped"
                       ) -> RooflineTerms:
    """Analytic roofline of G same-shape LSCD projections + epilogue.

    ``fused=True`` models one grouped kernel launch (DESIGN.md §8): the G
    compressed-A streams, B streamed **once**, and the epilogue applied in
    VMEM — C is one [M, N] write-back for binary epilogues
    (silu_mul/gelu_mul; the SwiGLU fusion) or G write-backs for unary ones.

    ``fused=False`` models the pre-fusion execution the model stack used to
    pay: G separate kernel calls (each re-streaming B and writing its
    pre-activation C), plus — when an epilogue is requested — an XLA
    pointwise pass that reads the pre-activation C's back from HBM and
    writes the activated result. The delta between the two is the traffic
    the grouped fused path removes; ``benchmarks/kernel_bench.py`` reports
    it per paper shape.
    """
    binary = _epilogue_is_binary(epilogue)
    if binary and group != 2:
        raise ValueError(f"binary epilogue {epilogue!r} needs group=2")
    nnz = m * k * (1.0 - sparsity)
    a_bytes = group * nnz * 4.0 / max(1.0 - pad_overhead, 1e-9)
    c_one = 2.0 * m * n                     # one bf16 [M, N] block
    if fused:
        b_bytes = 2.0 * k * n               # B streamed once for all G
        c_bytes = c_one if binary else group * c_one
    else:
        b_bytes = group * 2.0 * k * n       # one B stream per call
        c_bytes = group * c_one             # pre-activation writes
        if epilogue != "none":
            # separate pointwise pass: read the pre-activations back, write
            # the activated result (one combined C for binary epilogues).
            c_bytes += group * c_one + (c_one if binary else group * c_one)
    flops = group * 2.0 * m * k * n
    return RooflineTerms(flops=flops, hbm_bytes=a_bytes + b_bytes + c_bytes,
                         collective_bytes=0.0, chips=chips, label=label,
                         model_flops=flops)


# ---------------------------------------------------------------------------
# split-K schedule-level accounting (DESIGN.md §9)
# ---------------------------------------------------------------------------

# Number of independent tile-programs a launch needs before the chip stops
# being latency-bound: enough (m, n, s) grid cells must be in flight to keep
# the DMA engines saturating HBM while earlier cells occupy the VPU/MXU, and
# (on multi-core parts) to give every core work. Below this, achieved
# bandwidth degrades roughly linearly with available parallelism — the
# skinny-decode failure mode split-K exists to fix (paper §4.4: at N <= 64
# the N-tile count is 1 and M-tiles alone cannot fill the machine).
LATENCY_HIDING_TILES = 128


def splitk_partials_bytes(m: int, n_pad: int, split_k: int) -> float:
    """Extra HBM traffic a split-K schedule pays: the f32 partials buffer
    ``[S, M, N]`` is written once by the main kernel and read once by the
    reduce kernel. ``split_k == 1`` dispatches to the fused single-pass
    kernel (no partials buffer), so the cost is zero there."""
    if split_k <= 1:
        return 0.0
    return 2.0 * 4.0 * split_k * m * n_pad


@dataclasses.dataclass
class SplitKTerms:
    """Roofline terms of one concrete LSCD schedule (tile geometry + split).

    Unlike :func:`lscd_kernel_terms` (the shape-level ideal: every operand
    streamed once), this charges what the grid actually moves:

      * A re-streamed once per N-tile (the words block index is independent
        of n, but the grid revisits every (m, k) for each n-tile);
      * B re-streamed once per M-tile (symmetrically);
      * the f32 partials write+read when ``split_k > 1``.

    ``utilization`` models the skinny-regime parallelism cliff: with fewer
    than LATENCY_HIDING_TILES independent (m, n, s) cells the launch is
    latency-bound and achieved bandwidth scales with the cell count.
    ``effective_s = step_time / utilization`` is what the schedule selector
    minimises.
    """

    terms: RooflineTerms
    m_tb: int
    k_tb: int
    n_tb: int
    split_k: int
    parallel_tiles: int
    utilization: float
    partials_bytes: float

    @property
    def effective_s(self) -> float:
        return self.terms.step_time_s / max(self.utilization, 1e-9)

    def as_dict(self) -> dict:
        d = self.terms.as_dict()
        d.update({
            "m_tb": self.m_tb, "k_tb": self.k_tb, "n_tb": self.n_tb,
            "split_k": self.split_k, "parallel_tiles": self.parallel_tiles,
            "utilization": self.utilization,
            "partials_bytes": self.partials_bytes,
            "effective_s": self.effective_s,
        })
        return d


# Analytic per-tile stream bound when no measured encoding is at hand
# (DESIGN.md §4). The column-slotted layout gives every tile column the slot
# count of the fullest column among all the columns that share one
# encoding: every tile of a matrix, of every layer of a scan stack and of
# every member of a projection group. For a random unstructured mask a
# column of m_tb rows holds Binomial(m_tb, 1−s) non-zeros; the model takes
# the median of the maximum over ``columns`` such columns (the smallest c
# with columns·P(X > c) <= 1/2) and rounds up to the 8-row sublane quantum.
# At 80% sparsity on 128-row tiles: one 1536x8960 matrix (~1e5 columns)
# gives 47 -> 48 slots, a 28-layer stack of them (~3e6) 51 -> 56 slots.
_SLOT_QUANTUM = 8


@functools.lru_cache(maxsize=1024)
def analytic_max_nnz(m_tb: int, k_tb: int, sparsity: float, *,
                     columns: int) -> int:
    """Padded words per tile (``slots * k_tb``) of an encoding whose slot
    count is shared by ``columns`` tile columns."""
    d = min(max(1.0 - sparsity, 0.0), 1.0)
    pmf = [math.comb(m_tb, j) * d ** j * (1.0 - d) ** (m_tb - j)
           for j in range(m_tb + 1)]
    c, above = m_tb, 0.0                  # above = P(X > c)
    while c > 0 and max(columns, 1) * (above + pmf[c]) <= 0.5:
        above += pmf[c]
        c -= 1
    slots = max(math.ceil(c / _SLOT_QUANTUM), 1) * _SLOT_QUANTUM
    return min(slots, math.ceil(m_tb / _SLOT_QUANTUM) * _SLOT_QUANTUM) * k_tb


def lscd_splitk_terms(m: int, k: int, n: int, sparsity: float, *,
                      m_tb: int = 128, k_tb: int = 128, n_tb: int = 8,
                      split_k: int = 1, group: int = 1,
                      max_nnz: Optional[int] = None, chips: int = 1,
                      label: str = "lscd_splitk") -> SplitKTerms:
    """Schedule-level roofline of the (grouped) LSCD split-K SpMM.

    ``max_nnz`` is the encoding's real padded per-tile stream length when
    known (``TiledCSL.max_nnz`` — what the kernel actually DMAs); otherwise
    the DESIGN.md §4 analytic bound is used. ``group`` multiplies the A
    stream, FLOPs, and C/partials blocks (one output per group member; the
    binary-epilogue single-C saving is below the selection noise floor and
    is accounted by :func:`lscd_grouped_terms` instead).

    Returns :class:`SplitKTerms`; the schedule selector minimises its
    ``effective_s`` (roofline time deflated by the parallelism-utilization
    factor — the term that makes S > 1 win for skinny N despite the extra
    partials traffic).
    """
    if split_k < 1:
        raise ValueError(f"split_k must be >= 1, got {split_k}")
    mt = -(-m // m_tb)
    kt = -(-k // k_tb)
    nt = -(-n // n_tb)
    n_pad = nt * n_tb
    if max_nnz is None:
        max_nnz = analytic_max_nnz(m_tb, k_tb, sparsity,
                                   columns=group * mt * kt * k_tb)
    a_once = float(group) * mt * kt * (max_nnz * 4.0)     # words stream
    b_once = 2.0 * k * n_pad                              # bf16 activation
    c_bytes = float(group) * 2.0 * m * n_pad              # bf16 outputs
    partials = float(group) * splitk_partials_bytes(m, n_pad, split_k)
    bytes_ = nt * a_once + mt * b_once + c_bytes + partials
    flops = float(group) * 2.0 * m * k * n_pad
    if split_k > 1:                                       # reduce-kernel adds
        flops += float(group) * split_k * m * n_pad
    terms = RooflineTerms(flops=flops, hbm_bytes=bytes_, collective_bytes=0.0,
                          chips=chips, label=label,
                          model_flops=float(group) * 2.0 * m * k * n)
    parallel = mt * nt * split_k
    util = min(1.0, parallel / float(LATENCY_HIDING_TILES))
    return SplitKTerms(terms=terms, m_tb=m_tb, k_tb=k_tb, n_tb=n_tb,
                       split_k=split_k, parallel_tiles=parallel,
                       utilization=util, partials_bytes=partials)


def fused_epilogue_saved_bytes(m: int, k: int, n: int, sparsity: float, *,
                               group: int = 1, epilogue: str = "none",
                               pad_overhead: float = 0.0) -> float:
    """HBM bytes per call the grouped fused path avoids vs unfused."""
    unfused = lscd_grouped_terms(m, k, n, sparsity, group=group,
                                 epilogue=epilogue, fused=False,
                                 pad_overhead=pad_overhead)
    fused = lscd_grouped_terms(m, k, n, sparsity, group=group,
                               epilogue=epilogue, fused=True,
                               pad_overhead=pad_overhead)
    return unfused.hbm_bytes - fused.hbm_bytes
