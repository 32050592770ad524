"""Fig.10 / Fig.11 analogues: per-unit utilisation and latency breakdown.

Fig.10 (GPU-unit utilisation): for TPU we report, per MatMul shape, the
fraction of peak for MXU (compute), HBM, and the VMEM-bandwidth cost of the
extract stage (the paper's shared-memory pressure analogue):

    mxu_util  = T_ideal_compute / T_step
    hbm_util  = T_memory / T_step
    vmem_cost = extract bytes (slot words read + dense A-tile write) + MXU
                operand reads — relative to VMEM bw (~22x HBM).

Fig.11 (latency breakdown): per-stage times of the LSCD kernel under the
two-level-overlap model (stages overlap; wall = max(stages)):
    gmem  — compressed A + dense B traffic
    vmem  — extract + MXU operand reads
    mxu   — dense FLOPs

CSV: name,us_per_call,derived.
"""

from __future__ import annotations

from typing import List

from repro.core import roofline

VMEM_BW = 18e12  # ~per-chip VMEM bandwidth (v5e class, order-of-magnitude)


def stage_times(m: int, k: int, n: int, sparsity: float) -> dict:
    # column-slotted words at the analytic slot count (128x128 tiles)
    words = m * k / (128 * 128) * roofline.analytic_max_nnz(
        128, 128, sparsity, columns=m // 128 * k)
    gmem = (words * 4 + 2 * (k * n + m * n)) / roofline.HBM_BW
    # extract: read the slot words + write the dense tile
    vmem = (words * 4 + m * k * 2         # sparse->dense transform
            + (m * k + k * n) * 2          # MXU operand reads
            + m * n * 4) / VMEM_BW
    mxu = 2.0 * m * k * n / roofline.PEAK_FLOPS_BF16
    return {"gmem": gmem, "vmem": vmem, "mxu": mxu}


def run(full: bool = False) -> List[str]:
    rows: List[str] = []
    h = 9216  # OPT-66B hidden, the paper's Fig.10/11 model
    shapes = [("qkv", 3 * h, h), ("oproj", h, h),
              ("mlp1", 4 * h, h), ("mlp2", h, 4 * h)]
    for nm, m, k in shapes:
        for n in (16, 32):
            st_d = stage_times(m, k, n, 0.0)
            st_s = stage_times(m, k, n, 0.9)
            for tag, st in (("dense", st_d), ("lscd90", st_s)):
                wall = max(st.values())
                mxu_util = st["mxu"] / wall
                hbm_util = st["gmem"] / wall
                rows.append(
                    f"util_{nm}_n{n}_{tag},{wall * 1e6:.2f},"
                    f"mxu={mxu_util:.3f};hbm={hbm_util:.3f};"
                    f"gmem_us={st['gmem'] * 1e6:.2f};"
                    f"vmem_us={st['vmem'] * 1e6:.2f};"
                    f"mxu_us={st['mxu'] * 1e6:.2f}")
    return rows
