"""Tiled-CSL format benchmarks: encode throughput, compression ratio,
slot padding and bytes per non-zero vs sparsity.

Validates the format-level numbers everything else relies on:
  * bytes ratio vs dense bf16 (the Load-as-Sparse win)
  * measured slot padding (what ``roofline.analytic_max_nnz`` models)
  * streamed bytes per true non-zero, padding included

CSV: name,us_per_call,derived.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import tiled_csl


def run(full: bool = False) -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)
    m = k = 2048 if not full else 8192
    for s in (0.5, 0.7, 0.8, 0.9, 0.95):
        a = rng.standard_normal((m, k), dtype=np.float32)
        a[rng.random((m, k)) < s] = 0.0
        t0 = time.perf_counter()
        t = tiled_csl.encode(a)
        enc_us = (time.perf_counter() - t0) * 1e6
        ratio = t.nbytes_sparse / t.nbytes_dense
        rows.append(
            f"tiledcsl_encode_{m}x{k}_s{int(s * 100)},{enc_us:.0f},"
            f"bytes_ratio={ratio:.3f};pad_overhead={t.pad_overhead:.3f};"
            f"slots={t.slots};bytes_per_nnz={t.bytes_per_nonzero:.2f};"
            f"mb_per_s={(m * k * 4 / 2 ** 20) / (enc_us / 1e6):.0f}")
    # roundtrip sanity at 80%
    a = rng.standard_normal((1024, 1024), dtype=np.float32)
    a[rng.random(a.shape) < 0.8] = 0.0
    t = tiled_csl.encode(a)
    err = float(np.max(np.abs(tiled_csl.decode(t) - a)))
    rel = err / float(np.max(np.abs(a)))
    rows.append(f"tiledcsl_roundtrip_relerr,{rel * 1e6:.3f},bf16_rounding")

    # grouped encoding (gate+up style): the shared slot count costs a little
    # extra padding vs two independent encodings — measure that delta, since
    # it is the price of the one-launch grouped kernel (DESIGN.md §8).
    mats = []
    for s in (0.8, 0.8):
        g = rng.standard_normal((1024, 1024), dtype=np.float32)
        g[rng.random(g.shape) < s] = 0.0
        mats.append(g)
    t0 = time.perf_counter()
    tg = tiled_csl.encode_group(mats)
    enc_us = (time.perf_counter() - t0) * 1e6
    solo_bytes = sum(tiled_csl.encode(m).nbytes_sparse for m in mats)
    rows.append(
        f"tiledcsl_encode_group_g2_1024x1024_s80,{enc_us:.0f},"
        f"bytes_ratio={tg.nbytes_sparse / tg.nbytes_dense:.3f};"
        f"shared_slots_overhead={tg.nbytes_sparse / solo_bytes - 1.0:.4f};"
        f"pad_overhead={tg.pad_overhead:.3f}")
    return rows
