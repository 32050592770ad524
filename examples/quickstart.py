"""Quickstart: the Flash-LLM pipeline in 60 lines.

  1. make a dense weight, prune it to 80% unstructured sparsity
  2. reformat to Tiled-CSL (the paper's sparse encoding, column-slotted)
  3. run the Load-as-Sparse / Compute-as-Dense SpMM (Pallas, interpret
     mode on CPU) and check it against the dense result
  4. print the memory + roofline numbers behind the paper's claim

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import pruning, roofline, tiled_csl
from repro.kernels import ops, ref

M, K, N = 1024, 1024, 16          # a skinny decode-style MatMul
SPARSITY = 0.8

rng = np.random.default_rng(0)
w = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
x = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)

# 1. prune (magnitude, unstructured — the paper's accuracy-preserving kind)
w_pruned = pruning.prune(w, SPARSITY)
print(f"pruned: {float((w_pruned == 0).mean()):.1%} zeros")

# 2. reformat to Tiled-CSL
t = tiled_csl.encode(np.asarray(w_pruned))
print(f"Tiled-CSL: {t.grid} tiles of {t.m_tb}x{t.k_tb}, max_nnz={t.max_nnz}, "
      f"pad overhead {t.pad_overhead:.1%}")
print(f"bytes: dense {t.nbytes_dense / 2 ** 20:.2f} MiB -> "
      f"sparse {t.nbytes_sparse / 2 ** 20:.2f} MiB "
      f"({t.nbytes_sparse / t.nbytes_dense:.2f}x)")

# 3. LSCD SpMM on the Pallas kernel (interpret mode on CPU)
y_kernel = ops.spmm(t, x, backend="interpret", out_dtype=jnp.float32)
y_dense = ref.spmm_dense_oracle(w_pruned, x)
err = float(jnp.max(jnp.abs(y_kernel - y_dense)))
print(f"kernel vs dense max err: {err:.4f} (bf16 value rounding)")

# 4. the paper's roofline argument (Eq.1 / Eq.2) on TPU v5e numbers
d = roofline.dense_gemm_terms(M, K, N)
s = roofline.lscd_kernel_terms(M, K, N, SPARSITY, pad_overhead=t.pad_overhead)
print(f"dense : CI={roofline.dense_gemm_ci(M, N):6.1f}  "
      f"step={d.step_time_s * 1e6:7.2f} us  bound={d.bound}")
print(f"LSCD  : CI={roofline.lscd_ci(M, N, SPARSITY):6.1f}  "
      f"step={s.step_time_s * 1e6:7.2f} us  bound={s.bound}  "
      f"-> {d.step_time_s / s.step_time_s:.2f}x faster")
