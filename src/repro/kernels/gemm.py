"""Dense GEMM Pallas baseline — the paper's cuBLAS comparison point.

The paper benchmarks Flash-LLM against cuBLAS-with-tensor-cores (its Fig.9
"dense" bars) and re-implements a cutlass-style dense kernel for the Fig.11
stage breakdown. This is our equivalent: the same grid/pipeline structure as
``spmm.lscd_spmm`` (same tiling, same accumulator, same epilogue hooks) but
with A streamed dense — so kernel-level comparisons isolate exactly the
Load-as-Sparse delta, nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import budgets, contracts


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_tiles: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...].astype(a_ref.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m_tb", "k_tb", "n_tb",
                                              "out_dtype", "interpret"))
def dense_gemm(a: jax.Array, b: jax.Array, *, m_tb: int = 128,
               k_tb: int = 128, n_tb: int = 128,
               out_dtype=jnp.float32, interpret: bool) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N], MXU-tiled in A's dtype with f32
    accumulation. Dims must divide the tiles."""
    m, k = a.shape
    n = b.shape[1]
    if m % m_tb or k % k_tb or n % n_tb:
        raise ValueError(f"shape {(m, k, n)} not tile-aligned")
    # VMEM contract (rule KC-VMEM, DESIGN.md §12): dense A/B/out blocks are
    # double-buffered by the grid pipeline, the f32 accumulator is not.
    budget = budgets.vmem_budget("interpret" if interpret else "pallas")
    if budget is not None:
        blocks = ((m_tb * k_tb + k_tb * n_tb) * a.dtype.itemsize
                  + m_tb * n_tb * jnp.dtype(out_dtype).itemsize)
        footprint = blocks * contracts.DOUBLE_BUFFER + m_tb * n_tb * 4
        if footprint > budget:
            raise ValueError(
                f"KC-VMEM: dense_gemm tile ({m_tb},{k_tb},{n_tb}) needs "
                f"{footprint} B of VMEM, budget {budget} B")
    grid = (m // m_tb, n // n_tb, k // k_tb)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_tiles=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_tb, k_tb), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((k_tb, n_tb), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((m_tb, n_tb), lambda mi, ni, ki: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((m_tb, n_tb), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
