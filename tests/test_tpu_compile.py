"""Compile (never run) the LSCD kernel family and the dense Pallas GEMM for a
described TPU v5e at Qwen2-1.5B widths (d_model 1536, d_ff 8960), and the
LSCD launches that expand several K tiles a grid step at OPT-30B widths
(d_model 7168, d_ff 28672).

Interpret mode cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, primitives without a TPU lowering such as scatter, VMEM overruns);
the TPU compiler installed here can, without a chip. The topology is
described inside a module-scoped fixture — never while modules import — so
every test worker collects the same tests and only the worker running this
file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import roofline, tiled_csl
from repro.kernels import gemm, schedule, spmm

D_MODEL, D_FF = 1536, 8960
SPARSITY = 0.8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _csl(m, k, sharding, group=None):
    """Shape-only Tiled-CSL at the analytic 80%-sparse slot count."""
    mt, kt = m // tiled_csl.DEFAULT_M_TB, k // tiled_csl.DEFAULT_K_TB
    slots = roofline.analytic_max_nnz(
        tiled_csl.DEFAULT_M_TB, tiled_csl.DEFAULT_K_TB, SPARSITY,
        columns=(group or 1) * mt * k) // tiled_csl.DEFAULT_K_TB
    lead = (group,) if group else ()
    return tiled_csl.TiledCSL(
        words=_struct(lead + (mt, kt, slots, tiled_csl.DEFAULT_K_TB),
                      jnp.uint32, sharding),
        nnz=_struct(lead + (mt, kt), jnp.int32, sharding),
        slabs=_struct(lead + (mt, kt), jnp.int32, sharding),
        shape=(m, k), m_tb=tiled_csl.DEFAULT_M_TB,
        k_tb=tiled_csl.DEFAULT_K_TB, dtype=jnp.bfloat16)


def _decode_split(t, n):
    sched = schedule.select(
        t.shape[0], t.shape[1], n,
        schedule.sparsity_from_max_nnz(t.max_nnz, t.m_tb, t.k_tb),
        m_tb=t.m_tb, k_tb=t.k_tb, group=t.group or 1, max_nnz=t.max_nnz,
        backend="pallas", cache=False)
    assert sched.split_k > 1, f"select picked no split at N={n}: {sched}"
    return sched


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [8, 512])
def test_lscd_spmm_down_proj_compiles(one_chip, n):
    t = _csl(D_MODEL, D_FF, one_chip)
    b = _struct((D_FF, n), jnp.bfloat16, one_chip)
    _assert_kernel(spmm.lscd_spmm.lower(
        t, b, n_tb=min(n, 128), out_dtype=jnp.bfloat16,
        interpret=False).compile())


def test_lscd_spmm_grouped_gate_up_silu_mul_compiles(one_chip):
    t = _csl(D_FF, D_MODEL, one_chip, group=2)
    b = _struct((D_MODEL, 512), jnp.bfloat16, one_chip)
    _assert_kernel(spmm.lscd_spmm_grouped.lower(
        t, b, n_tb=128, out_dtype=jnp.bfloat16, interpret=False,
        epilogue="silu_mul").compile())


def test_lscd_spmm_splitk_pair_compiles_at_decode(one_chip):
    t = _csl(D_MODEL, D_FF, one_chip)
    sched = _decode_split(t, 8)
    b = _struct((D_FF, 8), jnp.bfloat16, one_chip)
    _assert_kernel(spmm.lscd_spmm_splitk.lower(
        t, b, n_tb=sched.n_tb, split_k=sched.split_k,
        out_dtype=jnp.bfloat16, interpret=False).compile())


def test_lscd_spmm_splitk_grouped_pair_compiles_at_decode(one_chip):
    t = _csl(D_FF, D_MODEL, one_chip, group=2)
    sched = _decode_split(t, 8)
    b = _struct((D_MODEL, 8), jnp.bfloat16, one_chip)
    _assert_kernel(spmm.lscd_spmm_splitk_grouped.lower(
        t, b, n_tb=sched.n_tb, split_k=sched.split_k,
        out_dtype=jnp.bfloat16, interpret=False,
        epilogue="silu_mul").compile())


@pytest.mark.parametrize("m,k,group,n,split_k,tiles", [
    (7168, 7168, 3, 64, 4, 14),       # decode q/k/v: grouped split-K pair
    (7168, 28672, None, 64, 4, 14),   # decode fc2: split-K pair
    (7168, 28672, None, 1024, 1, 16),  # prefill fc2: single pass
])
def test_lscd_multi_tile_steps_compile_at_opt30b_widths(one_chip, m, k, group,
                                                        n, split_k, tiles):
    t = _csl(m, k, one_chip, group=group)
    sched = schedule.select(
        m, k, n, schedule.sparsity_from_max_nnz(t.max_nnz, t.m_tb, t.k_tb),
        m_tb=t.m_tb, k_tb=t.k_tb, group=group or 1, max_nnz=t.max_nnz,
        backend="pallas", cache=False)
    assert sched.split_k == split_k
    _, d = spmm.launch_grid(t, n, n_tb=sched.n_tb, split_k=split_k,
                            b_dtype=jnp.bfloat16, out_dtype=jnp.bfloat16)
    assert d == tiles
    entry = {(False, False): spmm.lscd_spmm,
             (True, False): spmm.lscd_spmm_grouped,
             (False, True): spmm.lscd_spmm_splitk,
             (True, True): spmm.lscd_spmm_splitk_grouped}[
                 (group is not None, split_k > 1)]
    kw = {"split_k": split_k} if split_k > 1 else {}
    b = _struct((k, n), jnp.bfloat16, one_chip)
    _assert_kernel(entry.lower(
        t, b, n_tb=sched.n_tb, out_dtype=jnp.bfloat16, interpret=False,
        epilogue="gelu", **kw).compile())


def test_dense_gemm_compiles(one_chip):
    a = _struct((D_MODEL, D_FF), jnp.bfloat16, one_chip)
    b = _struct((D_FF, 8), jnp.bfloat16, one_chip)
    _assert_kernel(gemm.dense_gemm.lower(
        a, b, n_tb=8, out_dtype=jnp.bfloat16, interpret=False).compile())
