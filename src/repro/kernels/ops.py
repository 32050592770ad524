"""Public jit'd wrappers around the LSCD SpMM kernels.

``spmm`` is the framework-facing op: handles N padding, shape-aware
schedule selection (``kernels/schedule.py`` picks the N tile and the
split-K factor per (M, K, N, sparsity); ``split_k > 1`` routes to the
split-K kernel pair — DESIGN.md §9), backend dispatch (Pallas on TPU /
interpret for validation / XLA reference on CPU), fused bias/activation
epilogues, and a custom VJP (grad flows to the dense activation and the
bias only — the Tiled-CSL weight is an inference-time format; training
uses masked dense weights, see ``core/pruning.py``).

``spmm_grouped`` is the grouped entry (G same-shape weights, one launch, B
streamed once; binary epilogues combine G == 2 pairs — DESIGN.md §8).

Epilogue names are validated here against the kernel registry so a typo
raises a ``ValueError`` at the op boundary instead of a ``KeyError`` deep
inside the Pallas trace. Epilogues are elementwise over [M, N] (bias
broadcasts over N), so they commute with the N-padding slice both wrappers
apply.
"""

from __future__ import annotations

import functools
import math
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import tiled_csl
from repro.kernels import ref as ref_mod
from repro.kernels import schedule as schedule_mod
from repro.kernels import spmm as spmm_mod
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace

Backend = Literal["auto", "pallas", "interpret", "xla"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick_schedule(t: tiled_csl.TiledCSL, n: int, backend: str,
                   n_tb: int | None, split_k: int | None, b_dtype, out_dtype,
                   kind: str = "spmm") -> schedule_mod.Schedule:
    # Sparsity comes from static metadata only (the true nnz sum is a
    # device value and must not be read under jit); the shared helper keeps
    # dispatch and autotune cache keys bit-identical.
    sparsity = schedule_mod.sparsity_from_max_nnz(t.max_nnz, t.m_tb, t.k_tb)
    sched = schedule_mod.select(
        t.shape[0], t.shape[1], n, sparsity,
        m_tb=t.m_tb, k_tb=t.k_tb, n_tb=n_tb, split_k=split_k,
        group=t.group or 1, max_nnz=t.max_nnz, backend=backend)
    _note_launch(kind, t, n, sparsity, backend, sched, b_dtype, out_dtype)
    return sched


def _note_launch(kind: str, t: tiled_csl.TiledCSL, n: int, sparsity: float,
                 backend: str, sched: schedule_mod.Schedule, b_dtype,
                 out_dtype) -> None:
    """Observability hook at the dispatch site (runs at jit-trace time, so
    once per compiled shape — an honest granularity under jit: per-call
    wall timing needs the fenced profiling mode, obs/profile.py).

    The ``kernel`` event carries the compute kernel's ``grid_steps`` and
    the K tiles each step expands (``tiles_per_step``; 1 means the launch
    pays the per-step cost once per tile)."""
    prof = obs_profile.active()
    tr = obs_trace.get_tracer()
    if prof is None and not tr.enabled:
        return
    m, k = t.shape[0], t.shape[1]
    group = t.group or 1
    if prof is not None:
        prof.note_dispatch(kind, m, k, n, sparsity, group, t.max_nnz,
                           t.m_tb, t.k_tb, backend, sched)
    if tr.enabled:
        terms = schedule_mod.predicted(m, k, n, sparsity, sched,
                                       group=group, max_nnz=t.max_nnz)
        n_pad = -(-n // sched.n_tb) * sched.n_tb
        grid, d = spmm_mod.launch_grid(
            t, n_pad, n_tb=sched.n_tb, split_k=sched.split_k,
            b_dtype=b_dtype, out_dtype=out_dtype)
        tr.event("kernel", f"{kind} {m}x{k}x{n}", "kernel",
                 backend=backend, schedule=sched.as_dict(), group=group,
                 sparsity=round(float(sparsity), 4),
                 predicted_us=terms.effective_s * 1e6,
                 tiles_per_step=d,
                 grid_steps=sched.split_k * math.prod(grid))


def spmm(t: tiled_csl.TiledCSL,
         b: jax.Array,
         *,
         out_dtype=None,
         backend: Backend = "auto",
         n_tb: int | None = None,
         split_k: int | None = None,
         epilogue: str = "none",
         bias: jax.Array | None = None) -> jax.Array:
    """C[M, N] = epilogue(A_tiled_csl[M, K] @ B[K, N] + bias).

    backend:
      auto      — Pallas on TPU, XLA reference elsewhere (full-model CPU runs).
      pallas    — force the TPU kernel (interpret=False).
      interpret — Pallas kernel body on CPU (correctness validation).
      xla       — decompress-then-matmul reference path.

    ``n_tb``/``split_k`` pin the schedule; left None, ``schedule.select``
    picks both per (M, K, N, sparsity) — so the same weights get a split-K
    launch at decode N and a single-pass one at prefill N. ``split_k > 1``
    runs the split-K kernel pair (f32 partials + reduce; DESIGN.md §9).

    epilogue (unary: none/silu/gelu/relu) and bias ([M]) are fused into the
    kernel flush (applied by the reference oracle on the xla path) — the
    activated C is written once instead of write/read/write.
    """
    if t.group is not None:
        raise ValueError("grouped TiledCSL: use spmm_grouped")
    spmm_mod.epilogue_kind(epilogue)  # raises on unknown / binary names
    out_dtype = out_dtype or b.dtype
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend == "xla":
        return ref_mod.spmm_ref(t, b, out_dtype=out_dtype, epilogue=epilogue,
                                bias=bias)

    n = b.shape[1]
    sched = _pick_schedule(t, n, backend, n_tb, split_k, b.dtype, out_dtype,
                           kind="spmm")
    n_pad = -(-n // sched.n_tb) * sched.n_tb
    if n_pad != n:
        b = jnp.pad(b, ((0, 0), (0, n_pad - n)))
    kern = (spmm_mod.lscd_spmm if sched.split_k == 1
            else functools.partial(spmm_mod.lscd_spmm_splitk,
                                   split_k=sched.split_k))
    out = kern(t, b, n_tb=sched.n_tb, out_dtype=out_dtype,
               interpret=(backend == "interpret"), epilogue=epilogue,
               bias=bias)
    # Epilogues are elementwise, so slicing the padded columns off after the
    # fused flush equals applying them to the unpadded result.
    return out[:, :n] if n_pad != n else out


def spmm_grouped(t: tiled_csl.TiledCSL,
                 b: jax.Array,
                 *,
                 out_dtype=None,
                 backend: Backend = "auto",
                 n_tb: int | None = None,
                 split_k: int | None = None,
                 epilogue: str = "none",
                 bias: jax.Array | None = None) -> jax.Array:
    """Grouped LSCD SpMM: G same-shape weights against one B, one launch.

    Returns C[G, M, N] (unary epilogues, applied per group; bias is [G, M])
    or C[M, N] (binary epilogues ``silu_mul``/``gelu_mul`` combining the
    G == 2 pair in VMEM — the SwiGLU fusion). Backends and schedule
    selection (``n_tb``/``split_k`` pins vs ``schedule.select``) as in
    :func:`spmm`.
    """
    groups = t.group
    if groups is None:
        raise ValueError("ungrouped TiledCSL: use spmm")
    kind = spmm_mod.epilogue_kind(epilogue, groups=groups)
    out_dtype = out_dtype or b.dtype
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if backend == "xla":
        return ref_mod.spmm_grouped_ref(t, b, out_dtype=out_dtype,
                                        epilogue=epilogue, bias=bias)

    n = b.shape[1]
    sched = _pick_schedule(t, n, backend, n_tb, split_k, b.dtype, out_dtype,
                           kind="spmm_grouped")
    n_pad = -(-n // sched.n_tb) * sched.n_tb
    if n_pad != n:
        b = jnp.pad(b, ((0, 0), (0, n_pad - n)))
    kern = (spmm_mod.lscd_spmm_grouped if sched.split_k == 1
            else functools.partial(spmm_mod.lscd_spmm_splitk_grouped,
                                   split_k=sched.split_k))
    out = kern(t, b, n_tb=sched.n_tb, out_dtype=out_dtype,
               interpret=(backend == "interpret"), epilogue=epilogue,
               bias=bias)
    if n_pad != n:
        out = out[:, :n] if kind == "binary" else out[..., :n]
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def _spmm_diff(t, b, epilogue, bias):
    return spmm(t, b, epilogue=epilogue, bias=bias)


def _spmm_fwd(t, b, epilogue, bias):
    # The residual is the bias itself: its None-ness is pytree *structure*
    # (static under jit), which is all the backward needs to know.
    return spmm(t, b, epilogue=epilogue, bias=bias), bias


def _spmm_bwd(t, epilogue, bias, g):
    # dB = A^T @ dC; use the XLA reference transpose (backward runs on the
    # training path where weights are dense+masked anyway — this exists for
    # API completeness, e.g. activation-gradient probes through a served model).
    if epilogue != "none":
        raise ValueError(
            f"spmm_diff backward does not differentiate through the fused "
            f"epilogue {epilogue!r}; apply the activation outside spmm_diff "
            f"(epilogue='none') when gradients are needed")
    a = tiled_csl.decode_jax(t).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    db = jnp.dot(a.T, gf).astype(g.dtype)
    dbias = None if bias is None else jnp.sum(gf, axis=1).astype(bias.dtype)
    return (db, dbias)


_spmm_diff.defvjp(_spmm_fwd, _spmm_bwd)


def spmm_diff(t: tiled_csl.TiledCSL, b: jax.Array, *,
              epilogue: str = "none",
              bias: jax.Array | None = None) -> jax.Array:
    """Differentiable-in-(B, bias) SpMM (weights are a frozen inference
    format). ``epilogue``/``bias`` forward to :func:`spmm`; the backward
    supports only ``epilogue="none"`` and raises a ``ValueError`` otherwise
    — it must never silently differentiate the pre-activation function."""
    spmm_mod.epilogue_kind(epilogue)  # unknown/binary names raise up front
    return _spmm_diff(t, b, epilogue, bias)
