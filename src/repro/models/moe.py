"""Mixture-of-Experts: top-k routed experts + optional shared experts.

Covers qwen2-moe-a2.7b (4 shared + 60 routed top-4, d_expert=1408) and
qwen3-moe-30b-a3b (128 routed top-8, d_expert=768, no shared).

Dispatch is the TPU-native *dropping* scheme (Switch/MaxText style): tokens
are split into subgroups of ``moe_subgroup`` tokens; within a subgroup each
expert has capacity C = ceil(sg·k/E·cf); routing builds a one-hot dispatch
tensor [sg, E, C] contracted with einsums — no scatters, fully shardable:
tokens shard over (pod, data), experts shard over model (EP). Total dispatch
memory scales with sg (not sg²), so subgrouping keeps it bounded.

Expert weights are stacked [E, d_ff_e, d] / [E, d, d_ff_e] — per-expert
matrices are individually skinny at decode, so LSCD sparsification applies
per expert (stacked Tiled-CSL; DESIGN.md §6).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import sparse_linear, tiled_csl
from repro.models import nn, layers
from repro.models.config import ModelConfig


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, e, dff = cfg.d_model, cfg.n_routed_experts, cfg.d_expert
    ks = nn.split_keys(key, 5)
    p = {
        "router": {"w": nn.dense_init(ks[0], e, d, dtype)},
        "gate": jax.random.normal(ks[1], (e, dff, d)).astype(dtype) * d ** -0.5,
        "up": jax.random.normal(ks[2], (e, dff, d)).astype(dtype) * d ** -0.5,
        "down": jax.random.normal(ks[3], (e, d, dff)).astype(dtype) * dff ** -0.5,
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_swiglu_mlp(
            ks[4], d, cfg.d_shared_expert * cfg.n_shared_experts, dtype)
    return p


def _expert_ffn(params, xe: jax.Array) -> jax.Array:
    """xe: [E, C*, d] -> [E, C*, d] — batched per-expert SwiGLU.

    Expert weights may be stacked dense arrays [E, f, d] or stacked
    TiledCSL (words [E, mt, kt, slots, k_tb]); the latter uses a vmapped XLA
    reference decode (kernel path is per-expert at serving time).
    """
    def one(w_stack, x, out_dim):
        if isinstance(w_stack, tiled_csl.TiledCSL):
            def apply_e(wl_words, wl_nnz, wl_slabs, xl):
                t = tiled_csl.TiledCSL(
                    words=wl_words, nnz=wl_nnz, slabs=wl_slabs,
                    shape=w_stack.shape, m_tb=w_stack.m_tb,
                    k_tb=w_stack.k_tb, dtype=w_stack.dtype)
                return sparse_linear.linear_logical_out(t, out_dim, xl)
            return jax.vmap(apply_e)(w_stack.words, w_stack.nnz,
                                     w_stack.slabs, x)
        return jnp.einsum("ecd,efd->ecf", x, w_stack.astype(x.dtype))

    dff = (params["gate"].shape[1] if not isinstance(params["gate"], tiled_csl.TiledCSL)
           else params["gate"].shape[0])
    d = xe.shape[-1]
    g = one(params["gate"], xe, dff)
    u = one(params["up"], xe, dff)
    h = jax.nn.silu(g) * u
    return one(params["down"], h, d)


def moe_block(params: dict, x: jax.Array, cfg: ModelConfig, *,
              backend: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, aux_loss). Routed top-k with capacity dropping."""
    Bsz, S, d = x.shape
    E, k = cfg.n_routed_experts, cfg.top_k
    sg = min(cfg.moe_subgroup, Bsz * S)
    T = Bsz * S
    assert T % sg == 0, (T, sg)
    G = T // sg
    xt = x.reshape(G, sg, d)

    logits = sparse_linear.linear_logical_out(
        params["router"]["w"], E, xt, backend=backend).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # [G,sg,E]
    gate_vals, idx = jax.lax.top_k(probs, k)                  # [G,sg,k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=(0, 1))
    onehot_k = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # [G,sg,k,E]
    fe = jnp.mean(jnp.sum(onehot_k, axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * fe)

    C = int(-(-sg * k // E) * cfg.capacity_factor)
    C = max(C, 1)
    # Fold the k axis into E first (a token picks k *distinct* experts), so
    # the one-hot-over-capacity tensor is [G,sg,E,C], not [G,sg,k,E,C].
    oh_e = jnp.sum(onehot_k, axis=2)                          # [G,sg,E] 0/1
    gates_e = jnp.einsum("gsk,gske->gse", gate_vals.astype(jnp.float32),
                         onehot_k)                            # [G,sg,E]
    pos_e = (jnp.cumsum(oh_e, axis=1) * oh_e - 1.0).astype(jnp.int32)
    # one_hot maps -1 (not chosen) and >=C (over capacity) to all-zeros.
    dispatch = jax.nn.one_hot(pos_e, C, dtype=jnp.float32)    # [G,sg,E,C]
    combine = dispatch * gates_e[..., None]

    xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(x.dtype), xt)
    xe = xe.reshape(E, G * C, d)
    ye = _expert_ffn(params, xe).reshape(E, G, C, d)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), ye)

    if cfg.n_shared_experts:
        y = y + layers.swiglu_mlp(
            params["shared"], xt,
            d_ff=cfg.d_shared_expert * cfg.n_shared_experts,
            d_model=d, backend=backend)
    return y.reshape(Bsz, S, d), aux
