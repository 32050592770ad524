"""HBM budget planner: convert Tiled-CSL weight-byte savings into KV blocks.

This module makes the paper's memory→throughput conversion *executable*
(DESIGN.md §10): the abstract's claim is that compressing weights frees HBM
that turns into a larger effective batch. The planner computes exactly that
trade:

    n_blocks = (hbm_budget − weight_bytes(mode, sparsity) − workspace)
               // block_bytes(cfg, block)

so switching `dense → sparse_pallas` at a given sparsity *provably* buys a
larger block pool at equal total budget — the quantity the paged scheduler
(`serving.batching`, cache_kind="paged") then spends on admitted requests.

Weight bytes come from `launch.specs` weight-mode structs (the same
accounting the dry-run uses): dense bf16 leaves, or Tiled-CSL encoded
streams (`tiled_csl.nbytes_sparse`: 4 B/word + 4 B/tile slab count, and a
4 B/tile nnz counter, at the analytic slot count of
`roofline.analytic_max_nnz`). `sparse_pallas` and `sparse_xla` stream the
same encoded bytes — the mode names the kernel, not the format — so both
map to the sparse struct.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp

from repro.launch import specs
from repro.models.config import ModelConfig

WEIGHT_MODES = ("dense", "sparse_pallas", "sparse_xla")

# Decode-step workspace floor when the caller does not override it:
# activations, logits, scratch prefill cache and compiled-program slack.
DEFAULT_WORKSPACE_FRAC = 0.03


def weight_bytes(cfg: ModelConfig, mode: str = "dense",
                 sparsity: float = 0.8) -> int:
    """Serving weight bytes for one (arch × weight-mode) deployment."""
    if mode not in WEIGHT_MODES:
        raise ValueError(f"weight mode {mode!r} not in {WEIGHT_MODES}")
    if mode == "dense":
        struct = specs.params_struct(cfg, jnp.bfloat16)
    else:
        struct = specs.sparse_params_struct(cfg, sparsity, jnp.bfloat16)
    return specs.struct_weight_bytes(struct)


def block_bytes(cfg: ModelConfig, block: int, dtype_bytes: int = 2) -> int:
    """HBM bytes of ONE KV block (``block`` token positions, all layers).

    MLA layers store (c_kv, k_rope) latents; GQA layers store K + V heads.
    The sliding window does not change block bytes — it caps how many
    blocks a request can hold, not what a block costs.
    """
    per_tok = 0
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) != "attn":
            raise ValueError(
                "paged KV blocks require a pure-attention stack "
                f"(layer {i} is {cfg.layer_kind(i)!r})")
        if cfg.attn_kind == "mla":
            per_tok += (cfg.kv_lora_rank + cfg.qk_rope_dim) * dtype_bytes
        else:
            per_tok += 2 * cfg.n_kv * cfg.head_dim * dtype_bytes
    return per_tok * block


@dataclasses.dataclass(frozen=True)
class Plan:
    """One planned deployment: where every HBM byte goes."""

    arch: str
    weight_mode: str
    sparsity: float
    hbm_budget: int
    weight_bytes: int
    workspace_bytes: int
    block: int
    block_bytes: int
    n_blocks: int                 # usable KV blocks the budget affords
    kv_bytes: int                 # (n_blocks + 1) * block_bytes, incl. the
                                  # reserved trash block the device pool
                                  # physically carries (paged_cache)

    @property
    def kv_positions(self) -> int:
        return self.n_blocks * self.block

    def n_dense_slots(self, max_len: int) -> int:
        """The dense-cache baseline the same KV budget affords: slots of
        ``max_len`` pre-reserved positions (DESIGN.md §7) — the number the
        paged pool's admitted concurrency is measured against."""
        per_slot = max_len * (self.block_bytes // self.block)
        return self.kv_bytes // max(per_slot, 1)

    def worst_case_blocks(self, prompt_len: int, max_new_tokens: int,
                          max_len: int,
                          ring_len: Optional[int] = None) -> int:
        """KV blocks a request can grow to before it completes — the same
        bound `Scheduler.validate_request` enforces at submit: K/V
        positions reach prompt + (max_new − 1) generated (the last sampled
        token is never written back), capped by ``max_len`` and the
        sliding-window ring."""
        n_pos = min(prompt_len + max(max_new_tokens - 1, 0), max_len)
        if ring_len is not None:
            n_pos = min(n_pos, ring_len)
        return -(-n_pos // self.block)          # ceil div

    def can_serve(self, prompt_len: int, max_new_tokens: int,
                  max_len: int, ring_len: Optional[int] = None) -> bool:
        """Whether this plan's pool can ever run such a request to
        completion — the deploy-time twin of the server's submit-time
        `RequestRejected` check, so sizing scripts learn the answer before
        a server exists."""
        return self.worst_case_blocks(prompt_len, max_new_tokens, max_len,
                                      ring_len) <= self.n_blocks

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["kv_positions"] = self.kv_positions
        return d


def plan(cfg: ModelConfig, *, hbm_budget: int, weight_mode: str = "dense",
         sparsity: float = 0.8, block: int = 128,
         workspace_bytes: Optional[int] = None) -> Plan:
    """Size the KV block pool for one deployment.

    ``block`` defaults to 128 tokens — one MXU tile of positions, so a
    block's K/V rows land tile-aligned in the decode gather (DESIGN.md §10).
    Raises ValueError when the budget cannot hold the weights plus one
    block: that deployment needs more chips, not a scheduler.
    """
    wb = weight_bytes(cfg, weight_mode, sparsity)
    ws = (int(hbm_budget * DEFAULT_WORKSPACE_FRAC)
          if workspace_bytes is None else workspace_bytes)
    bb = block_bytes(cfg, block)
    usable = hbm_budget - wb - ws
    # The device pool physically carries one extra row — the reserved
    # trash block (paged_cache.BlockPool.physical_blocks) — so it is
    # charged here too: n_blocks counts only *usable* blocks.
    physical = usable // bb if usable > 0 else 0
    n_blocks = physical - 1
    if n_blocks < 1:
        raise ValueError(
            f"{cfg.name}/{weight_mode}: budget {hbm_budget / 1e9:.1f} GB "
            f"cannot hold weights ({wb / 1e9:.1f} GB) + workspace "
            f"({ws / 1e9:.1f} GB) + trash block + one usable "
            f"{bb / 1e6:.1f} MB KV block")
    return Plan(arch=cfg.name, weight_mode=weight_mode, sparsity=sparsity,
                hbm_budget=int(hbm_budget), weight_bytes=wb,
                workspace_bytes=ws, block=block, block_bytes=bb,
                n_blocks=int(n_blocks), kv_bytes=int(physical * bb))
