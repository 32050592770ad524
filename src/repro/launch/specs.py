"""Dry-run input specs and step builders.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model input of the given (arch × shape) cell — weak-type-correct, shardable,
zero allocation. ``build_cell`` wires up the step function + in_shardings
for lower/compile.

Weight modes (DESIGN.md §4, dry-run accounting note):
  dense      — baseline; f32 for train, bf16 for serving.
  sparse_xla — Tiled-CSL params with the XLA decompress-then-matmul path.
               The TiledCSL ShapeDtypeStructs use the analytic slot count
               of ``roofline.analytic_max_nnz``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import roofline, tiled_csl
from repro.distributed import sharding
from repro.models import transformer
from repro.models.config import ModelConfig, ShapeConfig
from repro.serving import engine
from repro.training import optimizer as opt_mod
from repro.training import train_loop

def _struct(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


# ---------------------------------------------------------------------------
# params / cache specs
# ---------------------------------------------------------------------------

def params_struct(cfg: ModelConfig, dtype=jnp.float32):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        functools.partial(transformer.init_model, cfg=cfg, dtype=dtype), key)


def _csl_struct(out_dim: int, in_dim: int, sparsity: float,
                lead: Tuple[int, ...] = ()) -> tiled_csl.TiledCSL:
    m_tb, k_tb = tiled_csl.DEFAULT_M_TB, tiled_csl.DEFAULT_K_TB
    mp = -(-out_dim // m_tb) * m_tb
    kp = -(-in_dim // k_tb) * k_tb
    mt, kt = mp // m_tb, kp // k_tb
    # one slot count for the whole leaf: every layer / expert of the stack
    n_mats = int(np.prod(lead, dtype=np.int64))
    slots = roofline.analytic_max_nnz(
        m_tb, k_tb, sparsity, columns=n_mats * mt * kp) // k_tb
    return tiled_csl.TiledCSL(
        words=_struct(lead + (mt, kt, slots, k_tb), jnp.uint32),
        nnz=_struct(lead + (mt, kt), jnp.int32),
        slabs=_struct(lead + (mt, kt), jnp.int32),
        shape=(mp, kp), m_tb=m_tb, k_tb=k_tb, dtype=jnp.bfloat16)


def default_should_sparsify(path: str) -> bool:
    """The paper's recipe: sparsify the big projection/FFN weights; keep
    router, norms, embeddings, conv kernels, gates dense."""
    sparse_names = ("wq", "wk", "wv", "wo", "gate", "up", "down",
                    "w_uq", "w_ukv", "w_dq", "w_dkv", "in_proj", "out_proj",
                    "w_x", "w_gate", "w_out", "lm_head")
    if "router" in path or "embed" in path or "norm" in path:
        return False
    if not path.endswith("['w']"):
        return False              # biases ([L, out]) must stay dense
    return any(f"'{n}'" in path for n in sparse_names)


def sparse_params_struct(cfg: ModelConfig, sparsity: float,
                         dtype=jnp.bfloat16,
                         should_sparsify: Callable[[str], bool] = None):
    """Dense param struct tree with selected weights replaced by TiledCSL
    structs (matching what ``pruning.sparsify_params`` produces)."""
    should = should_sparsify or default_should_sparsify
    dense = params_struct(cfg, dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(dense)
    leaves = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if leaf.ndim in (2, 3) and should(name):
            lead = tuple(leaf.shape[:-2])
            leaves.append(_csl_struct(leaf.shape[-2], leaf.shape[-1],
                                      sparsity, lead))
        else:
            leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def struct_weight_bytes(params) -> int:
    """HBM bytes of a params struct tree: TiledCSL leaves count their
    encoded streams (4 B/word + 4 B/tile slab count, =
    `tiled_csl.nbytes_sparse`) plus a 4 B/tile nnz counter, dense leaves
    their array bytes. Works on real trees and on
    `params_struct` / `sparse_params_struct` ShapeDtypeStruct stand-ins —
    the basis of `serving.budget`'s weight term."""
    total = 0
    leaves = jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, tiled_csl.TiledCSL))
    for leaf in leaves:
        if isinstance(leaf, tiled_csl.TiledCSL):
            total += int(np.prod(leaf.words.shape)) * 4
            total += int(np.prod(leaf.nnz.shape)) * 4
            total += int(np.prod(leaf.slabs.shape)) * 4
        else:
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total


def cache_struct(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(
        functools.partial(transformer.init_cache, cfg=cfg, batch=batch,
                          max_len=max_len))


# ---------------------------------------------------------------------------
# input specs per shape kind
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStructs for the step inputs of one (arch × shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    tok_shape = (B, cfg.n_codebooks, S) if cfg.n_codebooks else (B, S)
    if shape.kind == "train":
        out = {"tokens": _struct(tok_shape, jnp.int32),
               "targets": _struct(tok_shape, jnp.int32)}
        if cfg.mrope_sections is not None:
            out["positions"] = _struct((3, B, S), jnp.int32)
        return out
    if shape.kind == "prefill":
        out = {"tokens": _struct(tok_shape, jnp.int32)}
        if cfg.mrope_sections is not None:
            out["positions"] = _struct((3, B, S), jnp.int32)
        return out
    if shape.kind == "decode":
        tok = ((B, cfg.n_codebooks, 1) if cfg.n_codebooks else (B, 1))
        return {"token": _struct(tok, jnp.int32),
                "pos": _struct((), jnp.int32),
                "cache": cache_struct(cfg, B, S)}
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# cell builder: (step_fn, arg_structs, in_shardings)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    fn: Callable
    args: tuple
    in_shardings: tuple
    label: str
    donate: tuple = ()   # donated arg indices (prod: train state / kv cache)


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               weight_mode: str = "dense", sparsity: float = 0.8,
               backend: str = "xla", remat: Optional[str] = None,
               microbatches: int = 1) -> Cell:
    """Assemble the jit-able step + shardings for one dry-run cell."""
    if remat is None and shape.kind == "train":
        remat = "full"   # §Perf iteration 3: full per-block remat on the scan
    if remat is not None and remat != "keep":
        cfg = dataclasses.replace(cfg, remat=remat)
    stacked = cfg.scan_layers and cfg.uniform_layers
    train = shape.kind == "train"
    pdtype = jnp.float32 if train else jnp.bfloat16
    if weight_mode == "sparse_xla":
        params = sparse_params_struct(cfg, sparsity, pdtype)
    else:
        params = params_struct(cfg, pdtype)
    p_shard = sharding.params_shardings(params, mesh, fsdp=train)
    specs = input_specs(cfg, shape)
    label = f"{cfg.name}/{shape.name}/{weight_mode}"

    if shape.kind == "train":
        opt = opt_mod.AdamW(lr=1e-4)
        opt_state = jax.eval_shape(opt.init, params)
        o_shard = opt_mod.AdamWState(
            step=sharding.replicated(mesh),
            mu=jax.tree.map(lambda _, s: s, opt_state.mu, p_shard),
            nu=jax.tree.map(lambda _, s: s, opt_state.nu, p_shard))
        state = train_loop.TrainState(
            params=params, opt_state=opt_state,
            step=_struct((), jnp.int32))
        s_shard = train_loop.TrainState(
            params=p_shard, opt_state=o_shard,
            step=sharding.replicated(mesh))
        batch = {k: v for k, v in specs.items()}
        b_shard = jax.tree.map(
            lambda s: sharding.batch_sharding(
                mesh, s.ndim, batch_axis=1 if s.shape[0] == 3 else 0,
                shape=s.shape),
            batch)
        step = train_loop.make_train_step(cfg, opt, backend=backend,
                                          microbatches=microbatches)
        return Cell(fn=step, args=(state, batch),
                    in_shardings=(s_shard, b_shard), label=label,
                    donate=(0,))   # TrainState is updated in place

    if shape.kind == "prefill":
        B, S = shape.global_batch, shape.seq_len

        def prefill_fn(params, inputs):
            logits, cache = engine.prefill(
                params, inputs["tokens"], cfg, S,
                positions=inputs.get("positions"), backend=backend)
            return logits, cache

        in_sh = {k: sharding.batch_sharding(
            mesh, v.ndim, batch_axis=1 if v.shape[0] == 3 else 0,
            shape=v.shape)
            for k, v in specs.items()}
        return Cell(fn=prefill_fn, args=(params, specs),
                    in_shardings=(p_shard, in_sh), label=label)

    # decode
    B, S = shape.global_batch, shape.seq_len
    seq_shard = B == 1
    cache = specs["cache"]
    c_shard = sharding.cache_shardings(cache, mesh, stacked=stacked,
                                       seq_shard=seq_shard)
    tok_shard = (sharding.batch_sharding(mesh, specs["token"].ndim,
                                         shape=specs["token"].shape)
                 if B > 1 else sharding.replicated(mesh))

    def decode_fn(params, cache, token, pos):
        return engine.serve_step(params, cache, token, pos, cfg,
                                 backend=backend)

    return Cell(fn=decode_fn,
                args=(params, cache, specs["token"], specs["pos"]),
                in_shardings=(p_shard, c_shard, tok_shard,
                              sharding.replicated(mesh)),
                label=label,
                donate=(1,))   # KV cache is updated in place
