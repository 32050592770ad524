"""Sharding rules: param-tree paths → PartitionSpec (DP/TP/EP/SP/pod).

MaxText-style logical rules, expressed as (path-regex, spec-builder) pairs
matched against ``jax.tree_util.keystr`` paths. Conventions:

* ``model`` axis: TP — attention head/ff/vocab dims, MoE expert dim (EP).
* ``data`` (+ ``pod``) axes: batch DP; optionally FSDP weight shards.
* activations: batch over ("pod","data"), model-parallel dims over "model"
  (propagated by GSPMD from the param + input shardings).
* Tiled-CSL leaves: ``words [*, mt, kt, slots, k_tb]`` shard ``mt`` (the out-dim
  tile axis) over model — the encoding is tile-aligned so TP shards never
  split a tile (DESIGN.md §5).

Stacked scan params carry a leading L axis (never sharded); MoE experts
carry an E axis (sharded over model = EP).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DATA_AXES = ("pod", "data")   # batch shards over both (pod present or not)


def _spec(*axes) -> P:
    return P(*axes)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


# Rules: (regex on keystr path, out-dim-position spec builder).
# Builders receive ndim and return a PartitionSpec. The leading dims that
# don't belong to the logical matrix ([L] scan and/or [E] experts) are
# detected by ndim relative to the base rank.
def _mat(out_axis: Optional[str], in_axis: Optional[str]):
    """Spec for a [out, in] matrix with 0-2 leading stack dims.

    MoE expert stacks shard the leading E dim over model (EP). When E does
    not divide the model axis (e.g. qwen2-moe's 60 experts on 16-way TP),
    EP would be silently dropped by fit_spec and the experts fully
    replicated (measured: a 124 s collective term from per-layer expert
    all-gathers at train_4k multi-pod — §Perf iteration 7). The fallback
    shards *inside* each expert matrix instead (TP-within-expert)."""
    def build(path: str, ndim: int, shape=None, model_size: int = 16) -> P:
        lead = ndim - 2
        if _is_routed_expert(path):
            e = shape[lead - 1] if (shape is not None and lead >= 1) else None
            if e is not None and e % model_size:
                # EP doesn't divide -> TP within the expert matrices
                return P(*((None,) * lead), out_axis, in_axis)
            pre = ((None,) * (lead - 1) + ("model",)) if lead >= 1 else ()
            return P(*pre, None, None)
        return P(*((None,) * lead), out_axis, in_axis)
    return build


def _is_routed_expert(path: str) -> bool:
    """Routed-expert weight stacks [.., E, out, in] (not router / shared)."""
    return ("moe" in path and "shared" not in path and "router" not in path)


def _vec(axis: Optional[str]):
    def build(path: str, ndim: int) -> P:
        return P(*((None,) * (ndim - 1)), axis)
    return build


def _replicate(path: str, ndim: int) -> P:
    return P(*((None,) * 0))


# Tiled-CSL: words [lead..., mt, kt, slots, k_tb]; nnz [lead..., mt, kt].
def _csl_words(out_sharded: bool):
    def build(path: str, ndim: int) -> P:
        lead = ndim - 4
        if _is_routed_expert(path):
            pre = ((None,) * (lead - 1) + ("model",)) if lead >= 1 else ()
            return P(*pre, None, None, None, None)
        mt_ax, kt_ax = ("model", None) if out_sharded else (None, "model")
        return P(*((None,) * lead), mt_ax, kt_ax, None, None)
    return build


def _csl_nnz(out_sharded: bool):
    def build(path: str, ndim: int) -> P:
        lead = ndim - 2
        if _is_routed_expert(path):
            pre = ((None,) * (lead - 1) + ("model",)) if lead >= 1 else ()
            return P(*pre, None, None)
        mt_ax, kt_ax = ("model", None) if out_sharded else (None, "model")
        return P(*((None,) * lead), mt_ax, kt_ax)
    return build


# Which weight families shard out-dim over model (column-parallel) vs
# in-dim over model (row-parallel, Megatron pairing).
_COL = ("wq", "wk", "wv", "gate", "up", "w_uq", "w_ukv", "w_dq", "in_proj",
        "w_x", "w_gate", "wa", "lm_head",
        # reformat-time grouped projections (pruning.group_projections):
        # words [*, G, mt, kt, slots, k_tb] — the generic lead-axis handling in
        # _csl_words leaves the group axis unsharded, mt over model.
        "gate_up", "wqkv")
_ROW = ("wo", "down", "out_proj", "w_out")


def rule_for(path: str, ndim: int, *, fsdp: bool = False,
             shape=None, model_size: int = 16) -> P:
    """PartitionSpec for a param leaf at tree path ``path``.

    fsdp=True additionally shards the non-TP matrix dim over "data" (ZeRO-3
    style) — required for training-state residency of the 33B-class archs on
    v5e (params+AdamW moments / 256 chips). GSPMD inserts the per-layer
    all-gathers inside the scan (the overlap is the pipeliner's job)."""
    is_words = path.endswith(".words")
    # the per-tile counts (nnz, slabs) shard as the tiles do
    is_nnz = path.endswith((".nnz", ".slabs"))
    other = "data" if fsdp else None

    def family(names) -> bool:
        return any(f"'{n}'" in path for n in names)

    # embeddings: [V, d] (or [ncb, V, d]) — vocab over model
    if "'embed'" in path:
        if is_words:
            return _csl_words(True)(path, ndim)
        if is_nnz:
            return _csl_nnz(True)(path, ndim)
        return P(*((None,) * (ndim - 2)), "model", other)

    # MoE router [.., E, d]: out dim IS the expert dim — align with EP.
    if family(("router",)):
        if is_words:
            lead = ndim - 4
            return P(*((None,) * lead), "model", None, None, None)
        if is_nnz:
            return P(*((None,) * (ndim - 2)), "model", None)
        return P(*((None,) * (ndim - 2)), "model", None)

    def _expert_divides() -> bool:
        lead = ndim - 2
        if shape is None or lead < 1:
            return True
        return shape[lead - 1] % model_size == 0

    if family(_COL):
        if is_words:
            return _csl_words(True)(path, ndim)
        if is_nnz:
            return _csl_nnz(True)(path, ndim)
        if ndim == 1 or path.endswith("['b']"):   # bias [out]
            return _vec("model")(path, ndim)
        if _is_routed_expert(path) and fsdp and _expert_divides():
            lead = ndim - 2
            pre = ((None,) * (lead - 1) + ("model",)) if lead >= 1 else ()
            return P(*pre, "data", None)          # EP + expert-dim FSDP
        return _mat("model", other)(path, ndim, shape=shape,
                                    model_size=model_size)

    if family(_ROW):
        if is_words:
            return _csl_words(False)(path, ndim)  # in-dim (kt) over model
        if is_nnz:
            return _csl_nnz(False)(path, ndim)
        if ndim == 1 or path.endswith("['b']"):
            return P()                            # row-parallel bias replicated
        if _is_routed_expert(path) and fsdp and _expert_divides():
            lead = ndim - 2
            pre = ((None,) * (lead - 1) + ("model",)) if lead >= 1 else ()
            return P(*pre, "data", None)
        return _mat(other, "model")(path, ndim, shape=shape,
                                    model_size=model_size)

    # everything else (norms, gates, conv kernels, w_dkv, scalars): replicated
    return P()


def fit_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop spec axes that don't divide the dim evenly (pjit argument
    shardings must divide exactly; internal constraints may pad, arguments
    may not)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[n] for n in names]))
        if i < len(shape) and shape[i] % size == 0:
            out.append(entry)
        elif (not isinstance(entry, tuple)) or len(names) == 1:
            out.append(None)
        else:
            # try a prefix of the axis tuple
            kept = []
            rem = shape[i] if i < len(shape) else 0
            for n in names:
                if rem % mesh.shape[n] == 0:
                    kept.append(n)
                    rem //= mesh.shape[n]
            out.append(tuple(kept) if kept else None)
    out += [None] * (len(shape) - len(out))
    return P(*out)


def params_shardings(params, mesh: Mesh, *, fsdp: bool = False):
    """Tree of NamedShardings matching ``params``."""
    model_size = mesh.shape.get("model", 1)

    def leaf_spec(path, leaf):
        nd = getattr(leaf, "ndim", 0)
        spec = rule_for(jax.tree_util.keystr(path), nd, fsdp=fsdp,
                        shape=getattr(leaf, "shape", None),
                        model_size=model_size)
        spec = fit_spec(spec, getattr(leaf, "shape", ()), mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def batch_sharding(mesh: Mesh, ndim: int, *, batch_axis: int = 0,
                   shape: Optional[Sequence[int]] = None) -> NamedSharding:
    """Shard a batch tensor's leading axis over (pod, data)."""
    axes: list = [None] * ndim
    axes[batch_axis] = batch_axes(mesh)
    spec = P(*axes)
    if shape is not None:
        spec = fit_spec(spec, shape, mesh)
    return NamedSharding(mesh, spec)


def cache_shardings(cache, mesh: Mesh, *, stacked: bool,
                    seq_shard: bool = False):
    """KV/state caches: slot (batch) axis over (pod, data); KV-head (or
    head-dim, when kv-heads don't divide) over model — a 32k cache for a
    62L model does not fit one chip otherwise; optionally the sequence axis
    over data for long-context SP when batch == 1.

    Argument shardings must divide exactly (pjit requirement), so every
    axis choice is divisibility-guarded with fallbacks.

    Cache leaf layouts (``stacked`` = scan models carry a leading L):
      attention:  [L?, B, S, kv, hd]    k/v
      MLA:        [L?, B, S, kvr] ckv / [L?, B, S, dr] krope
      SSM:        [L?, B, h, p, n] state / [L?, B, cv-1, ch] conv
      RG-LRU:     [L?, B, r] h / [L?, B, cv-1, r] conv
    """
    dax = batch_axes(mesh)
    d_size = int(np.prod([mesh.shape[a] for a in dax]))
    m_size = mesh.shape.get("model", 1)

    def leaf_spec(path, leaf):
        nd = leaf.ndim
        key = jax.tree_util.keystr(path)
        b_idx = 1 if stacked else 0
        axes: list = [None] * nd
        is_kv = "'k'" in key or "'v'" in key
        is_latent = "'ckv'" in key
        if seq_shard and (is_kv or is_latent or "'krope'" in key):
            if leaf.shape[b_idx + 1] % mesh.shape["data"] == 0:
                axes[b_idx + 1] = "data"          # SP over the cache length
        elif leaf.shape[b_idx] % d_size == 0:
            axes[b_idx] = dax
        elif leaf.shape[b_idx] % mesh.shape["data"] == 0:
            axes[b_idx] = "data"
        if is_kv and nd == b_idx + 4:
            # Sequence-shard the cache over model (flash-decode style):
            # per-step collectives become tiny score/softmax psums instead
            # of a per-layer all-gather of the kv/hd-sharded cache
            # (measured 6.4 GiB/step of all-gathers at tinyllama decode_32k
            # — §Perf iteration 9). Head/hd sharding are the fallbacks.
            if axes[b_idx + 1] is None and leaf.shape[b_idx + 1] % m_size == 0:
                axes[b_idx + 1] = "model"         # sequence axis
            elif leaf.shape[b_idx + 2] % m_size == 0:
                axes[b_idx + 2] = "model"         # kv-head axis
            elif leaf.shape[b_idx + 3] % m_size == 0:
                axes[b_idx + 3] = "model"         # head-dim fallback
        # MLA latent caches stay model-replicated: the latent rank is tiny
        # (kvr+dr ~ 288 bytes/token) and sharding it over model puts an
        # all-reduce on the latent score contraction every decode step
        # (measured 0.43 s collective term at minicpm3 decode_32k —
        # §Perf iteration 6b); replicated latents let each device attend
        # with its own query heads collective-free.
        return NamedSharding(mesh, P(*axes))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _context_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` / ``jax.set_mesh`` context,
    if any."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x, *axes):
    """MaxText-style activation sharding constraint.

    ``axes`` are logical entries per dim: None, an axis name, a tuple of
    names, or "batch" (expands to the mesh's (pod, data)). No-ops when no
    mesh context is active (single-device tests) or when an axis doesn't
    divide, so model code can constrain unconditionally.
    """
    mesh = _context_mesh()
    if mesh is None:
        return x
    resolved = []
    for a in axes:
        if a == "batch":
            a = batch_axes(mesh)
        if isinstance(a, str) and a not in mesh.axis_names:
            a = None
        if isinstance(a, tuple):
            a = tuple(n for n in a if n in mesh.axis_names) or None
        resolved.append(a)
    spec = fit_spec(P(*resolved), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
