"""Weight pruning + sparsification tools (paper §3.1, §6.3.1, §5).

Implements the pruning principals the paper evaluates with, plus the weight
reformatting tool (dense checkpoint → Tiled-CSL), plus a beyond-paper
*tile-balanced* pruning mode that equalises per-tile nnz so the padded
Tiled-CSL format carries zero padding waste.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse_linear, tiled_csl


# ---------------------------------------------------------------------------
# importance scores
# ---------------------------------------------------------------------------

def magnitude_scores(w: jax.Array) -> jax.Array:
    """Magnitude pruning (paper §3.1): |w|."""
    return jnp.abs(w)


def taylor_scores(w: jax.Array, grad: jax.Array) -> jax.Array:
    """First-order Taylor importance (Molchanov et al., used in paper §6.3.1):
    |w * dL/dw| — the loss change of zeroing the weight, to first order."""
    return jnp.abs(w * grad)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def unstructured_mask(scores: jax.Array, sparsity: float) -> jax.Array:
    """Global top-(1-sparsity) mask over the whole matrix — unstructured."""
    if sparsity <= 0.0:
        return jnp.ones_like(scores, dtype=bool)
    k = int(round(scores.size * (1.0 - sparsity)))
    k = max(k, 1)
    thresh = jnp.sort(scores.reshape(-1))[-k]
    return scores >= thresh


def tile_balanced_mask(scores: jax.Array, sparsity: float,
                       m_tb: int = tiled_csl.DEFAULT_M_TB,
                       k_tb: int = tiled_csl.DEFAULT_K_TB) -> jax.Array:
    """Beyond-paper: keep exactly ceil((1-s)·m_tb·k_tb) top elements per tile.

    Still *unstructured within the tile* (any position allowed), but per-tile
    counts are equal. (The column-slotted Tiled-CSL layout pads to the
    fullest tile *column*, which this does not balance.) Accuracy impact is
    between global-unstructured and block-structured pruning; the paper's
    accuracy argument (element freedom) is preserved at tile granularity.
    """
    m, k = scores.shape
    if m % m_tb or k % k_tb:
        raise ValueError(f"shape {(m, k)} not tile-aligned")
    keep = max(int(np.ceil(m_tb * k_tb * (1.0 - sparsity))), 1)
    tiles = scores.reshape(m // m_tb, m_tb, k // k_tb, k_tb).transpose(0, 2, 1, 3)
    flat = tiles.reshape(m // m_tb, k // k_tb, m_tb * k_tb)
    thresh = jnp.sort(flat, axis=-1)[..., -keep][..., None]
    mask = (flat >= thresh)
    mask = mask.reshape(m // m_tb, k // k_tb, m_tb, k_tb).transpose(0, 2, 1, 3)
    return mask.reshape(m, k)


def prune(w: jax.Array, sparsity: float, *, method: str = "magnitude",
          grad: Optional[jax.Array] = None, balanced: bool = False) -> jax.Array:
    """Return the pruned (masked) dense weight."""
    scores = magnitude_scores(w) if method == "magnitude" else taylor_scores(w, grad)
    mask = (tile_balanced_mask(scores, sparsity) if balanced
            else unstructured_mask(scores, sparsity))
    return jnp.where(mask, w, jnp.zeros_like(w))


# ---------------------------------------------------------------------------
# layerwise sparsity plans (paper §6.3.1: first/last quarter MLP kept dense)
# ---------------------------------------------------------------------------

def opt_style_plan(n_layers: int, sparsity: float) -> Dict[int, float]:
    """The paper's OPT-30B recipe: keep the front quarter and last quarter
    feed-forward *input* layers dense; prune the rest at ``sparsity``."""
    plan = {}
    q = n_layers // 4
    for layer in range(n_layers):
        plan[layer] = 0.0 if (layer < q or layer >= n_layers - q) else sparsity
    return plan


# ---------------------------------------------------------------------------
# weight reformatting tool (paper §5): dense params -> Tiled-CSL params
# ---------------------------------------------------------------------------

def _pad_to_tiles(w: np.ndarray, m_tb: int, k_tb: int) -> np.ndarray:
    m, k = w.shape
    mp = -(-m // m_tb) * m_tb
    kp = -(-k // k_tb) * k_tb
    if (mp, kp) == (m, k):
        return w
    out = np.zeros((mp, kp), w.dtype)
    out[:m, :k] = w
    return out


def sparsify_matrix(w: jax.Array, sparsity: float, *,
                    method: str = "magnitude", balanced: bool = False,
                    m_tb: int = tiled_csl.DEFAULT_M_TB,
                    k_tb: int = tiled_csl.DEFAULT_K_TB
                    ) -> tiled_csl.TiledCSL:
    """Prune a dense [M, K] weight and encode it as Tiled-CSL."""
    wp = np.asarray(jax.device_get(
        prune(jnp.asarray(w, jnp.float32), sparsity, method=method,
              balanced=balanced)))
    wp = _pad_to_tiles(wp, m_tb, k_tb)
    return tiled_csl.encode(wp, m_tb=m_tb, k_tb=k_tb)


def _pregroupable(ws) -> bool:
    """Same-shape TiledCSLs (plain or sharing one scan stack) → one group,
    subject to the same max_nnz balance cap as call-time grouping (a group
    shares one pad target; wildly uneven members would bloat the stream)."""
    if not all(isinstance(w, tiled_csl.TiledCSL) for w in ws):
        return False
    key = (ws[0].shape, ws[0].m_tb, ws[0].k_tb, ws[0].words.ndim,
           ws[0].words.shape[0] if ws[0].words.ndim == 5 else None)
    return all((w.shape, w.m_tb, w.k_tb, w.words.ndim,
                w.words.shape[0] if w.words.ndim == 5 else None) == key
               for w in ws) and sparse_linear.balanced_group(ws)


def group_projections(params: Any) -> Any:
    """Pre-group same-shape Tiled-CSL projection pairs at reformat time.

    Walks a (possibly scan-stacked) params tree and rewrites, in place of
    the per-weight encodings:

    * ``{gate: {w}, up: {w}}``     → ``{gate_up: {w: grouped G=2}}``
      (SwiGLU; consumed by ``layers.swiglu_mlp`` via the ``silu_mul``
      binary epilogue)
    * ``{wq: {w}, wk: {w}, wv: {w}}`` → ``{wqkv: {w: grouped G=3}, ...}``
      (QKV; biases stay on the original dicts — only the weights group)

    whenever the members share one padded shape and tile geometry
    (scan-stacked leaves group along axis 1; ``lax.scan`` slices the layer
    axis back off). This is the production counterpart of
    ``sparse_linear.linear_grouped``'s call-time stacking: grouping happens
    ONCE here, so the jitted serving step streams the grouped words with no
    per-step pad+stack traffic (DESIGN.md §8). Dense or shape-mismatched
    projections are left untouched.
    """
    if not isinstance(params, dict):
        if isinstance(params, (list, tuple)):
            return type(params)(group_projections(p) for p in params)
        return params
    out = {k: group_projections(v) for k, v in params.items()}

    def w_of(name):
        sub = out.get(name)
        return sub.get("w") if isinstance(sub, dict) else None

    gate_up = [w_of("gate"), w_of("up")]
    if all(w is not None for w in gate_up) and _pregroupable(gate_up):
        out["gate_up"] = {"w": tiled_csl.group_stack(gate_up)}
        del out["gate"]["w"], out["up"]["w"]
        for name in ("gate", "up"):
            if not out[name]:
                del out[name]
    qkv = [w_of(n) for n in ("wq", "wk", "wv")]
    if all(w is not None for w in qkv) and _pregroupable(qkv):
        out["wqkv"] = {"w": tiled_csl.group_stack(qkv)}
        for name in ("wq", "wk", "wv"):
            del out[name]["w"]
            if not out[name]:
                del out[name]
    return out


def sparsify_params(params: Any, sparsity: float,
                    should_sparsify: Callable[[str], bool],
                    *, method: str = "magnitude", balanced: bool = False
                    ) -> Any:
    """Walk a params pytree; convert selected 2-D weights to Tiled-CSL.

    ``should_sparsify(path_str)`` decides per leaf (e.g. keep router /
    embedding / norm weights dense). Stacked scan weights [L, M, K] are
    encoded per layer, padded to one shared slot count and re-stacked.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out_leaves = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if (hasattr(leaf, "ndim") and leaf.ndim in (2, 3)
                and should_sparsify(name)):
            if leaf.ndim == 2:
                out_leaves.append(sparsify_matrix(
                    leaf, sparsity, method=method, balanced=balanced))
            else:  # stacked [L, M, K] scan weights
                per_layer = [sparsify_matrix(
                    leaf[i], sparsity, method=method, balanced=balanced)
                    for i in range(leaf.shape[0])]
                mx = max(t.slots for t in per_layer)
                per_layer = [tiled_csl.pad_slots(t, mx) for t in per_layer]
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
                out_leaves.append(stacked)
        else:
            out_leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)
