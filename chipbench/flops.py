"""Operations the served model requires, from the configuration alone.

Required work, not executed work: a sparse projection costs 2 multiply-adds
per kept weight (k = round(size * (1 - sparsity)) per matrix, the pruning
rule), a dense one 2 per element, attention 4 * n_heads * head_dim per
position attended (scores and the weighted sum), and the logits head only
where a token is sampled. Bucket padding, group padding, the
compute-as-dense tile expansion and recomputation after preemption are not
required and do not count. A configuration's reference module may count
its own projections and attention (see ``chipbench/harness.py``).
"""

from __future__ import annotations

from typing import Dict

from chipbench import reference


def _kept_flops(shapes: Dict[str, tuple], sparse, s: float) -> float:
    total = 0.0
    for name, shape in shapes.items():
        if len(shape) != 2:
            continue
        size = shape[0] * shape[1]
        kept = (max(int(round(size * (1.0 - s))), 1)
                if name in sparse and s else size)
        total += 2.0 * kept
    return total


def projection_flops(m: Dict, ref=reference) -> float:
    """Per position, all layers: the (pruned) projection matmuls, by the
    configuration's reference module ``ref``."""
    if hasattr(ref, "projection_flops"):
        return ref.projection_flops(m)
    s = m.get("sparsity") or 0.0
    sparse = getattr(ref, "SPARSE", ())
    shapes = ref.layer_shapes(m)
    if isinstance(shapes, dict):
        return _kept_flops(shapes, sparse, s) * m["n_layers"]
    return sum(_kept_flops(layer, sparse, s) for layer in shapes)


def attention_flops(m: Dict, context: int, ref=reference) -> float:
    """One query position attending ``context`` positions, all layers."""
    if hasattr(ref, "attention_flops"):
        return ref.attention_flops(m, context)
    hd = m.get("d_head") or m["d_model"] // m["n_heads"]
    return 4.0 * m["n_heads"] * hd * context * m["n_layers"]


def head_flops(m: Dict) -> float:
    return 2.0 * m["vocab"] * m["d_model"]


def prefill_flops(m: Dict, prompt_len: int, ref=reference) -> float:
    """A whole prompt (causal contexts 1..P) and its one sampled token."""
    attn = attention_flops(m, 1, ref) * prompt_len * (prompt_len + 1) / 2
    return projection_flops(m, ref) * prompt_len + attn + head_flops(m)


def decode_flops(m: Dict, context: int, ref=reference) -> float:
    """One decoded position attending ``context`` positions."""
    return (projection_flops(m, ref) + attention_flops(m, context, ref)
            + head_flops(m))
