"""Operations the served model requires, from the configuration alone.

Required work, not executed work: a sparse projection costs 2 multiply-adds
per kept weight (k = round(size * (1 - sparsity)) per matrix, the pruning
rule), a dense one 2 per element, attention 4 * n_heads * head_dim per
position attended (scores and the weighted sum), and the logits head only
where a token is sampled. Bucket padding, group padding, the
compute-as-dense tile expansion and recomputation after preemption are not
required and do not count.
"""

from __future__ import annotations

from typing import Dict

from chipbench import reference


def projection_flops(m: Dict) -> float:
    """Per position, all layers: the (pruned) projection matmuls."""
    s = m.get("sparsity") or 0.0
    per_layer = 0.0
    for name, shape in reference.layer_shapes(m).items():
        if len(shape) != 2:
            continue
        size = shape[0] * shape[1]
        kept = (max(int(round(size * (1.0 - s))), 1)
                if name in reference.SPARSE and s else size)
        per_layer += 2.0 * kept
    return per_layer * m["n_layers"]


def attention_flops(m: Dict, context: int) -> float:
    """One query position attending ``context`` positions, all layers."""
    hd = m.get("d_head") or m["d_model"] // m["n_heads"]
    return 4.0 * m["n_heads"] * hd * context * m["n_layers"]


def head_flops(m: Dict) -> float:
    return 2.0 * m["vocab"] * m["d_model"]


def prefill_flops(m: Dict, prompt_len: int) -> float:
    """A whole prompt (causal contexts 1..P) and its one sampled token."""
    attn = attention_flops(m, 1) * prompt_len * (prompt_len + 1) / 2
    return projection_flops(m) * prompt_len + attn + head_flops(m)


def decode_flops(m: Dict, context: int) -> float:
    """One decoded position attending ``context`` positions."""
    return projection_flops(m) + attention_flops(m, context) + head_flops(m)
