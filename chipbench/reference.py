"""Plain float32 reference of the served decoder, and its float8 control.

Written from the configuration alone: it imports nothing of the program and
takes nothing that the program made. Its weights come from
``chipbench.weights`` (the same seeded draw the served model was built
from), pruned here by the rule the configuration states: per matrix, keep
every weight whose magnitude is at least the k-th largest, k =
round(size * (1 - sparsity)). It runs layer by layer over a batch of whole
sequences, so one layer's weights are on the device at a time.

Architecture (as the program implements it; see the configuration file's
``deviations``): pre-norm residual blocks; RMSNorm (eps 1e-6) or LayerNorm
(eps 1e-5); q/k/v projections with optional bias; rotate-half RoPE on the
whole head; causal grouped-query softmax attention scaled by head_dim^-0.5;
output projection without bias; SwiGLU MLP (silu(gate) * up, then down) or
a GELU MLP (tanh approximation, with biases); final norm; logits against the
tied embedding or an untied head.

``gaps`` compares served tokens with the reference: at every served position
the gap is the reference's best logit minus its logit of the served token (0
where they agree). With ``control=True`` it also runs the float8 control (per-
tensor e4m3 weights, per-row e4m3 activations, in every projection and the
logits head) and reports the gap of the token the control puts first.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
SPARSE = ("attn.wq.w", "attn.wk.w", "attn.wv.w", "attn.wo.w",
          "mlp.gate.w", "mlp.up.w", "mlp.down.w")
FP8_MAX = 448.0
# Positions of logits the readout holds at a time.
CHUNK = 128


def layer_shapes(m: Dict) -> Dict[str, tuple]:
    """Per-layer leaf shapes ([out, in] for projections)."""
    d, hd = m["d_model"], m.get("d_head") or m["d_model"] // m["n_heads"]
    h, kv, f = m["n_heads"], m["n_kv"], m["d_ff"]
    s = {"attn.wq.w": (h * hd, d), "attn.wk.w": (kv * hd, d),
         "attn.wv.w": (kv * hd, d), "attn.wo.w": (d, h * hd)}
    if m.get("qkv_bias"):
        s.update({"attn.wq.b": (h * hd,), "attn.wk.b": (kv * hd,),
                  "attn.wv.b": (kv * hd,)})
    if m["mlp_kind"] == "swiglu":
        s.update({"mlp.gate.w": (f, d), "mlp.up.w": (f, d),
                  "mlp.down.w": (d, f)})
    else:
        s.update({"mlp.up.w": (f, d), "mlp.down.w": (d, f)})
        if m.get("mlp_bias"):
            s.update({"mlp.up.b": (f,), "mlp.down.b": (d,)})
    for norm in ("pre_norm", "mlp_norm"):
        s[f"{norm}.scale"] = (d,)
        if m["norm_kind"] == "layernorm":
            s[f"{norm}.bias"] = (d,)
    return s


def prune(w: jax.Array, sparsity: float) -> jax.Array:
    """Zero all but the weights whose magnitude reaches the k-th largest.

    The threshold is found by bisection over the bf16 magnitudes' bit
    patterns (monotone for non-negative floats): the largest t with
    count(|w| >= t) >= k is exactly the k-th largest magnitude.
    """
    k = max(int(round(w.size * (1.0 - sparsity))), 1)
    mag = jax.lax.bitcast_convert_type(
        jnp.abs(w.astype(jnp.bfloat16)), jnp.uint16).astype(jnp.int32)

    def halve(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        ok = jnp.sum(mag >= mid, dtype=jnp.int32) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 16, halve, (jnp.int32(0), jnp.int32(0x8000)))
    return jnp.where(mag >= lo, w, jnp.zeros_like(w))


def _layer_weights(m: Dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    out = {}
    for name, shape in layer_shapes(m).items():
        w = weights.leaf(seed, name, layer, shape)
        if name in SPARSE and m.get("sparsity"):
            w = prune(w, m["sparsity"])
        out[name] = w.astype(jnp.float32)
    return out


def _q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 under a max-abs scale (per tensor or per row)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(scale > 0, scale / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dense(x, w, b=None, fp8=False):
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    y = jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)
    return y if b is None else y + b


def _norm(m, p, prefix, x):
    if m["norm_kind"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * p[f"{prefix}.scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    return y * p[f"{prefix}.scale"] + p[f"{prefix}.bias"]


def _rope(x, theta):
    """x: [B, T, H, D]; positions 0..T-1; rotate-half pairing."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(m, p, x, fp8):
    B, T, d = x.shape
    h, kv = m["n_heads"], m["n_kv"]
    hd = m.get("d_head") or d // h
    a = _norm(m, p, "pre_norm", x)
    q = _dense(a, p["attn.wq.w"], p.get("attn.wq.b"), fp8)
    k = _dense(a, p["attn.wk.w"], p.get("attn.wk.b"), fp8)
    v = _dense(a, p["attn.wv.w"], p.get("attn.wv.b"), fp8)
    theta = m.get("rope_theta", 10000.0)
    q = _rope(q.reshape(B, T, h, hd), theta).reshape(B, T, kv, h // kv, hd)
    k = _rope(k.reshape(B, T, kv, hd), theta)
    v = v.reshape(B, T, kv, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, T, h * hd)
    x = x + _dense(o, p["attn.wo.w"], None, fp8)
    a = _norm(m, p, "mlp_norm", x)
    if m["mlp_kind"] == "swiglu":
        g = _dense(a, p["mlp.gate.w"], None, fp8)
        hid = jax.nn.sigmoid(g) * g * _dense(a, p["mlp.up.w"], None, fp8)
        return x + _dense(hid, p["mlp.down.w"], None, fp8)
    hid = _gelu_tanh(_dense(a, p["mlp.up.w"], p.get("mlp.up.b"), fp8))
    return x + _dense(hid, p["mlp.down.w"], p.get("mlp.down.b"), fp8)


def _readout(m, final, head, x_ref, x_ctl, tgt, control):
    """Gaps at the compared positions (``tgt >= 0``), ``CHUNK`` positions
    of logits at a time: [R, T] reference gap, agreement, control gap."""
    R, T, _ = x_ref.shape

    def chunk(args):
        xr, xc, t = args
        ref = _dense(_norm(m, final, "final_norm", xr), head)
        best = jnp.max(ref, -1)
        valid = t >= 0

        def gap_of(tok):
            at = jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
            return jnp.where(valid, best - at, 0.0)

        agree = valid & (jnp.argmax(ref, -1) == t)
        if not control:
            return gap_of(jnp.maximum(t, 0)), agree, jnp.zeros_like(best)
        ctl = _dense(_norm(m, final, "final_norm", xc), head, fp8=True)
        return (gap_of(jnp.maximum(t, 0)), agree,
                gap_of(jnp.argmax(ctl, -1)))

    def split(a):
        return jnp.moveaxis(a.reshape(R, T // CHUNK, CHUNK, *a.shape[2:]),
                            1, 0)

    outs = jax.lax.map(chunk, (split(x_ref), split(x_ctl), split(tgt)))
    return [jnp.moveaxis(o, 0, 1).reshape(R, T) for o in outs]


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, seed: int):
    """The reference's compiled pieces for one model and weight seed, with
    fixed shapes so that every run of a cell reuses them: one layer's
    weights (the layer index traced), a block of rows through one layer
    (reference and control), the embedding and head, and the readout."""
    m = json.loads(model_json)
    norms = ("scale", "bias") if m["norm_kind"] == "layernorm" else ("scale",)

    def outer():
        table = weights.leaf(seed, "embed.table", 0,
                             (m["vocab"], m["d_model"])).astype(jnp.float32)
        head = table if m.get("tie_embeddings") else weights.leaf(
            seed, "lm_head.w", 0, (m["vocab"], m["d_model"])
        ).astype(jnp.float32)
        final = {f"final_norm.{n}": weights.leaf(
            seed, f"final_norm.{n}", 0, (m["d_model"],)).astype(jnp.float32)
            for n in norms}
        return table, final, head

    return (jax.jit(outer),
            jax.jit(lambda layer: _layer_weights(m, seed, layer)),
            {fp8: jax.jit(functools.partial(_block, m, fp8=fp8))
             for fp8 in (False, True)},
            jax.jit(functools.partial(_readout, m),
                    static_argnames=("control",)))


def gaps(m: Dict, seed: int, prompts: Sequence[np.ndarray],
         served: Sequence[Sequence[int]], *, control: bool = False,
         rows: int = 4) -> List[Dict[str, float]]:
    """Per sequence: the widest gap of a served token below the reference's
    best logit, and with ``control`` the widest gap of the control's first
    choice. ``rows`` sequences go through a layer at a time; sequences are
    padded to a power of two (at least ``CHUNK``) and their number to a
    multiple of ``rows``, so that the compiled pieces are reused."""
    outer, layer_weights, block, readout = _programs(
        json.dumps(m, sort_keys=True), seed)
    longest = max(len(p) + len(s) - 1 for p, s in zip(prompts, served))
    T = max(CHUNK, 1 << (longest - 1).bit_length())
    R = -(-len(prompts) // rows) * rows
    toks = np.zeros((R, T), np.int32)
    tgt = np.full((R, T), -1, np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([np.asarray(p), np.asarray(s[:-1])])
        toks[i, :len(seq)] = seq
        tgt[i, len(p) - 1:len(p) - 1 + len(s)] = s
    with jax.default_matmul_precision("highest"):
        table, final, head = outer()
        x = jnp.take(table, jnp.asarray(toks), axis=0)
        del table
        paths = [x, x] if control else [x]
        for layer in range(m["n_layers"]):
            p = layer_weights(jnp.int32(layer))
            paths = [jnp.concatenate([block[k == 1](p, h[i:i + rows])
                                      for i in range(0, R, rows)])
                     for k, h in enumerate(paths)]
            del p
        gap, agree, ctl = (np.asarray(a) for a in readout(
            final, head, paths[0], paths[-1], jnp.asarray(tgt),
            control=control))
    out = []
    for i, s in enumerate(served):
        row = {"gap": float(gap[i].max()), "tokens": len(s),
               "agree": int(agree[i].sum())}
        if control:
            row["control_gap"] = float(ctl[i].max())
        out.append(row)
    return out
