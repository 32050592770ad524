"""Seeded synthetic weights, shared by the served model and the reference.

Every leaf has a name (``embed.table``, ``attn.wq.w``, ``pre_norm.scale``,
...) and is drawn from ``(weight_seed, name, layer)`` alone. The value is a
sum of four uniform random bytes (a bounded bell, integer arithmetic) times
one constant, rounded once to the served dtype. So any process that asks for
a leaf gets the same bits, whether one jitted call makes the whole tree for
the program or the reference makes one layer at a time.

This module imports nothing of the program under test.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

# Standard deviation of a sum of four independent uniform bytes.
_BELL_STD = (4 * (256 ** 2 - 1) / 12) ** 0.5
_BELL_MEAN = 510

# (mean, std) of each kind of leaf. Projections use 1/sqrt(fan_in).
EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_SCALE = (1.0, 0.1)
NORM_BIAS_STD = 0.02


def _key(seed: int, name: str, layer: int) -> jax.Array:
    tag = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4],
                         "little") & 0x7FFFFFFF
    k = jax.random.fold_in(jax.random.key(seed % (2 ** 32)), tag)
    return jax.random.fold_in(k, layer)


def _moments(name: str, shape) -> tuple:
    field = name.rsplit(".", 1)[-1]
    if name == "embed.table":
        return 0.0, EMBED_STD
    if field == "w":
        return 0.0, float(shape[-1]) ** -0.5
    if "norm" in name:
        return (NORM_SCALE if field == "scale" else (0.0, NORM_BIAS_STD))
    if field == "b":
        return 0.0, BIAS_STD
    raise ValueError(f"no weight rule for leaf {name!r}")


def leaf(seed: int, name: str, layer: int, shape, dtype=jnp.bfloat16
         ) -> jax.Array:
    """One leaf of one layer (``layer`` 0 for leaves outside the stack)."""
    mean, std = _moments(name, shape)
    step = std / _BELL_STD
    offset = int(round(mean / step)) if mean else 0
    bits = jax.random.bits(_key(seed, name, layer), tuple(shape), jnp.uint32)
    total = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32)
                for i in range(4))
    centred = total - _BELL_MEAN + offset
    return (centred.astype(jnp.float32) * jnp.float32(step)).astype(dtype)
