"""Tiled-CSL sparse format (Flash-LLM §4.3), adapted for TPU.

The paper's format stores, per (M_TB x K_TB) weight tile, a variable-length
list of 32-bit words, each packing a 16-bit value with a 16-bit intra-tile
location, plus a ``TileOffsets`` array delimiting each tile's span in the flat
``NonZeros`` stream.

TPU adaptation — the *column-slotted* layout (see DESIGN.md §2):

* values are bf16 (TPU-native 16-bit float) instead of fp16;
* each tile is a ``[slots, k_tb]`` block of words: lane ``c`` holds tile
  column ``c``, and slot ``r`` of that lane holds the column's r-th
  non-zero as ``(bf16 value << 16) | row-in-tile``. The kernel rebuilds the
  dense tile by a compare-select per slot (``kernels/spmm.py``), which
  Mosaic lowers; a flat per-tile word list would need a scatter, which it
  does not;
* Pallas block specs need static shapes, so every column of every tile
  carries the same ``slots`` count: the matrix's largest per-tile column
  count, rounded up to ``SLOT_QUANTUM`` (the uint32 sublane tile, so a
  tile's block is (8, 128)-aligned in VMEM and in HBM). Unused slots hold
  ``PAD_WORD`` = (+0.0 | row 0xFFFF): no tile has that row, so the
  expansion never selects it.

The format is sharding-transparent: encoding is generated per TP shard, and
tiles never cross shard boundaries (shards are tile-aligned by construction).

Grouped encodings (:func:`encode_group` / :func:`group_stack`) stack G
same-shape matrices on a leading group axis of ``words``/``nnz`` with one
shared ``slots`` count, so the grouped LSCD kernel can produce all G outputs
in a single launch that streams the activation matrix once (DESIGN.md §8).
Per-layer scan stacks (``pruning.sparsify_params`` on [L, M, K] leaves) use
the same representation — a group is just "independent same-shape matrices
sharing one pad target".
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts

# Default tile geometry: MXU native 128x128 (paper: 128x64 for 128 threads).
DEFAULT_M_TB = 128
DEFAULT_K_TB = 128
# Slots per column are padded to a multiple of the uint32 sublane tile, so
# every tile block is (8, 128)-aligned and the kernel reads slots in whole
# 8-row slabs.
SLOT_QUANTUM = 8
# Row field value that marks an unused slot (no tile has 65535 rows).
PAD_ROW = 0xFFFF
PAD_WORD = PAD_ROW   # (+0.0 << 16) | PAD_ROW


@dataclasses.dataclass(frozen=True)
class TiledCSL:
    """A sparse matrix of logical shape ``(m, k)`` in column-slotted
    Tiled-CSL format.

    Attributes:
      words:  uint32[mt, kt, slots, k_tb] — per tile, lane ``c`` lists
              column ``c``'s non-zeros as (bf16 value | 16-bit row) words,
              padded with ``PAD_WORD``. A *grouped* encoding (see
              :func:`encode_group`) carries a leading group axis:
              uint32[G, mt, kt, slots, k_tb] — G same-shape matrices
              sharing one ``slots`` count so a single kernel launch can
              stream all G weight streams against one activation block.
      nnz:    int32[mt, kt] (or int32[G, mt, kt]) — true non-zero count per
              tile (<= max_nnz).
      slabs:  int32, shaped as ``nnz`` — per tile, the 8-slot slabs
              (``SLOT_QUANTUM``) that hold a real word in any of its
              columns: ceil(fullest column count / 8), 0 exactly when the
              tile is empty. Every slab past it is all ``PAD_WORD``, so the
              kernel's expansion stops there.
      shape:  logical dense shape (m, k) of *each* matrix;
              m % m_tb == 0 and k % k_tb == 0.
      m_tb, k_tb: tile geometry.
      dtype:  dtype of the dense reconstruction (bf16 or f32 source).
    """

    words: jax.Array
    nnz: jax.Array
    slabs: jax.Array
    shape: Tuple[int, int]
    m_tb: int
    k_tb: int
    dtype: jnp.dtype

    # ---- derived -----------------------------------------------------------
    @property
    def slots(self) -> int:
        """Word slots per tile column (padding included)."""
        return int(self.words.shape[-2])

    @property
    def max_nnz(self) -> int:
        """Words per tile, padding included — what the kernel DMAs per
        tile (``slots * k_tb``)."""
        return self.slots * int(self.words.shape[-1])

    @property
    def group(self) -> Optional[int]:
        """Number of grouped matrices, or None for a plain encoding.

        Caveat: grouped-ness is inferred from ``words.ndim == 5``, which is
        the SAME layout scan/expert stacks use ([L, ...] / [E, ...] leaves
        from ``pruning.sparsify_params``) — "G independent same-shape
        matrices sharing one pad target" is one representation. Callers
        that hold a *stack* must slice the lead axis (scan does; MoE vmaps)
        before treating a leaf as a projection group; the grouped ops
        cannot tell a stack from a group on their own."""
        return int(self.words.shape[0]) if self.words.ndim == 5 else None

    @property
    def grid(self) -> Tuple[int, int]:
        return (self.shape[0] // self.m_tb, self.shape[1] // self.k_tb)

    @property
    def n_nonzero(self) -> int:
        return int(np.asarray(jax.device_get(self.nnz)).sum())

    @property
    def nbytes_sparse(self) -> int:
        """Bytes actually streamed by the LSCD kernel for A (incl. padding):
        the words and the per-tile slab counts it prefetches."""
        return int(self.words.size * 4) + int(self.slabs.size * 4)

    @property
    def nbytes_dense(self) -> int:
        """Bytes of the dense bf16 counterpart — counting every matrix in
        the leading word axes (group and/or scan-stack), to match what
        ``nbytes_sparse`` streams."""
        n_mats = int(np.prod(self.words.shape[:-4], dtype=np.int64))
        return int(np.prod(self.shape)) * 2 * n_mats

    @property
    def pad_overhead(self) -> float:
        """Fraction of streamed words that are padding (imbalance waste)."""
        total_words = self.words.size
        real = self.n_nonzero
        return 1.0 - real / max(total_words, 1)

    @property
    def bytes_per_nonzero(self) -> float:
        """Streamed A bytes per true non-zero, padding included (dense
        bf16 costs ``2 / density``)."""
        return self.nbytes_sparse / max(self.n_nonzero, 1)


def _tcsl_flatten_with_keys(t: TiledCSL):
    return (((jax.tree_util.GetAttrKey("words"), t.words),
             (jax.tree_util.GetAttrKey("nnz"), t.nnz),
             (jax.tree_util.GetAttrKey("slabs"), t.slabs)),
            (t.shape, t.m_tb, t.k_tb, t.dtype))


def _tcsl_unflatten(aux, children):
    words, nnz, slabs = children
    shape, m_tb, k_tb, dtype = aux
    return TiledCSL(words=words, nnz=nnz, slabs=slabs, shape=shape,
                    m_tb=m_tb, k_tb=k_tb, dtype=dtype)


jax.tree_util.register_pytree_with_keys(
    TiledCSL, _tcsl_flatten_with_keys, _tcsl_unflatten)


# ---------------------------------------------------------------------------
# packing helpers
# ---------------------------------------------------------------------------

def pack_words(values: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Pack bf16 values and 16-bit in-tile rows into uint32 words.

    word = (bf16_bits << 16) | row — the paper's (val, loc) 32-bit layout,
    with the column implied by the word's lane.
    """
    v = np.ascontiguousarray(values, dtype=np.float32)
    # f32 -> bf16 bits: round-to-nearest-even on the high 16 bits.
    bits32 = v.view(np.uint32)
    rounded = bits32 + np.uint32(0x7FFF) + ((bits32 >> np.uint32(16)) & np.uint32(1))
    bf16_bits = (rounded >> np.uint32(16)).astype(np.uint32)
    row = np.asarray(rows, dtype=np.uint32) & np.uint32(0xFFFF)
    return (bf16_bits << np.uint32(16)) | row


def unpack_words(words: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`pack_words` → (f32 values, int32 rows)."""
    w = np.ascontiguousarray(words, dtype=np.uint32)
    vals = (w & np.uint32(0xFFFF0000)).view(np.float32)
    rows = (w & np.uint32(0xFFFF)).astype(np.int32)
    return vals, rows


def pad_slots(t: TiledCSL, slots: int) -> TiledCSL:
    """Pad ``t`` to ``slots`` word slots per column with ``PAD_WORD``.

    jit-safe (pure pad). Used to give a scan stack or a projection group
    one shared slot count. Padding adds only pad slabs, so ``slabs`` stays."""
    if slots < t.slots:
        raise ValueError(f"cannot pad {t.slots} slots down to {slots}")
    if slots == t.slots:
        return t
    widths = ((0, 0),) * (t.words.ndim - 2) + ((0, slots - t.slots), (0, 0))
    words = jnp.pad(t.words, widths, constant_values=PAD_WORD)
    return TiledCSL(words=words, nnz=t.nnz, slabs=t.slabs, shape=t.shape,
                    m_tb=t.m_tb, k_tb=t.k_tb, dtype=t.dtype)


# ---------------------------------------------------------------------------
# encode / decode
# ---------------------------------------------------------------------------

def encode(dense: np.ndarray | jax.Array,
           m_tb: int = DEFAULT_M_TB,
           k_tb: int = DEFAULT_K_TB) -> TiledCSL:
    """Encode a dense (m, k) matrix into column-slotted Tiled-CSL.

    ``m`` and ``k`` must be multiples of the tile geometry (pad upstream —
    ``pruning.sparsify_matrix`` does). Zero elements are dropped; everything
    else is kept with bf16-rounded values. Within a column, slots run in
    ascending row order.
    """
    a = np.asarray(jax.device_get(dense))
    orig_dtype = jnp.bfloat16 if a.dtype == jnp.bfloat16 else jnp.dtype(str(a.dtype))
    a = a.astype(np.float32)
    m, k = a.shape
    if m % m_tb or k % k_tb:
        raise ValueError(f"shape {(m, k)} not tile-aligned to ({m_tb},{k_tb})")
    # The packed word carries a 16-bit in-tile row whose all-ones value
    # marks padding. Shared predicate with the static checker (rule
    # KC-LOC, DESIGN.md §12) so encoding and checker cannot disagree.
    contracts.require_tile_loc(m_tb)
    mt, kt = m // m_tb, k // k_tb

    mask = a != 0
    # Running count down each column inside its row of tiles: an entry's
    # slot is the number of non-zeros above it in its tile column.
    cum = np.cumsum(mask.reshape(mt, m_tb, k), axis=1, dtype=np.int32)
    col_counts = cum[:, -1, :]                                # [mt, k]
    slots = max(int(col_counts.max()) if col_counts.size else 0, 1)
    slots = -(-slots // SLOT_QUANTUM) * SLOT_QUANTUM

    words = np.full((mt, kt, slots, k_tb), PAD_WORD, np.uint32)
    rr, cc = np.nonzero(mask)
    if rr.size:
        slot = cum.reshape(m, k)[rr, cc] - 1
        words[rr // m_tb, cc // k_tb, slot, cc % k_tb] = pack_words(
            a[rr, cc], rr % m_tb)
    tile_cols = col_counts.reshape(mt, kt, k_tb)
    nnz = tile_cols.sum(axis=-1, dtype=np.int32)
    slabs = -(-tile_cols.max(axis=-1) // SLOT_QUANTUM)

    return TiledCSL(
        words=jnp.asarray(words),
        nnz=jnp.asarray(nnz),
        slabs=jnp.asarray(slabs, jnp.int32),
        shape=(m, k),
        m_tb=m_tb,
        k_tb=k_tb,
        dtype=orig_dtype,
    )


def encode_group(weights: Sequence[np.ndarray | jax.Array],
                 m_tb: int = DEFAULT_M_TB,
                 k_tb: int = DEFAULT_K_TB) -> TiledCSL:
    """Encode G same-shape (m, k) matrices as one grouped Tiled-CSL.

    The result stacks per-weight ``words``/``nnz`` along a leading group
    axis and shares one ``slots`` count (the max over the group, re-padded
    with ``PAD_WORD``), so the grouped LSCD kernel can stream every weight
    with a single static block shape while B is streamed once.
    Tiles stay per-weight — grouping changes layout, not tiling or math.
    """
    if not weights:
        raise ValueError("encode_group needs at least one weight")
    ts = [encode(w, m_tb=m_tb, k_tb=k_tb) for w in weights]
    shapes = {t.shape for t in ts}
    if len(shapes) != 1:
        raise ValueError(f"grouped weights must share one shape, got {shapes}")
    return group_stack(ts)


def group_stack(ts: Sequence[TiledCSL]) -> TiledCSL:
    """Stack already-encoded same-shape TiledCSLs into a grouped TiledCSL.

    Pads every member to the group's largest ``slots`` (:func:`pad_slots`)
    and stacks ``words``/``nnz``/``slabs``. jit-safe: pure pad/stack,
    usable at trace time on weights captured as arguments — though the
    production path pre-groups once at weight-reformat time
    (:func:`encode_group` / ``pruning.group_projections``) so the serving
    hot path carries no restacking traffic.

    Members that are themselves layer-stacked scan leaves (words
    ``[L, mt, kt, slots, k_tb]``, as produced by ``pruning.sparsify_params``
    on ``[L, M, K]`` weights) stack on axis 1 → words
    ``[L, G, mt, kt, slots, k_tb]``; ``lax.scan`` slices the leading L back
    off, yielding a per-layer grouped TiledCSL inside the scan body.
    """
    ts = list(ts)
    if not ts:
        raise ValueError("group_stack needs at least one TiledCSL")
    lead = ts[0].words.ndim - 4
    if lead not in (0, 1):
        raise ValueError("group_stack members must be plain or scan-stacked "
                         f"encodings, got words rank {ts[0].words.ndim}")
    for t in ts:
        if t.words.ndim != ts[0].words.ndim or (
                lead and t.words.shape[0] != ts[0].words.shape[0]):
            raise ValueError("group_stack members must share the scan stack")
        if (t.shape, t.m_tb, t.k_tb) != (ts[0].shape, ts[0].m_tb, ts[0].k_tb):
            raise ValueError("group_stack members must share shape and tile "
                             f"geometry, got {[(t.shape, t.m_tb, t.k_tb) for t in ts]}")
    mx = max(t.slots for t in ts)
    words = jnp.stack([pad_slots(t, mx).words for t in ts], axis=lead)
    nnz = jnp.stack([t.nnz for t in ts], axis=lead)
    slabs = jnp.stack([t.slabs for t in ts], axis=lead)
    return TiledCSL(words=words, nnz=nnz, slabs=slabs, shape=ts[0].shape,
                    m_tb=ts[0].m_tb, k_tb=ts[0].k_tb, dtype=ts[0].dtype)


def group_slice(t: TiledCSL, g: int) -> TiledCSL:
    """Member ``g`` of a grouped TiledCSL as a plain encoding."""
    if t.group is None:
        raise ValueError("group_slice needs a grouped TiledCSL")
    return TiledCSL(words=t.words[g], nnz=t.nnz[g], slabs=t.slabs[g],
                    shape=t.shape, m_tb=t.m_tb, k_tb=t.k_tb, dtype=t.dtype)


def slab_share(params) -> Optional[float]:
    """Share (%) of the full compare-select expansion that the LSCD kernels
    run over every Tiled-CSL leaf of ``params``: the tiles' ``slabs`` over
    ``slots / SLOT_QUANTUM`` slabs a tile. None without a Tiled-CSL leaf."""
    leaves = [x for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, TiledCSL))
        if isinstance(x, TiledCSL)]
    full = sum(x.slabs.size * (x.slots // SLOT_QUANTUM) for x in leaves)
    if not full:
        return None
    run = sum(int(np.asarray(jax.device_get(x.slabs), np.int64).sum())
              for x in leaves)
    return 100.0 * run / full


def decode(t: TiledCSL) -> np.ndarray:
    """Reconstruct the dense f32 matrix (numpy; the test/debug inverse).

    Grouped encodings decode to ``[G, m, k]``.
    """
    if t.group is not None:
        return np.stack([decode(group_slice(t, g)) for g in range(t.group)])
    vals, rows = unpack_words(np.asarray(jax.device_get(t.words)))
    ti, tj, _, c = np.nonzero(rows != PAD_ROW)
    r = rows[rows != PAD_ROW]
    out = np.zeros(t.shape, np.float32)
    out[ti * t.m_tb + r, tj * t.k_tb + c] = vals[rows != PAD_ROW]
    return out


def decode_jax(t: TiledCSL) -> jax.Array:
    """Pure-JAX dense reconstruction (scatter-add), jit/vjp-friendly.

    This is the ``sparse_xla`` full-model path: XLA materialises the dense
    weight in HBM (the round-trip penalty the fused Pallas kernel removes).
    Grouped encodings decode to ``[G, m, k]`` (vmapped over the group axis).
    """
    if t.group is not None:
        return jax.vmap(lambda w, n, s: decode_jax(TiledCSL(
            words=w, nnz=n, slabs=s, shape=t.shape, m_tb=t.m_tb,
            k_tb=t.k_tb, dtype=t.dtype)))(t.words, t.nnz, t.slabs)
    words = t.words.astype(jnp.uint32)
    real = (words & 0xFFFF) != PAD_ROW
    # Padding slots add +0.0 at their tile's (0, column): exact no-ops.
    vals = jnp.where(real, jax.lax.bitcast_convert_type(
        words & jnp.uint32(0xFFFF0000), jnp.float32), 0.0)
    in_r = jnp.where(real, words & 0xFFFF, 0).astype(jnp.int32)
    shp = words.shape
    ti = jax.lax.broadcasted_iota(jnp.int32, shp, 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, shp, 1)
    in_c = jax.lax.broadcasted_iota(jnp.int32, shp, 3)
    rows = (ti * t.m_tb + in_r).reshape(-1)
    cols = (tj * t.k_tb + in_c).reshape(-1)
    out = jnp.zeros((t.shape[0] * t.shape[1],), jnp.float32)
    out = out.at[rows * t.shape[1] + cols].add(vals.reshape(-1))
    return out.reshape(t.shape).astype(t.dtype)
