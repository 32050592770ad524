"""Tiled-CSL format: roundtrip, column-slotted layout invariants, padding
accounting, the per-tile slab counts.

Deterministic property sweeps (seeded grids over the same space the old
hypothesis strategies drew from) + targeted unit tests.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.core import pruning, tiled_csl


def _random_sparse(rng, m, k, sparsity):
    a = rng.standard_normal((m, k), dtype=np.float32)
    a[rng.random((m, k)) < sparsity] = 0.0
    return a


# ---------------------------------------------------------------------------
# unit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k", [(128, 128), (256, 384), (512, 128)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.8, 0.99])
@pytest.mark.parametrize("m_tb,k_tb", [(128, 128), (64, 128), (128, 64)])
def test_roundtrip(m, k, sparsity, m_tb, k_tb):
    rng = np.random.default_rng(42)
    a = _random_sparse(rng, m, k, sparsity)
    t = tiled_csl.encode(a, m_tb=m_tb, k_tb=k_tb)
    dec = tiled_csl.decode(t)
    # bf16 value rounding only; zero/nonzero pattern must be exact
    assert ((dec != 0) == (a != 0)).all() or sparsity == 0.0
    rel = np.max(np.abs(dec - a)) / (np.max(np.abs(a)) + 1e-12)
    assert rel < 0.01
    assert t.n_nonzero == int((a != 0).sum())


def test_decode_jax_matches_numpy():
    rng = np.random.default_rng(0)
    a = _random_sparse(rng, 256, 256, 0.8)
    t = tiled_csl.encode(a)
    np.testing.assert_allclose(np.asarray(tiled_csl.decode_jax(t),
                                          dtype=np.float32),
                               tiled_csl.decode(t), atol=1e-6)


def test_column_slots_exact_placement():
    """Lane c of a tile lists column c's non-zeros top to bottom; unused
    slots hold PAD_WORD."""
    a = np.zeros((128, 128), np.float32)
    a[3, 5], a[7, 5], a[0, 9] = 1.0, -2.0, 0.5
    t = tiled_csl.encode(a)
    w = np.asarray(t.words)[0, 0]
    assert t.slots == tiled_csl.SLOT_QUANTUM and w.shape == (8, 128)
    vals, rows = tiled_csl.unpack_words(w)
    assert (rows[:2, 5] == [3, 7]).all() and (vals[:2, 5] == [1.0, -2.0]).all()
    assert rows[0, 9] == 0 and vals[0, 9] == 0.5
    untouched = np.ones(w.shape, bool)
    untouched[:2, 5] = untouched[0, 9] = False
    assert (w[untouched] == tiled_csl.PAD_WORD).all()
    assert int(np.asarray(t.nnz)[0, 0]) == 3


def _select_expand(words, m_tb):
    """The kernel's transform in numpy: one compare-select per slot."""
    vals, rows = tiled_csl.unpack_words(words)
    a = np.zeros((m_tb, words.shape[-1]), np.float32)
    row_ids = np.arange(m_tb)[:, None]
    for r in range(words.shape[0]):
        a = np.where(row_ids == rows[r][None, :], vals[r][None, :], a)
    return a


@pytest.mark.parametrize("sparsity", [0.0, 0.3, 0.6, 0.8, 0.95])
def test_select_expansion_rebuilds_tiles(sparsity):
    """Selecting each slot where its row matches rebuilds every tile: rows
    are unique within a column and PAD_ROW matches no row."""
    rng = np.random.default_rng(2)
    a = _random_sparse(rng, 256, 256, sparsity)
    t = tiled_csl.encode(a)
    dec = tiled_csl.decode(t)
    words = np.asarray(t.words)
    for mi in range(2):
        for ki in range(2):
            np.testing.assert_array_equal(
                _select_expand(words[mi, ki], 128),
                dec[mi * 128:(mi + 1) * 128, ki * 128:(ki + 1) * 128])


def test_pack_unpack_inverse():
    rng = np.random.default_rng(3)
    vals = rng.standard_normal(1000).astype(np.float32)
    rows = rng.integers(0, 2 ** 14, 1000)
    w = tiled_csl.pack_words(vals, rows)
    v2, r2 = tiled_csl.unpack_words(w)
    assert (r2 == rows).all()
    rel = np.abs(v2 - vals) / (np.abs(vals) + 1e-12)
    assert rel.max() < 0.008      # bf16 mantissa

def test_padding_word_is_exact_noop():
    """PAD_WORD is (+0.0 | PAD_ROW): a row no legal tile has, so the
    expansion never selects it, and a zero value should anything add it."""
    vals, rows = tiled_csl.unpack_words(np.full(4, tiled_csl.PAD_WORD,
                                                np.uint32))
    assert (vals == 0.0).all() and (rows == tiled_csl.PAD_ROW).all()
    assert not contracts.tile_loc_ok(tiled_csl.PAD_ROW)


def test_pad_overhead_bounded():
    """Slots follow the fullest tile column, so at 80% sparsity on
    128-row tiles about half the words are padding (~48 slots for a mean
    of ~26 non-zeros per column) — the layout still streams fewer bytes
    than dense bf16."""
    rng = np.random.default_rng(4)
    a = _random_sparse(rng, 1024, 1024, 0.8)
    t = tiled_csl.encode(a)
    assert t.pad_overhead < 0.5
    assert t.nbytes_sparse < 0.8 * t.nbytes_dense
    assert t.bytes_per_nonzero == pytest.approx(
        t.nbytes_sparse / t.n_nonzero)
    assert t.bytes_per_nonzero < 2.0 / 0.2      # dense bf16 per non-zero


@pytest.mark.parametrize("extra", [0, 8, 24])
def test_pad_slots_keeps_matrix(extra):
    rng = np.random.default_rng(5)
    t = tiled_csl.encode(_random_sparse(rng, 256, 128, 0.7))
    tp = tiled_csl.pad_slots(t, t.slots + extra)
    assert tp.slots == t.slots + extra
    assert (np.asarray(tp.words)[:, :, t.slots:] == tiled_csl.PAD_WORD).all()
    np.testing.assert_array_equal(tiled_csl.decode(tp), tiled_csl.decode(t))
    with pytest.raises(ValueError):
        tiled_csl.pad_slots(tp, t.slots - 8)


def test_misaligned_shape_raises():
    with pytest.raises(ValueError):
        tiled_csl.encode(np.zeros((100, 128), np.float32))


# ---------------------------------------------------------------------------
# property sweeps (deterministic; formerly hypothesis-driven)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mt,kt,sparsity,seed,m_tb", [
    (1, 1, 0.0, 11, 128),
    (1, 1, 0.999, 12, 64),
    (1, 2, 0.25, 13, 128),
    (1, 3, 0.5, 14, 64),
    (2, 1, 0.6, 15, 128),
    (2, 2, 0.7, 16, 64),
    (2, 3, 0.8, 17, 128),
    (3, 1, 0.85, 18, 64),
    (3, 2, 0.9, 19, 128),
    (3, 3, 0.95, 20, 64),
    (1, 1, 0.5, 21, 64),
    (2, 2, 0.99, 22, 128),
    (3, 3, 0.999, 23, 128),
    (1, 3, 0.33, 24, 64),
    (3, 1, 0.05, 25, 128),
    (2, 1, 0.97, 26, 64),
    (1, 2, 0.77, 27, 64),
    (2, 3, 0.42, 28, 128),
    (3, 2, 0.66, 29, 64),
    (2, 2, 0.15, 30, 128),
])
def test_roundtrip_property(mt, kt, sparsity, seed, m_tb):
    rng = np.random.default_rng(seed)
    a = _random_sparse(rng, mt * m_tb, kt * 128, sparsity)
    t = tiled_csl.encode(a, m_tb=m_tb, k_tb=128)
    dec = tiled_csl.decode(t)
    assert ((dec != 0) == (a != 0)).all()
    if (a != 0).any():
        rel = np.max(np.abs(dec - a)) / np.max(np.abs(a))
        assert rel < 0.01
    # derived stats are consistent
    assert t.n_nonzero == int((a != 0).sum())
    assert t.slots % tiled_csl.SLOT_QUANTUM == 0
    assert int(np.asarray(t.nnz).max()) <= t.max_nnz


@pytest.mark.parametrize("seed,sparsity,m_tb", [
    (31, 0.3, 128), (32, 0.35, 64), (33, 0.4, 128), (34, 0.45, 64),
    (35, 0.5, 128), (36, 0.55, 64), (37, 0.6, 128), (38, 0.65, 64),
    (39, 0.7, 128), (40, 0.75, 64), (41, 0.8, 128), (42, 0.85, 64),
    (43, 0.9, 128), (44, 0.93, 64), (45, 0.95, 128),
])
def test_slot_layout_property(seed, sparsity, m_tb):
    """Every lane lists its tile column's non-zeros in ascending row order,
    packed to the front; the slot count is the fullest column's, rounded
    up to SLOT_QUANTUM; ``nnz`` counts the real words per tile and
    ``slabs`` the 8-slot slabs up to its fullest column's last word."""
    rng = np.random.default_rng(seed)
    a = _random_sparse(rng, 2 * m_tb, 256, sparsity)
    t = tiled_csl.encode(a, m_tb=m_tb)
    vals, rows = tiled_csl.unpack_words(np.asarray(t.words))
    real = rows != tiled_csl.PAD_ROW
    col_counts = real.sum(axis=2)                        # [mt, kt, k_tb]
    assert t.slots == max(-(-int(col_counts.max()) // 8) * 8, 8)
    # packed to the front: slot r is real iff r < the column's count
    assert (real == (np.arange(t.slots)[None, None, :, None]
                     < col_counts[:, :, None, :])).all()
    assert (rows[real] < m_tb).all()
    r = np.where(real, rows, np.iinfo(np.int32).max)
    assert (np.diff(r, axis=2)[real[:, :, 1:]] > 0).all()
    np.testing.assert_array_equal(np.asarray(t.nnz), col_counts.sum(-1))
    np.testing.assert_array_equal(np.asarray(t.slabs),
                                  -(-col_counts.max(-1) // 8))
    assert (vals[~real] == 0.0).all()


# ---------------------------------------------------------------------------
# 16-bit row field overflow guard
# ---------------------------------------------------------------------------

def test_loc_overflow_tile_geometry_raises():
    """A tile taller than the 16-bit row field (whose all-ones value marks
    padding) would wrap ``row & 0xFFFF`` or alias the pad marker and
    corrupt weight placement; encode must refuse."""
    with pytest.raises(ValueError, match="16-bit row"):
        tiled_csl.encode(np.zeros((0xFFFF, 1), np.float32), m_tb=0xFFFF,
                         k_tb=1)
    with pytest.raises(ValueError, match="16-bit row"):
        tiled_csl.encode(np.zeros((0x10000, 1), np.float32), m_tb=0x10000,
                         k_tb=1)


def test_loc_boundary_geometry_roundtrips():
    """m_tb == 0xFFFE is the tallest legal tile: its bottom row (row field
    0xFFFE, one below the pad marker) must survive the roundtrip exactly."""
    a = np.zeros((0xFFFE, 2), np.float32)
    a[0, 0] = 2.0
    a[0xFFFD, 1] = 1.0
    t = tiled_csl.encode(a, m_tb=0xFFFE, k_tb=1)
    dec = tiled_csl.decode(t)
    np.testing.assert_allclose(dec, a, atol=0.0)


# ---------------------------------------------------------------------------
# grouped encoding
# ---------------------------------------------------------------------------

def _group_mats(rng, g, m, k, sparsities):
    return [_random_sparse(rng, m, k, s) for s in sparsities[:g]]


@pytest.mark.parametrize("g", [1, 2, 3])
def test_encode_group_roundtrip(g):
    rng = np.random.default_rng(50 + g)
    mats = _group_mats(rng, g, 256, 128, (0.5, 0.8, 0.95))
    tg = tiled_csl.encode_group(mats)
    assert tg.group == g
    assert tg.words.shape[:3] == (g, 2, 1)
    assert tg.nnz.shape == (g, 2, 1)
    dec = tiled_csl.decode(tg)
    dec_j = np.asarray(tiled_csl.decode_jax(tg), np.float32)
    assert dec.shape == (g, 256, 128)
    np.testing.assert_allclose(dec_j, dec, atol=1e-6)
    for i, a in enumerate(mats):
        assert ((dec[i] != 0) == (a != 0)).all()
        per = tiled_csl.decode(tiled_csl.group_slice(tg, i))
        np.testing.assert_allclose(per, dec[i], atol=0.0)


def test_encode_group_shares_max_nnz():
    """The group pads every member to one max_nnz (the stacking invariant
    the grouped kernel's static block shape needs); padding words stay
    exact no-ops so per-member decode is unchanged."""
    rng = np.random.default_rng(60)
    dense_ish = _random_sparse(rng, 128, 128, 0.3)
    sparse_ish = _random_sparse(rng, 128, 128, 0.95)
    tg = tiled_csl.encode_group([dense_ish, sparse_ish])
    t_solo = tiled_csl.encode(dense_ish)
    assert tg.max_nnz == t_solo.max_nnz       # max over the group
    np.testing.assert_allclose(tiled_csl.decode(tg)[1],
                               tiled_csl.decode(tiled_csl.encode(sparse_ish)),
                               atol=0.0)


def test_group_stack_matches_encode_group():
    rng = np.random.default_rng(61)
    mats = _group_mats(rng, 2, 128, 256, (0.7, 0.9))
    via_group = tiled_csl.encode_group(mats)
    via_stack = tiled_csl.group_stack([tiled_csl.encode(m) for m in mats])
    np.testing.assert_array_equal(np.asarray(via_group.words),
                                  np.asarray(via_stack.words))
    np.testing.assert_array_equal(np.asarray(via_group.nnz),
                                  np.asarray(via_stack.nnz))


def test_encode_group_rejects_mixed_shapes():
    rng = np.random.default_rng(62)
    with pytest.raises(ValueError, match="share one shape"):
        tiled_csl.encode_group([_random_sparse(rng, 128, 128, 0.5),
                                _random_sparse(rng, 256, 128, 0.5)])
    with pytest.raises(ValueError):
        tiled_csl.encode_group([])


# ---------------------------------------------------------------------------
# per-tile slab counts
# ---------------------------------------------------------------------------

# (rows of column 0, rows of column 5) per tile of a 2 x 3 tile grid, and
# the slab count each tile must carry: ceil(fullest column / 8), 0 empty.
_SLAB_TILES = {(0, 0): ((1, 0), 1), (0, 1): ((0, 0), 0), (0, 2): ((9, 3), 2),
               (1, 0): ((8, 8), 1), (1, 1): ((40, 17), 5),
               (1, 2): ((0, 16), 2)}


@pytest.mark.parametrize("m_tb", [128, 64])
def test_slabs_bound_each_tile(m_tb):
    """``slabs`` is ceil(fullest column count / 8) per tile and 0 exactly
    on an empty tile; every slab past it holds only PAD_WORD."""
    a = np.zeros((2 * m_tb, 3 * 128), np.float32)
    for (mi, ki), ((c0, c5), _) in _SLAB_TILES.items():
        r0, k0 = mi * m_tb, ki * 128
        a[r0:r0 + c0, k0] = np.arange(1, c0 + 1)
        a[r0 + m_tb - c5:r0 + m_tb, k0 + 5] = -np.arange(1, c5 + 1)
    t = tiled_csl.encode(a, m_tb=m_tb)
    assert t.slots == 40
    slabs = np.asarray(t.slabs)
    assert slabs.dtype == np.int32 and slabs.shape == np.asarray(t.nnz).shape
    for (mi, ki), (_, want) in _SLAB_TILES.items():
        assert slabs[mi, ki] == want, (mi, ki)
    assert ((slabs == 0) == (np.asarray(t.nnz) == 0)).all()
    words = np.asarray(t.words)
    for mi, ki in np.ndindex(slabs.shape):
        tail = words[mi, ki, slabs[mi, ki] * tiled_csl.SLOT_QUANTUM:]
        assert (tail == tiled_csl.PAD_WORD).all()


def _carries(t, want_slabs):
    np.testing.assert_array_equal(np.asarray(t.slabs), want_slabs)


@pytest.mark.parametrize("how", ["pad_slots", "group_stack", "scan_stack",
                                 "pytree", "pickle", "jit"])
def test_slabs_survive_every_reshaping(how):
    rng = np.random.default_rng(70)
    mats = [_random_sparse(rng, 256, 256, s) for s in (0.6, 0.95)]
    ts = [tiled_csl.encode(m) for m in mats]
    want = [np.asarray(t.slabs) for t in ts]
    assert not np.array_equal(want[0], want[1])
    t = ts[0]
    if how == "pad_slots":
        _carries(tiled_csl.pad_slots(t, t.slots + 16), want[0])
    elif how == "group_stack":
        tg = tiled_csl.group_stack(ts)
        _carries(tg, np.stack(want))
        for g in range(2):
            _carries(tiled_csl.group_slice(tg, g), want[g])
        _carries(tiled_csl.encode_group(mats), np.stack(want))
    elif how == "scan_stack":
        leaf = jnp.asarray(np.stack(mats))
        sp = pruning.sparsify_params({"w": leaf}, 0.0,
                                     should_sparsify=lambda n: True)["w"]
        # encoded per layer, padded to one slot count and re-stacked
        _carries(sp, np.stack([np.asarray(tiled_csl.encode(
            np.asarray(leaf[i])).slabs) for i in range(2)]))
        tg = tiled_csl.group_stack([sp, sp])      # [L, G, mt, kt]
        assert np.asarray(tg.slabs).shape == (2, 2, 2, 2)
    elif how == "pytree":
        leaves, treedef = jax.tree_util.tree_flatten(t)
        assert len(leaves) == 3
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        _carries(back, want[0])
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(t)[0]]
        assert paths == [".words", ".nnz", ".slabs"]
    elif how == "pickle":
        _carries(pickle.loads(pickle.dumps(jax.device_get(t))), want[0])
    else:
        _carries(jax.jit(lambda x: x)(t), want[0])


def test_decode_reads_only_the_words():
    """``slabs`` is what the kernel may skip, never what the matrix is:
    both decoders give the same matrix whatever it holds."""
    import dataclasses
    rng = np.random.default_rng(71)
    tg = tiled_csl.encode_group([_random_sparse(rng, 256, 128, s)
                                 for s in (0.5, 0.9)])
    zeroed = dataclasses.replace(tg, slabs=jnp.zeros_like(tg.slabs))
    np.testing.assert_array_equal(tiled_csl.decode(zeroed),
                                  tiled_csl.decode(tg))
    np.testing.assert_array_equal(np.asarray(tiled_csl.decode_jax(zeroed)),
                                  np.asarray(tiled_csl.decode_jax(tg)))


def test_slab_share_of_a_tree():
    """Share of the full expansion run: summed slabs over tiles x
    slots / 8, over every Tiled-CSL leaf; None without one."""
    rng = np.random.default_rng(72)
    a = tiled_csl.encode(_random_sparse(rng, 256, 256, 0.8))
    b = tiled_csl.encode_group([_random_sparse(rng, 128, 128, s)
                                for s in (0.5, 0.97)])
    tree = {"a": a, "b": {"w": b}, "dense": jnp.ones(3)}
    run = int(np.asarray(a.slabs).sum() + np.asarray(b.slabs).sum())
    full = a.slabs.size * a.slots // 8 + b.slabs.size * b.slots // 8
    assert tiled_csl.slab_share(tree) == pytest.approx(100 * run / full)
    assert 0 < tiled_csl.slab_share(tree) < 100
    assert tiled_csl.slab_share({"dense": jnp.ones(3)}) is None
