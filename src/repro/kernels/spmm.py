"""Flash-LLM Load-as-Sparse / Compute-as-Dense SpMM — Pallas TPU kernel.

Computes ``C[M, N] = A_sparse[M, K] @ B[K, N]`` where A is a Tiled-CSL
encoded unstructured-sparse weight matrix and B is a dense (skinny)
activation matrix. The kernel mirrors the paper's design point-for-point,
re-derived for the TPU memory hierarchy (DESIGN.md §2, §4):

* **Load-as-Sparse**: the only A traffic is the compressed ``words`` block —
  ``uint32[slots, k_tb]`` per (m, k) tile, column-slotted (DESIGN.md §2) —
  streamed HBM→VMEM by the Pallas grid pipeline, ``d`` consecutive K tiles
  of one M-tile row per grid step (:func:`launch_grid`). This is the paper's
  ``gmem2reg`` + the reduced-footprint insight.
* **Sparse→Dense transform** (:func:`_expand_tile`): the dense tile is
  built on the VPU by one compare-select per slot. Slot ``r`` is a row of
  ``k_tb`` words, one per tile column; broadcast down the sublanes, its row
  fields are compared with the row index of every tile element and its
  values selected where they match (paper: ``rst_smem`` + ``extract`` on
  SIMT cores). Mosaic has no scatter, so the paper's per-word store becomes
  one full-tile select per slot. Slots run in 8-slot slabs, and each
  tile's loop stops at its own last slab that holds a real word (the
  format's ``slabs``; the paper's Alg.2 bounds its loop by ``nnz_thread``
  likewise): the slabs past it hold only padding, whose row no tile has,
  so skipping them changes no bit.
* **Compute-as-Dense**: a full ``(M_TB, K_TB) @ (K_TB, N_TB)`` MXU matmul per
  tile in B's dtype, ``preferred_element_type=f32`` — redundant FLOPs
  tolerated because the op is memory-bound (paper §3.2.2); the tile's
  values are bf16, so casting the expanded tile to a bf16 B loses nothing.
* **Two-level overlap** (paper §4.2): inter-iteration double buffering is the
  Mosaic grid pipeliner (HBM→VMEM DMA of block *i+1* overlaps the body of
  block *i*); intra-iteration overlap is the DMA engine running async with
  the VPU expansion and MXU dot by construction.
* **TileOffsets prefetch** (paper Alg.1 lines 5-12): the per-tile
  ``slabs`` array rides in SMEM via ``PrefetchScalarGridSpec`` scalar
  prefetch; it bounds each tile's expansion loop and gates an
  all-zero-tile fast path (``pl.when(slabs > 0)``) — beyond-paper
  micro-optimisations that exactness of padding makes free.
* **Several K tiles per grid step**: each step expands and multiplies
  ``d`` tiles in K order (:func:`_block_dot`), each behind its own slab
  gate, into the same f32 accumulator — the association of a
  one-tile-per-step loop, bit for bit — so the per-step cost (pipeline
  bookkeeping, DMA issue and wait) is paid once per ``d`` tiles. ``d`` is
  read off the launch shape (``contracts.tiles_per_step``).

Two beyond-paper fusions remove the pointwise HBM round-trips the model
stack otherwise pays after every projection (DESIGN.md §8):

* **Fused epilogues** — ``epilogue`` in {silu, gelu, relu} plus an optional
  [M] ``bias`` are applied to the f32 accumulator in VMEM at the flush, so
  linear→activation patterns (MLP up + GELU) write the *activated* C once
  instead of write-preact / read-preact / write-act. ``sparse_linear.linear``
  and the model MLPs route through this path for Tiled-CSL weights.
* **Grouped SpMM** (``lscd_spmm_grouped``) — a grouped Tiled-CSL (G
  same-shape weights, shared ``slots``; ``tiled_csl.encode_group``) adds a
  fourth, innermost grid dimension. For each (m, n, k) step the G word
  streams are visited back-to-back while the B block index stays fixed, so
  the pipeliner streams B *once* for all G outputs. Binary epilogues
  (``silu_mul``/``gelu_mul``) combine the G=2 group-pair accumulators in
  VMEM — SwiGLU's ``silu(gate(x)) * up(x)`` flushes as a single C-sized
  write-back instead of two pre-activation writes plus a pointwise pass.

Grids (DESIGN.md §4, §9):

* **Single-pass** (``lscd_spmm`` / ``lscd_spmm_grouped``):
  ``(Mt, Nt, Kt/d[, G])`` with K (then G) innermost ("arbitrary"
  semantics); the f32 accumulator lives in VMEM scratch and is flushed —
  bias + epilogue applied, one cast — at the last K block (last group for
  binary epilogues).
* **Split-K** (``lscd_spmm_splitk`` / ``lscd_spmm_splitk_grouped``, paper
  §4.4's global-reduction splitting re-derived for the skinny decode
  regime): a leading *parallel* split dimension partitions the Kt tiles,
  ``(S, Mt, Nt, ceil(Kt/S)/d[, G])``; each slice accumulates its K-range in
  VMEM scratch and writes an f32 partials block ``[S,(G,) M, N]``, and a
  second lightweight reduce kernel (grid ``(Mt, Nt)``) sums the S partials
  and applies bias + epilogue at the final flush. Partials stay f32 end to
  end, so the bias/activation/output-cast rounding points are identical to
  the single-pass flush. ``kernels/schedule.py`` picks S (and the tile
  sizes) per shape; at N <= 64 the N-tile count is 1 and S > 1 is the only
  way to put more than Mt programs in flight.

Every raw entry takes ``interpret`` as a required keyword: ``True`` runs
the kernel body in the Pallas interpreter (CPU validation against
``ref.spmm_ref`` / ``ref.spmm_grouped_ref`` over shapes × sparsities ×
dtypes × tile geometries × group sizes × epilogues), ``False`` lowers it
through Mosaic for the TPU (``tests/test_tpu_compile.py`` compiles every
entry for a described v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import contracts
from repro.core import tiled_csl


def _require_launch(t: tiled_csl.TiledCSL, n: int, n_tb: int, split_k: int,
                    interpret: bool, b_dtype, out_dtype) -> None:
    """Last line of defence before ``pallas_call``: re-validate the launch
    against the kernel contracts (KC-*, DESIGN.md §12). ``schedule.select``
    already filters, but raw kernel entries are public — a caller pinning
    geometry by hand must hit the same wall the selector enforces."""
    m, k = t.shape
    contracts.require_schedule(
        m, k, n, m_tb=t.m_tb, k_tb=t.k_tb, n_tb=n_tb, split_k=split_k,
        group=t.group or 1, max_nnz=t.max_nnz,
        backend="interpret" if interpret else "pallas",
        b_dtype_bytes=jnp.dtype(b_dtype).itemsize,
        out_dtype_bytes=jnp.dtype(out_dtype).itemsize,
        path=f"launch({m},{k},{n})")


# Unary epilogues: applied per output in the flush stage (f32, pre-cast).
_EPILOGUES = {
    "none": lambda x: x,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": lambda x: jnp.maximum(x, 0.0),
}

# Binary epilogues: combine the two accumulators of a G=2 grouped call into
# ONE output (gate-style fusions; argument order is (group 0, group 1)).
_BINARY_EPILOGUES = {
    "silu_mul": lambda a, b: jax.nn.silu(a) * b,   # SwiGLU: silu(gate)*up
    "gelu_mul": lambda a, b: jax.nn.gelu(a) * b,   # GeGLU
}


def apply_epilogue(name: str, *accs: jax.Array) -> jax.Array:
    """Apply a registered epilogue outside the kernel (oracles, dense
    paths): one accumulator for unary names, the (group 0, group 1) pair
    for binary names. Keeps the registry encapsulated here."""
    if name in _BINARY_EPILOGUES:
        a, b = accs
        return _BINARY_EPILOGUES[name](a, b)
    return _EPILOGUES[name](*accs)


def epilogue_kind(name: str, *, groups: int = 1) -> str:
    """Validate ``name`` against the kernel registry → "unary" | "binary".

    Raises ValueError on unknown names (instead of a KeyError deep inside
    the Pallas trace) and on binary epilogues with a group size != 2.
    """
    if name in _EPILOGUES:
        return "unary"
    if name in _BINARY_EPILOGUES:
        if groups != 2:
            raise ValueError(
                f"binary epilogue {name!r} combines exactly 2 grouped "
                f"outputs, got group size {groups}")
        return "binary"
    known = sorted(_EPILOGUES) + sorted(_BINARY_EPILOGUES)
    raise ValueError(f"unknown epilogue {name!r}; known: {known}")


def _expand_tile(words_ref, tile, n_slabs, m_tb: int, dtype) -> jax.Array:
    """Tile ``tile`` of the column-slotted words block ``[d, slots, k_tb]`` →
    dense ``[m_tb, k_tb]`` tile.

    Reads the tile's first ``n_slabs`` 8-row slabs (one uint32 vreg of
    sublanes each; the slabs after them, up to the block's ``slots``, hold
    only padding); each slot row broadcasts down the tile and selects its
    value where its row field equals the element's row. An element takes at
    most one slot's value (rows are unique within a column), so the selects
    chain without adds.
    """
    k_tb = words_ref.shape[-1]
    q = tiled_csl.SLOT_QUANTUM
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (m_tb, k_tb), 0)

    def slab(i, a):
        w = words_ref[tile, pl.ds(pl.multiple_of(i * q, q), q), :]
        rows = (w & 0xFFFF).astype(jnp.int32)
        # The bf16 value sits in the high half: masking the row field off
        # leaves exactly that value as an f32 bit pattern.
        vals = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        for j in range(q):
            a = jnp.where(row_ids == rows[j:j + 1], vals[j:j + 1], a)
        return a

    a = jax.lax.fori_loop(0, n_slabs, slab,
                          jnp.zeros((m_tb, k_tb), jnp.float32))
    return a.astype(dtype)


def _block_dot(words_ref, b_ref, m_tb: int, tile_slabs, add) -> None:
    """One grid step's contribution, tile by tile in K order: tile ``j`` of
    the ``d``-tile block is expanded over its ``tile_slabs(j)`` slabs and
    multiplied by its ``k_tb`` rows of B when that count is above 0 (the
    tile is not empty), and its f32 product handed to ``add``.

    The loop is unrolled (``d`` is at most ``contracts.MAX_TILES_PER_STEP``):
    no loop bookkeeping between tiles, and constant tile offsets. Unrolled
    through ``fori_loop`` the body is traced once and lowered ``d`` times,
    half the trace-and-lower time of a Python loop; that time is paid at
    every process start, compile cache or not.
    """
    d, _, k_tb = words_ref.shape

    def tile(j, carry):
        n_slabs = tile_slabs(j)

        @pl.when(n_slabs > 0)
        def _body():
            # sparse -> dense transform (paper Fig.6b; VPU compare-select),
            # then compute-as-dense (MXU)
            a = _expand_tile(words_ref, j, n_slabs, m_tb, b_ref.dtype)
            rows = pl.ds(pl.multiple_of(j * k_tb, k_tb), k_tb)
            add(jnp.dot(a, b_ref[rows, :],
                        preferred_element_type=jnp.float32))
        return carry

    jax.lax.fori_loop(0, d, tile, 0, unroll=True)


def launch_grid(t: tiled_csl.TiledCSL, n: int, *, n_tb: int, split_k: int,
                b_dtype, out_dtype) -> tuple[tuple[int, ...], int]:
    """The compute kernel's grid for one K slice of this launch, ``(Mt, Nt,
    ceil(Kt/S)/d[, G])``, and the K tiles ``d`` each of its steps covers.
    The split-K entries prepend the ``S`` axis; ``n`` is already padded to
    ``n_tb``."""
    mt, kt = t.grid
    d = contracts.tiles_per_step(
        kt, split_k, m_tb=t.m_tb, k_tb=t.k_tb, n_tb=n_tb,
        max_nnz=t.max_nnz, group=t.group or 1,
        b_dtype_bytes=jnp.dtype(b_dtype).itemsize,
        out_dtype_bytes=jnp.dtype(out_dtype).itemsize)
    grid = (mt, n // n_tb, _splitk_chunk(kt, split_k) // d)
    return grid + ((t.group,) if t.group is not None else ()), d


def _words_spec(t: tiled_csl.TiledCSL, d: int, index_map) -> pl.BlockSpec:
    """``d`` consecutive tiles' ``[d, slots, k_tb]`` word block of one
    (group,) M-tile row; leading axes squeezed. The block's two minor dims
    are the array's own, which Mosaic accepts at any size."""
    lead = t.words.ndim - 3
    return pl.BlockSpec((pl.squeezed,) * lead + (d, t.slots, t.k_tb),
                        index_map)


def _lscd_spmm_kernel(slabs_ref,          # SMEM int32[Mt, Kt] (scalar prefetch)
                      words_ref,          # VMEM uint32[d, slots, K_TB]
                      b_ref,              # VMEM bf16/f32[d*K_TB, N_TB]
                      o_ref,              # VMEM out[M_TB, N_TB]
                      acc_ref,            # VMEM scratch f32[M_TB, N_TB]
                      *,
                      m_tb: int,
                      k_blocks: int,
                      epilogue: str = "none",
                      bias_ref=None):
    m, kb = pl.program_id(0), pl.program_id(2)
    d = words_ref.shape[0]

    @pl.when(kb == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _add(contrib):
        acc_ref[...] += contrib

    _block_dot(words_ref, b_ref, m_tb, lambda j: slabs_ref[m, kb * d + j],
               _add)

    @pl.when(kb == k_blocks - 1)
    def _flush():
        # Fused epilogue: bias + activation applied to the f32 accumulator in
        # VMEM before the HBM write-back — the pervasive linear->activation
        # pattern (MLP up + GELU) writes the activated C once instead of
        # write/read/write (wired end-to-end via ops.spmm ->
        # sparse_linear.linear -> models/layers.py).
        out = acc_ref[...]
        if bias_ref is not None:
            out = out + bias_ref[...].astype(jnp.float32)
        out = _EPILOGUES[epilogue](out)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_tb", "out_dtype", "interpret",
                                              "epilogue"))
def lscd_spmm(t: tiled_csl.TiledCSL,
              b: jax.Array,
              *,
              n_tb: int = 128,
              out_dtype=jnp.float32,
              interpret: bool,
              epilogue: str = "none",
              bias: jax.Array | None = None) -> jax.Array:
    """Raw kernel entry. Requires N % n_tb == 0; see ops.spmm for padding.

    ``epilogue`` in {none, silu, gelu, relu} and ``bias`` ([M] vector) fuse
    the post-GEMM pointwise stage into the flush (beyond-paper)."""
    if t.group is not None:
        raise ValueError("grouped TiledCSL: use lscd_spmm_grouped")
    epilogue_kind(epilogue)  # raises on unknown / binary names
    m, k = t.shape
    n = b.shape[1]
    if b.shape[0] != k:
        raise ValueError(f"B rows {b.shape[0]} != K {k}")
    if n % n_tb:
        raise ValueError(f"N={n} not a multiple of n_tb={n_tb}")
    _require_launch(t, n, n_tb, 1, interpret, b.dtype, out_dtype)
    grid, d = launch_grid(t, n, n_tb=n_tb, split_k=1, b_dtype=b.dtype,
                          out_dtype=out_dtype)

    in_specs = [
        # d compressed A tiles: the ONLY A traffic (load-as-sparse).
        _words_spec(t, d, lambda m_, n_, k_, slabs: (m_, k_, 0, 0)),
        # The d tiles' rows of the dense activation.
        pl.BlockSpec((d * t.k_tb, n_tb), lambda m_, n_, k_, slabs: (k_, n_)),
    ]
    args = [t.slabs, t.words, b]
    body = dict(m_tb=t.m_tb, k_blocks=grid[2], epilogue=epilogue)
    if bias is None:
        kernel = functools.partial(_lscd_spmm_kernel, bias_ref=None, **body)
    else:
        # bias tile rides along as [M_TB, 1] broadcast in the epilogue
        kernel = functools.partial(_lscd_spmm_kernel_bias, **body)
        in_specs.append(
            pl.BlockSpec((t.m_tb, 1), lambda m_, n_, k_, slabs: (m_, 0)))
        args.append(bias.reshape(m, 1).astype(jnp.float32))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((t.m_tb, n_tb), lambda m_, n_, k_, slabs: (m_, n_)),
            scratch_shapes=[pltpu.VMEM((t.m_tb, n_tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


def _lscd_spmm_kernel_bias(slabs_ref, words_ref, b_ref, bias_ref, o_ref,
                           acc_ref, *, m_tb, k_blocks, epilogue):
    """Bias-carrying variant (separate because Pallas positional refs)."""
    _lscd_spmm_kernel(slabs_ref, words_ref, b_ref, o_ref, acc_ref,
                      m_tb=m_tb, k_blocks=k_blocks,
                      epilogue=epilogue, bias_ref=bias_ref)


# ---------------------------------------------------------------------------
# grouped LSCD SpMM: G same-shape weights, one launch, B streamed once
# ---------------------------------------------------------------------------

def _add_group(acc_ref, g, groups: int, contrib) -> None:
    """``acc_ref[g] += contrib`` as static-index stores (unrolled over the
    small G) — no dynamic VMEM indexing in the inner loop."""
    for gi in range(groups):
        @pl.when(g == gi)
        def _store(gi=gi):
            acc_ref[gi] += contrib


def _lscd_spmm_grouped_kernel(slabs_ref,  # SMEM int32[G, Mt, Kt]
                              words_ref,  # VMEM uint32[d, slots, K_TB]
                              b_ref,      # VMEM bf16/f32[d*K_TB, N_TB]
                              o_ref,      # VMEM out[G, M_TB, N_TB] (unary)
                                          #      or [M_TB, N_TB]   (binary)
                              acc_ref,    # VMEM scratch f32[G, M_TB, N_TB]
                              *,
                              m_tb: int,
                              k_blocks: int,
                              groups: int,
                              epilogue: str = "none",
                              bias_ref=None):
    m, kb, g = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    d = words_ref.shape[0]
    binary = epilogue in _BINARY_EPILOGUES

    # g is innermost: for a fixed (m, n) the visit order is
    # (kb=0, g=0..G-1), (kb=1, g=0..G-1), ... — every accumulator slot takes
    # its first contribution during the kb==0 sweep, so one zeroing of the
    # whole scratch at (kb==0, g==0) suffices.
    @pl.when((kb == 0) & (g == 0))
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _block_dot(words_ref, b_ref, m_tb, lambda j: slabs_ref[g, m, kb * d + j],
               functools.partial(_add_group, acc_ref, g, groups))

    def _biased(gi, acc):
        if bias_ref is not None:
            return acc + bias_ref[gi].astype(jnp.float32)
        return acc

    if binary:
        # One C-sized write-back for the whole group pair (SwiGLU/GeGLU).
        @pl.when((kb == k_blocks - 1) & (g == groups - 1))
        def _flush_binary():
            out = _BINARY_EPILOGUES[epilogue](_biased(0, acc_ref[0]),
                                              _biased(1, acc_ref[1]))
            o_ref[...] = out.astype(o_ref.dtype)
    else:
        @pl.when(kb == k_blocks - 1)
        def _flush():
            for gi in range(groups):
                @pl.when(g == gi)
                def _w(gi=gi):
                    out = _EPILOGUES[epilogue](_biased(gi, acc_ref[gi]))
                    o_ref[gi] = out.astype(o_ref.dtype)


def _lscd_spmm_grouped_kernel_bias(slabs_ref, words_ref, b_ref, bias_ref,
                                   o_ref, acc_ref, *, m_tb, k_blocks,
                                   groups, epilogue):
    """Bias-carrying variant (separate because Pallas positional refs)."""
    _lscd_spmm_grouped_kernel(slabs_ref, words_ref, b_ref, o_ref, acc_ref,
                              m_tb=m_tb, k_blocks=k_blocks,
                              groups=groups, epilogue=epilogue,
                              bias_ref=bias_ref)


@functools.partial(jax.jit, static_argnames=("n_tb", "out_dtype", "interpret",
                                              "epilogue"))
def lscd_spmm_grouped(t: tiled_csl.TiledCSL,
                      b: jax.Array,
                      *,
                      n_tb: int = 128,
                      out_dtype=jnp.float32,
                      interpret: bool,
                      epilogue: str = "none",
                      bias: jax.Array | None = None) -> jax.Array:
    """Grouped kernel entry: C[G, M, N] (or C[M, N] for binary epilogues).

    ``t`` is a grouped Tiled-CSL (``tiled_csl.encode_group`` /
    ``group_stack``): G same-shape [M, K] weights sharing one ``slots``.
    The grid gains an innermost group dimension; consecutive group steps
    reuse the resident B block, so B is streamed once for all G outputs and
    the per-(m, n) output block (the full [G, M_TB, N_TB] column for unary
    epilogues) is written back exactly once.

    ``epilogue``: unary names apply per group (bias [G, M] likewise);
    ``silu_mul``/``gelu_mul`` need G == 2 and combine the pair's
    accumulators into a single [M, N] output in VMEM.
    Requires N % n_tb == 0; see ops.spmm_grouped for padding.
    """
    groups = t.group
    if groups is None:
        raise ValueError("ungrouped TiledCSL: use lscd_spmm")
    kind = epilogue_kind(epilogue, groups=groups)
    m, k = t.shape
    n = b.shape[1]
    if b.shape[0] != k:
        raise ValueError(f"B rows {b.shape[0]} != K {k}")
    if n % n_tb:
        raise ValueError(f"N={n} not a multiple of n_tb={n_tb}")
    _require_launch(t, n, n_tb, 1, interpret, b.dtype, out_dtype)
    grid, d = launch_grid(t, n, n_tb=n_tb, split_k=1, b_dtype=b.dtype,
                          out_dtype=out_dtype)

    in_specs = [
        # Group g's d compressed A tiles (the only A traffic). The B block
        # index is independent of g, so the pipeliner holds B resident
        # across the G inner steps.
        _words_spec(t, d, lambda m_, n_, k_, g_, slabs: (g_, m_, k_, 0, 0)),
        pl.BlockSpec((d * t.k_tb, n_tb),
                     lambda m_, n_, k_, g_, slabs: (k_, n_)),
    ]
    args = [t.slabs, t.words, b]
    body = dict(m_tb=t.m_tb, k_blocks=grid[2], groups=groups,
                epilogue=epilogue)
    if bias is None:
        kernel = functools.partial(_lscd_spmm_grouped_kernel, bias_ref=None,
                                   **body)
    else:
        kernel = functools.partial(_lscd_spmm_grouped_kernel_bias, **body)
        in_specs.append(
            pl.BlockSpec((groups, t.m_tb, 1),
                         lambda m_, n_, k_, g_, slabs: (0, m_, 0)))
        args.append(bias.reshape(groups, m, 1).astype(jnp.float32))

    if kind == "binary":
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
        out_specs = pl.BlockSpec((t.m_tb, n_tb),
                                 lambda m_, n_, k_, g_, slabs: (m_, n_))
    else:
        # The whole [G, M_TB, N_TB] column is one block: its index is
        # constant over (k, g), so it is written back once per (m, n).
        out_shape = jax.ShapeDtypeStruct((groups, m, n), out_dtype)
        out_specs = pl.BlockSpec((groups, t.m_tb, n_tb),
                                 lambda m_, n_, k_, g_, slabs: (0, m_, n_))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((groups, t.m_tb, n_tb), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# split-K LSCD SpMM: partials over K slices + a global-reduce flush kernel
# (paper §4.4, re-derived for the skinny decode regime — DESIGN.md §9)
# ---------------------------------------------------------------------------

def _splitk_chunk(kt: int, split_k: int) -> int:
    """K tiles per split slice. The last slice may own fewer real tiles
    (Kt % S != 0); its out-of-range blocks (``d`` divides Kt, so a block is
    wholly real or wholly past the end) clamp their block index and are
    predicated off via the slab gate, contributing exact zeros."""
    return -(-kt // split_k)


def _splitk_tile_slabs(slabs_row, k_tiles: int, kb, d: int):
    """Tile ``j`` of global K block ``kb``'s slab count, 0 past the end of
    K: such tiles read a clamped-index block but are masked off, so the
    partial stays an exact zero."""
    def tile_slabs(j):
        k = kb * d + j
        return jnp.where(k < k_tiles,
                         slabs_row(jnp.minimum(k, k_tiles - 1)), 0)
    return tile_slabs


def _lscd_spmm_splitk_kernel(slabs_ref,    # SMEM int32[Mt, Kt]
                             words_ref,    # VMEM uint32[d, slots, K_TB]
                             b_ref,        # VMEM bf16/f32[d*K_TB, N_TB]
                             p_ref,        # VMEM f32[1, M_TB, N_TB] partials
                             acc_ref,      # VMEM scratch f32[M_TB, N_TB]
                             *,
                             m_tb: int,
                             k_tiles: int,
                             k_blocks: int):
    m, kl = pl.program_id(1), pl.program_id(3)
    kb = pl.program_id(0) * k_blocks + kl  # global K-block index of this step
    d = words_ref.shape[0]

    @pl.when(kl == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _add(contrib):
        acc_ref[...] += contrib

    _block_dot(words_ref, b_ref, m_tb,
               _splitk_tile_slabs(lambda k: slabs_ref[m, k], k_tiles, kb, d),
               _add)

    @pl.when(kl == k_blocks - 1)
    def _flush_partial():
        # f32 partials, NO epilogue/cast: the single rounding point stays in
        # the reduce kernel's flush.
        p_ref[0] = acc_ref[...]


def _splitk_reduce_kernel(p_ref,           # VMEM f32[S, M_TB, N_TB]
                          o_ref,           # VMEM out[M_TB, N_TB]
                          *, epilogue: str, bias_ref=None):
    out = jnp.sum(p_ref[...], axis=0)      # f32 global reduction over S
    if bias_ref is not None:
        out = out + bias_ref[...].astype(jnp.float32)
    o_ref[...] = _EPILOGUES[epilogue](out).astype(o_ref.dtype)


def _splitk_reduce_kernel_bias(p_ref, bias_ref, o_ref, *, epilogue):
    _splitk_reduce_kernel(p_ref, o_ref, epilogue=epilogue, bias_ref=bias_ref)


@functools.partial(jax.jit, static_argnames=("n_tb", "split_k", "out_dtype",
                                              "interpret", "epilogue"))
def lscd_spmm_splitk(t: tiled_csl.TiledCSL,
                     b: jax.Array,
                     *,
                     n_tb: int = 128,
                     split_k: int = 2,
                     out_dtype=jnp.float32,
                     interpret: bool,
                     epilogue: str = "none",
                     bias: jax.Array | None = None) -> jax.Array:
    """Split-K kernel entry: grid ``(S, Mt, Nt, ceil(Kt/S)/d)`` + a reduce.

    Each split slice accumulates its K-tile range into VMEM scratch and
    writes one f32 partials block; the reduce kernel (grid ``(Mt, Nt)``)
    sums the S partials and applies bias + epilogue at the one flush, so
    numerics match :func:`lscd_spmm` apart from the (f32) partial-sum
    association. ``split_k == 1`` is the identical computation in two
    launches. Requires N % n_tb == 0; see ops.spmm for padding.
    """
    if t.group is not None:
        raise ValueError("grouped TiledCSL: use lscd_spmm_splitk_grouped")
    epilogue_kind(epilogue)
    m, k = t.shape
    n = b.shape[1]
    kt = t.grid[1]
    if b.shape[0] != k:
        raise ValueError(f"B rows {b.shape[0]} != K {k}")
    if n % n_tb:
        raise ValueError(f"N={n} not a multiple of n_tb={n_tb}")
    # KC-SPLIT and the rest of the launch contract (VMEM footprint of both
    # the partials and the reduce launch) in one shared predicate.
    _require_launch(t, n, n_tb, split_k, interpret, b.dtype, out_dtype)
    grid, d = launch_grid(t, n, n_tb=n_tb, split_k=split_k, b_dtype=b.dtype,
                          out_dtype=out_dtype)
    k_blocks = grid[2]

    kernel = functools.partial(
        _lscd_spmm_splitk_kernel, m_tb=t.m_tb, k_tiles=kt, k_blocks=k_blocks)
    k_ix = lambda s_, kl_: jnp.minimum(s_ * k_blocks + kl_, kt // d - 1)
    partials = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(split_k,) + grid,
            in_specs=[
                _words_spec(t, d, lambda s_, m_, n_, kl_, slabs:
                            (m_, k_ix(s_, kl_), 0, 0)),
                pl.BlockSpec((d * t.k_tb, n_tb),
                             lambda s_, m_, n_, kl_, slabs: (k_ix(s_, kl_),
                                                           n_)),
            ],
            out_specs=pl.BlockSpec((1, t.m_tb, n_tb),
                                   lambda s_, m_, n_, kl_, slabs: (s_, m_, n_)),
            scratch_shapes=[pltpu.VMEM((t.m_tb, n_tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((split_k, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(t.slabs, t.words, b)

    in_specs = [pl.BlockSpec((split_k, t.m_tb, n_tb),
                             lambda m_, n_: (0, m_, n_))]
    args = [partials]
    if bias is None:
        red = functools.partial(_splitk_reduce_kernel, epilogue=epilogue,
                                bias_ref=None)
    else:
        red = functools.partial(_splitk_reduce_kernel_bias, epilogue=epilogue)
        in_specs.append(pl.BlockSpec((t.m_tb, 1), lambda m_, n_: (m_, 0)))
        args.append(bias.reshape(m, 1).astype(jnp.float32))
    return pl.pallas_call(
        red,
        grid=grid[:2],
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t.m_tb, n_tb), lambda m_, n_: (m_, n_)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(*args)


def _lscd_spmm_splitk_grouped_kernel(slabs_ref,  # SMEM int32[G, Mt, Kt]
                                     words_ref,  # VMEM uint32[d, slots, K_TB]
                                     b_ref,      # VMEM bf16/f32[d*K_TB, N_TB]
                                     p_ref,      # VMEM f32[1, G, M_TB, N_TB]
                                     acc_ref,    # scratch f32[G, M_TB, N_TB]
                                     *,
                                     m_tb: int,
                                     k_tiles: int,
                                     k_blocks: int,
                                     groups: int):
    m = pl.program_id(1)
    kl, g = pl.program_id(3), pl.program_id(4)
    kb = pl.program_id(0) * k_blocks + kl
    d = words_ref.shape[0]

    @pl.when((kl == 0) & (g == 0))
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _block_dot(words_ref, b_ref, m_tb,
               _splitk_tile_slabs(lambda k: slabs_ref[g, m, k], k_tiles, kb,
                                  d),
               functools.partial(_add_group, acc_ref, g, groups))

    @pl.when((kl == k_blocks - 1) & (g == groups - 1))
    def _flush_partial():
        p_ref[0] = acc_ref[...]


def _splitk_reduce_grouped_kernel(p_ref,   # VMEM f32[S, G, M_TB, N_TB]
                                  o_ref,   # VMEM out[G, M_TB, N_TB] (unary)
                                           #      or [M_TB, N_TB]   (binary)
                                  *, epilogue: str, bias_ref=None):
    acc = jnp.sum(p_ref[...], axis=0)      # f32 [G, M_TB, N_TB]
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    if epilogue in _BINARY_EPILOGUES:
        out = _BINARY_EPILOGUES[epilogue](acc[0], acc[1])
    else:
        out = _EPILOGUES[epilogue](acc)
    o_ref[...] = out.astype(o_ref.dtype)


def _splitk_reduce_grouped_kernel_bias(p_ref, bias_ref, o_ref, *, epilogue):
    _splitk_reduce_grouped_kernel(p_ref, o_ref, epilogue=epilogue,
                                  bias_ref=bias_ref)


@functools.partial(jax.jit, static_argnames=("n_tb", "split_k", "out_dtype",
                                              "interpret", "epilogue"))
def lscd_spmm_splitk_grouped(t: tiled_csl.TiledCSL,
                             b: jax.Array,
                             *,
                             n_tb: int = 128,
                             split_k: int = 2,
                             out_dtype=jnp.float32,
                             interpret: bool,
                             epilogue: str = "none",
                             bias: jax.Array | None = None) -> jax.Array:
    """Grouped split-K entry: grid ``(S, Mt, Nt, ceil(Kt/S)/d, G)`` + reduce.

    Semantics match :func:`lscd_spmm_grouped` — C[G, M, N] for unary
    epilogues (bias [G, M] applied per group), C[M, N] for binary ones —
    with the K reduction split exactly as in :func:`lscd_spmm_splitk`: f32
    partials [S, G, M, N], bias + epilogue at the reduce kernel's flush.
    B still streams once per (s, m, n) for all G groups.
    """
    groups = t.group
    if groups is None:
        raise ValueError("ungrouped TiledCSL: use lscd_spmm_splitk")
    kind = epilogue_kind(epilogue, groups=groups)
    m, k = t.shape
    n = b.shape[1]
    kt = t.grid[1]
    if b.shape[0] != k:
        raise ValueError(f"B rows {b.shape[0]} != K {k}")
    if n % n_tb:
        raise ValueError(f"N={n} not a multiple of n_tb={n_tb}")
    # KC-SPLIT plus the VMEM contract of the [S, G, m_tb, n_tb] reduce block.
    _require_launch(t, n, n_tb, split_k, interpret, b.dtype, out_dtype)
    grid, d = launch_grid(t, n, n_tb=n_tb, split_k=split_k, b_dtype=b.dtype,
                          out_dtype=out_dtype)
    k_blocks = grid[2]

    kernel = functools.partial(
        _lscd_spmm_splitk_grouped_kernel, m_tb=t.m_tb, k_tiles=kt,
        k_blocks=k_blocks, groups=groups)
    k_ix = lambda s_, kl_: jnp.minimum(s_ * k_blocks + kl_, kt // d - 1)
    partials = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(split_k,) + grid,
            in_specs=[
                _words_spec(t, d, lambda s_, m_, n_, kl_, g_, slabs:
                            (g_, m_, k_ix(s_, kl_), 0, 0)),
                pl.BlockSpec((d * t.k_tb, n_tb),
                             lambda s_, m_, n_, kl_, g_, slabs:
                             (k_ix(s_, kl_), n_)),
            ],
            out_specs=pl.BlockSpec((1, groups, t.m_tb, n_tb),
                                   lambda s_, m_, n_, kl_, g_, slabs:
                                   (s_, 0, m_, n_)),
            scratch_shapes=[pltpu.VMEM((groups, t.m_tb, n_tb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((split_k, groups, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(t.slabs, t.words, b)

    if kind == "binary":
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
        out_specs = pl.BlockSpec((t.m_tb, n_tb), lambda m_, n_: (m_, n_))
    else:
        out_shape = jax.ShapeDtypeStruct((groups, m, n), out_dtype)
        out_specs = pl.BlockSpec((groups, t.m_tb, n_tb),
                                 lambda m_, n_: (0, m_, n_))
    in_specs = [pl.BlockSpec((split_k, groups, t.m_tb, n_tb),
                             lambda m_, n_: (0, 0, m_, n_))]
    args = [partials]
    if bias is None:
        red = functools.partial(_splitk_reduce_grouped_kernel,
                                epilogue=epilogue, bias_ref=None)
    else:
        red = functools.partial(_splitk_reduce_grouped_kernel_bias,
                                epilogue=epilogue)
        in_specs.append(pl.BlockSpec((groups, t.m_tb, 1),
                                     lambda m_, n_: (0, m_, 0)))
        args.append(bias.reshape(groups, m, 1).astype(jnp.float32))
    return pl.pallas_call(
        red,
        grid=grid[:2],
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(*args)
