"""Kernel launch contracts: the shared predicates behind rules KC-* .

This module is the single source of truth for the resource invariants of
the LSCD SpMM / dense GEMM Pallas launches (DESIGN.md §12). It is kept
dependency-light (stdlib + ``core.roofline`` + the budget/finding models)
so the *enforcement sites* can import it without cycles:

* ``core.tiled_csl.encode`` calls :func:`require_tile_loc` (rule KC-LOC) —
  the encoding check and the static checker literally share one predicate;
* ``kernels.schedule.select`` / ``autotune`` call :func:`require_schedule`
  / :func:`check_schedule` so an invalid schedule is rejected *before* any
  ``pallas_call`` and can never be persisted as a cache winner;
* the ``kernels.spmm`` / ``kernels.gemm`` launch builders validate their
  concrete launch with :func:`require_schedule` as a last line of defence;
* ``benchmarks.check_regression`` re-validates the recorded schedule picks
  in both the committed baseline and the current run;
* ``analysis.kernel_pass`` sweeps the whole selector grid through
  :func:`check_schedule` for the CLI/CI gate.

Checked invariants (one rule id each):

KC-LOC    ``m_tb < 65535``: the packed Tiled-CSL word stores the in-tile
          row in 16 bits and reserves the all-ones row for padding; a
          taller tile would wrap ``row & 0xFFFF`` or alias the pad marker
          and corrupt weight placement.
KC-GRID   the dense dims must tile evenly (``m % m_tb == k % k_tb == 0``)
          — the BlockSpec index maps assume exact tiling of M and K (N is
          exempt: ``ops.spmm`` pads N to the tile before launch).
KC-SPLIT  ``1 <= split_k <= Kt``: a K slice with zero real tiles is pure
          partials traffic; ``split_k < 1`` breaks the partials grid.
KC-NTB    ``n_tb`` must be a positive multiple of 8 (VPU sublane quantum)
          and at most 128 (TPU lane width). On the ``pallas`` backend
          Mosaic also needs the N block to fill the lanes: ``n_tb == 128``
          or a single N tile (``n_tb`` equal to the padded N).
KC-VMEM   the launch's static VMEM footprint — double-buffered in/out
          blocks plus accumulator scratch, for BOTH kernels of a split-K
          pair — must fit the per-backend budget
          (``analysis.budgets.vmem_budget``).

Source-level contracts (checked by AST over the kernel files, reported by
``analysis.kernel_pass``):

KC-ACC    every ``pltpu.VMEM`` scratch and every ``preferred_element_type``
          in the kernel bodies is float32 — bf16 accumulation loses ~8 bits
          of mantissa over K=8192 reductions.
KC-OUT    every ``sparse_linear.linear*`` call site in ``models/`` passes
          ``declared_out``/``declared_outs`` — the padded-out-dim slice
          contract (DESIGN.md §6) is caller-declared and silently wrong
          when omitted.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import List, Optional, Tuple

from repro.analysis import budgets
from repro.analysis.findings import Finding
from repro.core import roofline

#: Rows the packed Tiled-CSL word's 16-bit row field can address; the
#: all-ones value (``tiled_csl.PAD_ROW``) is the padding marker.
MAX_TILE_ROWS = 0xFFFF

#: TPU vector lane/sublane geometry the N tile must respect.
LANE_WIDTH = 128
SUBLANE_QUANTUM = 8

#: Grid-pipeline double-buffering factor for in/out blocks (the next block
#: DMAs while the current one computes); scratch is single-buffered.
DOUBLE_BUFFER = 2


class ScheduleContractError(ValueError):
    """An invalid launch schedule, raised before any ``pallas_call``.

    Carries the findings so callers (autotune sweeps, tests) can inspect
    the violated rule ids via ``err.findings``.
    """

    def __init__(self, findings: List[Finding]):
        self.findings = findings
        super().__init__("; ".join(f"{f.rule}: {f.message}" for f in findings))


def tile_loc_ok(m_tb: int) -> bool:
    """KC-LOC predicate: tile rows fit the 16-bit row field."""
    return 1 <= m_tb < MAX_TILE_ROWS


def require_tile_loc(m_tb: int) -> None:
    """Raise ``ValueError`` on KC-LOC violation (shared with
    ``tiled_csl.encode`` — the message is part of its API)."""
    if not tile_loc_ok(m_tb):
        raise ValueError(
            f"tile height m_tb={m_tb} does not fit the 16-bit row field "
            f"(at most {MAX_TILE_ROWS - 1} rows; {MAX_TILE_ROWS} marks "
            f"padding)")


@dataclasses.dataclass(frozen=True)
class VmemBreakdown:
    """Static VMEM bytes per buffer class for one (possibly split-K) launch.

    ``main_bytes`` is the compute kernel's footprint; ``reduce_bytes`` the
    split-K reduce kernel's (0 when ``split_k == 1``). The checkable
    footprint is their max — the two are separate launches.
    ``expand_bytes`` is the dense tile the slot expansion builds (f32,
    plus its cast copy for the MXU).
    """

    words_bytes: int
    b_block_bytes: int
    out_block_bytes: int
    bias_bytes: int
    acc_scratch_bytes: int
    expand_bytes: int
    reduce_bytes: int

    @property
    def main_bytes(self) -> int:
        return (self.words_bytes + self.b_block_bytes + self.out_block_bytes
                + self.bias_bytes + self.acc_scratch_bytes
                + self.expand_bytes)

    @property
    def total_bytes(self) -> int:
        return max(self.main_bytes, self.reduce_bytes)


#: Most K tiles one LSCD grid step expands (DESIGN.md §4). Past ~8 the
#: per-step cost is spread thin (8 and 16 time within 0.5% of each other on
#: a v5e at OPT-30B's widths); at 16 the d-tile word and B blocks stay
#: ~2 MB of VMEM.
MAX_TILES_PER_STEP = 16


def _vmem_breakdown(d: int, m_tb: int, k_tb: int, n_tb: int, split_k: int,
                    max_nnz: int, group: int, b_dtype_bytes: int,
                    out_dtype_bytes: int) -> VmemBreakdown:
    g = max(1, group)
    slots = -(-max_nnz // k_tb)
    lanes = -(-k_tb // LANE_WIDTH) * LANE_WIDTH
    words = 4 * d * slots * lanes * DOUBLE_BUFFER
    expand = m_tb * k_tb * (4 + b_dtype_bytes)
    b_blk = d * k_tb * n_tb * b_dtype_bytes * DOUBLE_BUFFER
    # split-K pass 1 writes one f32 partials slice [1,(g,)m_tb,n_tb];
    # the fused kernel writes the final [g,m_tb,n_tb] in out_dtype.
    out_elem = 4 if split_k > 1 else out_dtype_bytes
    out_blk = g * m_tb * n_tb * out_elem * DOUBLE_BUFFER
    bias = g * m_tb * 4 * DOUBLE_BUFFER
    acc = g * m_tb * n_tb * 4
    reduce_b = 0
    if split_k > 1:
        reduce_b = (split_k * g * m_tb * n_tb * 4 * DOUBLE_BUFFER   # partials in
                    + g * m_tb * n_tb * out_dtype_bytes * DOUBLE_BUFFER
                    + bias)
    return VmemBreakdown(words, b_blk, out_blk, bias, acc, expand, reduce_b)


def tiles_per_step(k_tiles: int, split_k: int, *, m_tb: int, k_tb: int,
                   n_tb: int, max_nnz: int, group: int = 1,
                   b_dtype_bytes: int = 4, out_dtype_bytes: int = 4) -> int:
    """K tiles ``d`` that one LSCD grid step covers.

    The largest divisor of both ``k_tiles`` and the slice's tile count
    (``k_tiles``, or ``ceil(k_tiles / split_k)`` for split-K) that is at
    most :data:`MAX_TILES_PER_STEP` and keeps the launch inside the pallas
    VMEM budget; 1 when no larger one does. Dividing ``k_tiles`` makes every
    K block wholly real or wholly past the end of K, so a ragged split-K
    slice never mixes real tiles with clamped reads. The kernels and
    :func:`schedule_vmem_breakdown` both read ``d`` from here.
    """
    chunk = -(-k_tiles // split_k)
    budget = budgets.vmem_budget("pallas")
    for d in range(min(MAX_TILES_PER_STEP, chunk), 1, -1):
        if k_tiles % d or chunk % d:
            continue
        bd = _vmem_breakdown(d, m_tb, k_tb, n_tb, split_k, max_nnz, group,
                             b_dtype_bytes, out_dtype_bytes)
        if bd.total_bytes <= budget:
            return d
    return 1


def schedule_vmem_breakdown(m_tb: int, k_tb: int, n_tb: int, split_k: int, *,
                            k_tiles: int, max_nnz: int, group: int = 1,
                            b_dtype_bytes: int = 4,
                            out_dtype_bytes: int = 4) -> VmemBreakdown:
    """Model the VMEM-resident bytes of one LSCD SpMM launch.

    Mirrors the BlockSpecs in ``kernels/spmm.py`` exactly: a grid step
    covers ``d`` K tiles (:func:`tiles_per_step`), so the A stream is ``d``
    tiles' packed words ``[d, slots, k_tb]`` (uint32, ``slots * k_tb ==
    max_nnz``; VMEM pads the lane dim to 128) and B a ``[d * k_tb, n_tb]``
    block; the expansion builds one dense ``[m_tb, k_tb]`` f32 tile at a
    time plus its B-dtype copy; the output block is ``[group, m_tb, n_tb]``
    (f32 partials ``[1, (group,) m_tb, n_tb]`` for split-K pass 1), the
    accumulator scratch is f32 ``[group, m_tb, n_tb]``. In/out blocks are
    charged at ``DOUBLE_BUFFER`` x for the grid pipeline; scratch at 1x.
    For split-K the reduce kernel's ``[split_k, group, m_tb, n_tb]`` f32
    input block is modeled too and the reported total is the max of the
    two launches.
    """
    d = tiles_per_step(k_tiles, split_k, m_tb=m_tb, k_tb=k_tb, n_tb=n_tb,
                       max_nnz=max_nnz, group=group,
                       b_dtype_bytes=b_dtype_bytes,
                       out_dtype_bytes=out_dtype_bytes)
    return _vmem_breakdown(d, m_tb, k_tb, n_tb, split_k, max_nnz, group,
                           b_dtype_bytes, out_dtype_bytes)


def check_schedule(m: int, k: int, n: int, *, m_tb: int, k_tb: int,
                   n_tb: int, split_k: int, group: int = 1,
                   max_nnz: Optional[int] = None, sparsity: float = 0.0,
                   backend: str = "pallas", b_dtype_bytes: int = 4,
                   out_dtype_bytes: int = 4,
                   path: str = "schedule") -> List[Finding]:
    """Validate one launch schedule; returns findings (empty == valid).

    Rules: KC-LOC, KC-GRID, KC-SPLIT, KC-NTB, KC-VMEM (see module doc).
    ``path`` labels the findings (e.g. ``select(m,k,n)`` or a bench cell).
    """
    out: List[Finding] = []
    if not tile_loc_ok(m_tb):
        out.append(Finding(
            "KC-LOC", path, 0,
            f"tile height m_tb={m_tb} does not fit the 16-bit row field "
            f"(at most {MAX_TILE_ROWS - 1} rows)",
            hint=f"shrink m_tb below {MAX_TILE_ROWS}"))
    if m_tb < 1 or k_tb < 1 or m % m_tb or k % k_tb:
        out.append(Finding(
            "KC-GRID", path, 0,
            f"dense dims (M={m}, K={k}) not tiled evenly by "
            f"(m_tb={m_tb}, k_tb={k_tb})",
            hint="encode pads M/K to the tile multiple; pick a dividing "
                 "geometry or re-encode"))
    if n_tb < SUBLANE_QUANTUM or n_tb % SUBLANE_QUANTUM or n_tb > LANE_WIDTH:
        out.append(Finding(
            "KC-NTB", path, 0,
            f"n_tb={n_tb} is not a multiple of {SUBLANE_QUANTUM} in "
            f"[{SUBLANE_QUANTUM}, {LANE_WIDTH}]",
            hint="use the N_TB_LADDER values (8..128)"))
    elif backend == "pallas" and n_tb != LANE_WIDTH and n > n_tb:
        out.append(Finding(
            "KC-NTB", path, 0,
            f"n_tb={n_tb} splits N={n} into blocks narrower than the "
            f"{LANE_WIDTH} lanes, which Mosaic cannot tile",
            hint=f"use n_tb={LANE_WIDTH}, or one N tile >= N"))
    kt = -(-k // k_tb) if k_tb >= 1 else 0
    if split_k < 1 or (kt and split_k > kt):
        out.append(Finding(
            "KC-SPLIT", path, 0,
            f"split_k={split_k} outside [1, Kt={kt}] for K={k}, k_tb={k_tb}",
            hint="cap split_k at the K tile count"))
    budget = budgets.vmem_budget(backend)
    if budget is not None and not out:
        if max_nnz is None:   # the DESIGN.md §4 bound the roofline uses
            max_nnz = roofline.analytic_max_nnz(
                m_tb, k_tb, sparsity,
                columns=max(1, group) * -(-m // m_tb) * kt * k_tb)
        bd = schedule_vmem_breakdown(
            m_tb, k_tb, n_tb, split_k, k_tiles=kt, group=group,
            max_nnz=max_nnz,
            b_dtype_bytes=b_dtype_bytes,
            out_dtype_bytes=out_dtype_bytes)
        if bd.total_bytes > budget:
            which = ("reduce kernel" if bd.reduce_bytes > bd.main_bytes
                     else "compute kernel")
            out.append(Finding(
                "KC-VMEM", path, 0,
                f"{which} VMEM footprint {bd.total_bytes} B exceeds the "
                f"{backend} budget {budget} B (schedule m_tb={m_tb} "
                f"k_tb={k_tb} n_tb={n_tb} split_k={split_k} group={group})",
                hint="lower n_tb or split_k; the split-K reduce block is "
                     "split_k*group*m_tb*n_tb floats"))
    return out


def require_schedule(m: int, k: int, n: int, *, m_tb: int, k_tb: int,
                     n_tb: int, split_k: int, group: int = 1,
                     max_nnz: Optional[int] = None, sparsity: float = 0.0,
                     backend: str = "pallas", b_dtype_bytes: int = 4,
                     out_dtype_bytes: int = 4,
                     path: str = "schedule") -> None:
    """Raise :class:`ScheduleContractError` if the schedule is invalid."""
    found = check_schedule(
        m, k, n, m_tb=m_tb, k_tb=k_tb, n_tb=n_tb, split_k=split_k,
        group=group, max_nnz=max_nnz, sparsity=sparsity, backend=backend,
        b_dtype_bytes=b_dtype_bytes, out_dtype_bytes=out_dtype_bytes,
        path=path)
    if found:
        raise ScheduleContractError(found)


# ---------------------------------------------------------------------------
# source-level kernel contracts (KC-ACC, KC-OUT)
# ---------------------------------------------------------------------------

_F32_NAMES = {"float32"}


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``a.b.c`` -> "a.b.c")."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_f32(node: ast.AST) -> bool:
    d = _dotted(node)
    return d.rsplit(".", 1)[-1] in _F32_NAMES


def check_kernel_source(path: str, source: Optional[str] = None
                        ) -> List[Finding]:
    """KC-ACC over one kernel file: every ``pltpu.VMEM(shape, dtype)``
    scratch allocation and every ``preferred_element_type=`` keyword must
    name float32. Anything else silently truncates the K-loop accumulation.
    """
    if source is None:
        with open(path) as f:
            source = f.read()
    rel = os.path.relpath(path) if os.path.isabs(path) else path
    tree = ast.parse(source, filename=path)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee.endswith("VMEM") and len(node.args) >= 2:
            if not _is_f32(node.args[1]):
                out.append(Finding(
                    "KC-ACC", rel, node.lineno,
                    f"VMEM scratch dtype {ast.unparse(node.args[1])!r} "
                    f"is not float32",
                    hint="accumulate in f32; cast at the flush"))
        for kw in node.keywords:
            if kw.arg == "preferred_element_type" and not _is_f32(kw.value):
                out.append(Finding(
                    "KC-ACC", rel, node.lineno,
                    f"preferred_element_type "
                    f"{ast.unparse(kw.value)!r} is not float32",
                    hint="MXU accumulation must request f32"))
    return out


#: sparse_linear entry -> the declared-out keyword it requires.
_DECLARED_OUT_KW = {"linear": "declared_out", "linear_grouped": "declared_outs"}


def check_declared_out(path: str, source: Optional[str] = None
                       ) -> List[Finding]:
    """KC-OUT over one model file: ``sparse_linear.linear`` /
    ``linear_grouped`` call sites must pass ``declared_out`` /
    ``declared_outs`` — the encode-time M padding is sliced off by the
    callee only when the caller declares the true output dim."""
    if source is None:
        with open(path) as f:
            source = f.read()
    rel = os.path.relpath(path) if os.path.isabs(path) else path
    tree = ast.parse(source, filename=path)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        base = callee.rsplit(".", 1)[-1]
        if base not in _DECLARED_OUT_KW or "sparse_linear" not in callee:
            continue
        want = _DECLARED_OUT_KW[base]
        kws = {kw.arg for kw in node.keywords}
        if want not in kws and None not in kws:   # None == **kwargs splat
            out.append(Finding(
                "KC-OUT", rel, node.lineno,
                f"{callee}(...) call without {want}=",
                hint=f"pass {want} so the padded out dim is sliced to the "
                     f"true feature size"))
    return out


def kernel_source_files(repo_root: str) -> Tuple[List[str], List[str]]:
    """(kernel files for KC-ACC, model files for KC-OUT) under ``repo_root``."""
    kern_dir = os.path.join(repo_root, "src", "repro", "kernels")
    kern = [os.path.join(kern_dir, f) for f in ("spmm.py", "gemm.py")]
    model_dir = os.path.join(repo_root, "src", "repro", "models")
    models = sorted(
        os.path.join(model_dir, f) for f in os.listdir(model_dir)
        if f.endswith(".py"))
    return [p for p in kern if os.path.exists(p)], models
