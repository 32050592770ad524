"""Static-analysis subsystem: kernel contracts, trace audit, AST lint.

Golden-file tests: each pass must catch its seeded violation class in the
``tests/fixtures/analysis/`` files with the right rule id, and the live
tree at HEAD must be clean. The VMEM-overflow injection tests pin the
ISSUE-6 acceptance criterion: an invalid schedule is rejected by
``schedule.select()`` before any ``pallas_call``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budgets, contracts, findings, lint, trace_audit
from repro.analysis.contracts import ScheduleContractError
from repro.core import roofline, tiled_csl
from repro.kernels import ops, schedule
from repro.kernels import spmm as spmm_mod

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _rules(fs, *, suppressed=False):
    return [f.rule for f in fs if f.suppressed == suppressed]


# ---------------------------------------------------------------------------
# kernel contracts (KC-*)
# ---------------------------------------------------------------------------

def test_loc_predicate_shared_with_encode():
    assert contracts.tile_loc_ok(128)
    assert contracts.tile_loc_ok(0xFFFE)
    assert not contracts.tile_loc_ok(0xFFFF)      # the padding row marker
    with pytest.raises(ValueError, match="16-bit row"):
        contracts.require_tile_loc(0xFFFF)
    # encode routes through the SAME predicate — same message, same bound
    with pytest.raises(ValueError, match="16-bit row"):
        tiled_csl.encode(np.zeros((0xFFFF, 1), np.float32), 0xFFFF, 1)
    assert _rules(contracts.check_schedule(
        0xFFFF, 128, 8, m_tb=0xFFFF, k_tb=128, n_tb=8, split_k=1)) == ["KC-LOC"]


def test_indivisible_grid_flagged():
    got = contracts.check_schedule(100, 256, 8, m_tb=128, k_tb=128,
                                   n_tb=8, split_k=1)
    assert _rules(got) == ["KC-GRID"]


@pytest.mark.parametrize("n,n_tb,backend,ok", [
    (24, 8, "pallas", False),      # three 8-wide N blocks: Mosaic refuses
    (24, 8, "interpret", True),    # the interpreter tiles anything
    (24, 32, "pallas", True),      # one N tile covering the padded N
    (8, 8, "pallas", True),        # decode: one exact N tile
    (512, 128, "pallas", True),    # full-lane blocks
    (512, 64, "pallas", False),
])
def test_ntb_lane_rule_on_pallas(n, n_tb, backend, ok):
    got = contracts.check_schedule(128, 256, n, m_tb=128, k_tb=128,
                                   n_tb=n_tb, split_k=1, backend=backend)
    assert _rules(got) == ([] if ok else ["KC-NTB"])


def test_split_bounds_flagged():
    kt2 = dict(m_tb=128, k_tb=128, n_tb=8)          # K=256 -> Kt=2
    assert _rules(contracts.check_schedule(
        128, 256, 8, split_k=0, **kt2)) == ["KC-SPLIT"]
    assert _rules(contracts.check_schedule(
        128, 256, 8, split_k=3, **kt2)) == ["KC-SPLIT"]


def test_lane_alignment_flagged():
    got = contracts.check_schedule(128, 256, 8, m_tb=128, k_tb=128,
                                   n_tb=7, split_k=1)
    assert _rules(got) == ["KC-NTB"]
    got = contracts.check_schedule(128, 256, 8, m_tb=128, k_tb=128,
                                   n_tb=256, split_k=1)
    assert _rules(got) == ["KC-NTB"]


def test_vmem_overflow_flagged_with_breakdown():
    # grouped split-K at S=64, G=2, n_tb=128: the reduce kernel's
    # [S, G, 128, 128] f32 input block alone is 16 MiB double-buffered
    got = contracts.check_schedule(8192, 8192, 128, m_tb=128, k_tb=128,
                                   n_tb=128, split_k=64, group=2,
                                   sparsity=0.8)
    assert _rules(got) == ["KC-VMEM"]
    assert "reduce kernel" in got[0].message
    bd = contracts.schedule_vmem_breakdown(
        128, 128, 128, 64, k_tiles=64, group=2,
        max_nnz=roofline.analytic_max_nnz(128, 128, 0.8,
                                          columns=2 * 64 * 64 * 128))
    assert bd.reduce_bytes > budgets.vmem_budget("pallas")
    assert bd.total_bytes == max(bd.main_bytes, bd.reduce_bytes)
    # the xla reference path has no VMEM contract
    assert contracts.check_schedule(8192, 8192, 128, m_tb=128, k_tb=128,
                                    n_tb=128, split_k=64, group=2,
                                    sparsity=0.8, backend="xla") == []


# OPT-30B's LSCD launches at 80% sparsity: 56 slots of 128 x 128 tiles,
# bf16 activations, N = 64 decode slots.
_OPT_LAUNCH = dict(m_tb=128, k_tb=128, n_tb=64, max_nnz=56 * 128,
                   b_dtype_bytes=2, out_dtype_bytes=2)


@pytest.mark.parametrize("kt,split_k,d", [
    (56, 1, 14),    # fc1 (single pass)
    (56, 4, 14),    # decode q/k/v and out: 14-tile slices
    (224, 4, 14),   # decode fc2: 56-tile slices
    (224, 1, 16),   # prefill fc2: the cap
    (17, 1, 1),     # no divisor up to the cap
    (3, 2, 1),      # ragged: 2-tile slices of an odd Kt
    (12, 5, 3),     # ragged: the last slice lies wholly past K
])
def test_tiles_per_step_divides_kt_and_slice(kt, split_k, d):
    """d is the largest divisor of both Kt and the slice's tile count up to
    the cap, and the VMEM model charges d tiles of words and a d*k_tb-row B
    block — the blocks the kernels launch."""
    assert contracts.tiles_per_step(kt, split_k, **_OPT_LAUNCH) == d
    bd = contracts.schedule_vmem_breakdown(
        128, 128, 64, split_k, k_tiles=kt, max_nnz=56 * 128,
        b_dtype_bytes=2, out_dtype_bytes=2)
    dbl = contracts.DOUBLE_BUFFER
    assert bd.words_bytes == 4 * d * 56 * 128 * dbl
    assert bd.b_block_bytes == d * 128 * 64 * 2 * dbl
    assert bd.expand_bytes == 128 * 128 * (4 + 2)   # one tile at a time


def test_tiles_per_step_steps_down_to_fit_vmem():
    """Dense 512 x 512 tiles carry 2 MiB of words each (double-buffered):
    16 of them would break KC-VMEM, so d steps down to the largest divisor
    of Kt that fits, and the launch passes the contract."""
    kw = dict(m_tb=512, k_tb=512, n_tb=128, max_nnz=512 * 512)
    d = contracts.tiles_per_step(16, 1, **kw)
    assert d == 4
    budget = budgets.vmem_budget("pallas")
    fits = contracts.schedule_vmem_breakdown(512, 512, 128, 1, k_tiles=16,
                                             max_nnz=512 * 512)
    assert fits.total_bytes <= budget
    assert contracts._vmem_breakdown(8, 512, 512, 128, 1, 512 * 512, 1, 4,
                                     4).total_bytes > budget
    assert contracts.check_schedule(512, 16 * 512, 128, m_tb=512, k_tb=512,
                                    n_tb=128, split_k=1,
                                    max_nnz=512 * 512) == []


def test_select_rejects_injected_vmem_overflow():
    """ISSUE-6 acceptance: an injected VMEM-overflow schedule is rejected
    by ``schedule.select()`` — before any pallas_call exists to fail."""
    with pytest.raises(ScheduleContractError) as ei:
        schedule.select(8192, 8192, 128, 0.8, m_tb=128, k_tb=128,
                        n_tb=128, split_k=64, group=2)
    assert "KC-VMEM" in {f.rule for f in ei.value.findings}
    # ScheduleContractError is a ValueError: existing callers' error
    # handling keeps working
    assert isinstance(ei.value, ValueError)


def test_select_ignores_poisoned_cache_entry(tmp_path):
    """A cache file carrying an unlaunchable winner (foreign machine,
    hand-edited, stale budget) silently falls back to the analytic pick."""
    cache = schedule.ScheduleCache(str(tmp_path / "poison.json"))
    key = schedule.cache_key(8192, 8192, 128, 0.8, group=2,
                             backend="pallas", m_tb=128, k_tb=128)
    cache.put(key, schedule.Schedule(128, 128, 128, 64))   # KC-VMEM at G=2
    got = schedule.select(8192, 8192, 128, 0.8, m_tb=128, k_tb=128,
                          group=2, cache=cache)
    assert got != schedule.Schedule(128, 128, 128, 64)
    assert contracts.check_schedule(
        8192, 8192, 128, m_tb=got.m_tb, k_tb=got.k_tb, n_tb=got.n_tb,
        split_k=got.split_k, group=2, sparsity=0.8) == []


def test_ops_dispatch_rejects_before_pallas_call(monkeypatch):
    """The grouped dispatch path refuses the injected overflow schedule
    inside select() — the kernel entry is never reached."""
    rng = np.random.default_rng(0)
    dense = rng.standard_normal((128, 8192)).astype(np.float32)
    dense[rng.random(dense.shape) < 0.8] = 0.0
    tg = tiled_csl.group_stack([tiled_csl.encode(dense),
                                tiled_csl.encode(dense)])
    called = []
    monkeypatch.setattr(
        spmm_mod, "lscd_spmm_splitk_grouped",
        lambda *a, **k: called.append(1))
    b = jnp.ones((8192, 128), jnp.float32)
    with pytest.raises(ScheduleContractError):
        ops.spmm_grouped(tg, b, backend="interpret", n_tb=128, split_k=64)
    assert not called


def test_kernel_entry_validates_directly():
    """Raw kernel entries are public: a hand-pinned invalid launch hits
    the same contract wall (KC-SPLIT here) without going through select."""
    rng = np.random.default_rng(1)
    dense = rng.standard_normal((128, 256)).astype(np.float32)
    dense[rng.random(dense.shape) < 0.8] = 0.0
    t = tiled_csl.encode(dense)                      # Kt = 2
    b = jnp.ones((256, 8), jnp.float32)
    with pytest.raises(ValueError, match="split_k"):
        spmm_mod.lscd_spmm_splitk(t, b, n_tb=8, split_k=5, interpret=True)


def test_autotune_never_times_or_persists_invalid(tmp_path):
    rng = np.random.default_rng(2)
    dense = rng.standard_normal((128, 256)).astype(np.float32)
    dense[rng.random(dense.shape) < 0.8] = 0.0
    t = tiled_csl.encode(dense)                      # Kt = 2
    cache = schedule.ScheduleCache(str(tmp_path / "tuned.json"))
    best, timings = schedule.autotune(t, 8, backend="interpret",
                                      cache=cache, reps=1, n_tbs=(8,),
                                      splits=(1, 5))   # 5 > Kt: filtered
    assert set(timings) == {schedule.Schedule(128, 128, 8, 1)}
    assert best.split_k == 1
    for ent in schedule.ScheduleCache(cache.path)._data.values():
        assert contracts.check_schedule(
            128, 256, 8, m_tb=ent["m_tb"], k_tb=ent["k_tb"],
            n_tb=ent["n_tb"], split_k=ent["split_k"],
            backend="interpret") == []


def test_bad_kernel_fixture_caught():
    got = contracts.check_kernel_source(
        os.path.join(FIXTURES, "bad_kernel.py"))
    assert _rules(got) == ["KC-ACC", "KC-ACC"]
    msgs = " ".join(f.message for f in got)
    assert "preferred_element_type" in msgs and "scratch" in msgs


def test_live_kernels_pass_source_checks():
    for path in contracts.kernel_source_files(REPO_ROOT)[0]:
        assert contracts.check_kernel_source(path) == []


def test_declared_out_checked():
    src = ("from repro.core import sparse_linear\n"
           "def f(w, x, b):\n"
           "    good = sparse_linear.linear(w, x, b, declared_out=4)\n"
           "    return sparse_linear.linear(w, x, b)\n")
    got = contracts.check_declared_out("snippet.py", src)
    assert _rules(got) == ["KC-OUT"]
    assert got[0].line == 4
    # live model tree is clean
    for path in contracts.kernel_source_files(REPO_ROOT)[1]:
        assert contracts.check_declared_out(path) == []


# ---------------------------------------------------------------------------
# trace auditor (TA-*)
# ---------------------------------------------------------------------------

def test_retracing_entry_point_caught():
    """A deliberately shape-polymorphic fn driven at two shapes blows the
    one-entry budget of a step function."""
    entry = trace_audit.EntryPoint(
        "engine_decode_step",                       # budget: 1 shape
        lambda: (lambda x: x * 2.0,
                 [(jnp.zeros((8,)),), (jnp.zeros((16,)),)]))
    got = trace_audit.audit_entry(entry)
    assert _rules(got) == ["TA-RETRACE"]
    assert "budget of 1" in got[0].message


def test_within_budget_entry_clean():
    entry = trace_audit.EntryPoint(
        "engine_decode_step",
        lambda: (lambda x: x * 2.0, [(jnp.zeros((8,)),)] * 3))
    assert trace_audit.audit_entry(entry) == []


def test_host_callback_caught():
    def noisy(x):
        jax.debug.print("x = {}", x)                # host callback
        return x + 1

    got = trace_audit.audit_jaxpr(jax.make_jaxpr(noisy)(jnp.ones(4)),
                                  "trace:test")
    assert "TA-CALLBACK" in _rules(got)


def test_large_upcast_caught_small_ignored():
    big = jnp.zeros((256, 256), jnp.bfloat16)       # 65536 elems
    small = jnp.zeros((8, 8), jnp.bfloat16)
    up = lambda x: x.astype(jnp.float32) * 2
    got = trace_audit.audit_jaxpr(jax.make_jaxpr(up)(big), "trace:test")
    assert _rules(got) == ["TA-UPCAST"]
    assert "(256, 256)" in got[0].message
    assert trace_audit.audit_jaxpr(jax.make_jaxpr(up)(small),
                                   "trace:test") == []


def test_pallas_kernel_bodies_not_audited():
    """The f32 accumulator *inside* a kernel is the KC-ACC requirement;
    the upcast rule must not recurse into pallas_call jaxprs."""
    rng = np.random.default_rng(3)
    dense = rng.standard_normal((256, 256)).astype(np.float32)
    dense[rng.random(dense.shape) < 0.8] = 0.0
    t = tiled_csl.encode(dense)
    b = jnp.ones((256, 8), jnp.bfloat16)
    jx = jax.make_jaxpr(
        lambda b_: ops.spmm(t, b_, backend="interpret"))(b)
    assert [f for f in trace_audit.audit_jaxpr(jx, "trace:test")
            if f.rule == "TA-UPCAST"] == []


def test_compile_budget_table():
    # the shared bound test_serving asserts: ceil(log2(max_len))
    assert budgets.compile_budget("batcher_prefill", max_len=32) == 5
    assert budgets.compile_budget("batcher_prefill", max_len=1) == 1
    assert budgets.compile_budget("engine_decode_step") == 1
    with pytest.raises(KeyError):
        budgets.compile_budget("unregistered_entry")


def test_vmem_budget_table():
    assert budgets.vmem_budget("pallas") == 14 * 2 ** 20
    assert budgets.vmem_budget("interpret") == budgets.vmem_budget("pallas")
    assert budgets.vmem_budget("xla") is None
    # unknown backends default to the strict budget, not to unconstrained
    assert budgets.vmem_budget("future_backend") == \
        budgets.vmem_budget("pallas")


# ---------------------------------------------------------------------------
# AST lint (PK-*, PY-*)
# ---------------------------------------------------------------------------

def test_bad_keys_fixture_caught():
    got = lint.lint_file(os.path.join(FIXTURES, "bad_keys.py"),
                         serving=True)
    assert sorted(_rules(got)) == ["PK-FRESH", "PK-REUSE", "PK-SPLIT"]
    assert _rules(got, suppressed=True) == ["PK-REUSE"]   # inline ignore
    by_rule = {f.rule: f for f in got if not f.suppressed}
    assert "fold" in by_rule["PK-SPLIT"].hint


def test_bad_branch_fixture_caught():
    got = lint.lint_file(os.path.join(FIXTURES, "bad_branch.py"),
                         serving=False)
    assert sorted(_rules(got)) == ["PY-DICT-MUT", "PY-MUT-DEFAULT",
                                   "PY-TRACED-BRANCH", "PY-TRACED-BRANCH"]


def test_bad_swallow_fixture_caught():
    got = lint.lint_file(os.path.join(FIXTURES, "bad_swallow.py"),
                         serving=True)
    # four swallows live, the inline-ignored one suppressed; the
    # recorded / re-raising / narrow handlers stay clean
    assert _rules(got) == ["PY-SWALLOW"] * 4
    assert _rules(got, suppressed=True) == ["PY-SWALLOW"]
    assert all("record" in f.hint for f in got)


def test_swallow_rule_scoped_to_serving():
    src = ("def f(step):\n"
           "    try:\n"
           "        return step()\n"
           "    except Exception:\n"
           "        return None\n")
    assert lint.lint_file("models_like.py", serving=False, source=src) == []
    assert _rules(lint.lint_file("serving_like.py", serving=True,
                                 source=src)) == ["PY-SWALLOW"]


def test_key_rules_scoped_to_serving():
    src = ("import jax\n"
           "def init(keys):\n"
           "    out = []\n"
           "    for k in keys:\n"
           "        key, sub = jax.random.split(k)\n"
           "        out.append(sub)\n"
           "    return out\n")
    # models/ init-time key fan-out is fine...
    assert lint.lint_file("models_like.py", serving=False, source=src) == []
    # ...the same pattern in serving/ is the PK-SPLIT violation
    assert _rules(lint.lint_file("serving_like.py", serving=True,
                                 source=src)) == ["PK-SPLIT"]


def test_isinstance_branch_not_flagged():
    src = ("import jax.numpy as jnp\n"
           "def f(w):\n"
           "    if not isinstance(w, jnp.ndarray):\n"
           "        return w.words\n"
           "    return w\n")
    assert lint.lint_file("x.py", serving=False, source=src) == []


def test_bad_sync_fixture_caught():
    with open(os.path.join(FIXTURES, "bad_sync.py")) as f:
        src = f.read()
    # OB-SYNC scopes to the step module, so lint under its pseudo-path
    got = lint.lint_file("serving/step.py", serving=True, source=src)
    assert _rules(got) == ["OB-SYNC"] * 3
    msgs = [f.message for f in got if not f.suppressed]
    assert any("block_until_ready" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    assert any("_decode_step" in m for m in msgs)
    # the profiling-fence annotation and the generic inline ignore both
    # suppress, with distinct justifications
    sup = {f.justification for f in got if f.suppressed}
    assert sup == {"profiling-fence annotation", "inline ignore"}


def test_sync_rule_scoped_to_step_module():
    src = ("import jax\n"
           "def drain(x):\n"
           "    jax.block_until_ready(x)\n"
           "    return x\n")
    # a deliberate drain in batching.py (or anywhere else) is not the
    # step hot path — only step.py carries the async-launch contract
    assert lint.lint_file("serving/batching.py", serving=True,
                          source=src) == []
    assert _rules(lint.lint_file("serving/step.py", serving=True,
                                 source=src)) == ["OB-SYNC"]


def test_live_tree_lint_clean():
    assert [f for f in lint.lint_tree(REPO_ROOT) if not f.suppressed] == []


# ---------------------------------------------------------------------------
# findings / suppression model
# ---------------------------------------------------------------------------

def test_inline_ignore_covers_own_and_next_line():
    ig = findings.parse_inline_ignores(
        "x = 1\n# repro: ignore[KC-VMEM]\ny = 2  # repro: ignore[KC-LOC]\n")
    assert ig[2] == ("KC-VMEM",) and "KC-VMEM" in ig[3]
    assert "KC-LOC" in ig[3] and "KC-LOC" in ig[4]


def test_unregistered_rule_asserts():
    with pytest.raises(AssertionError):
        findings.Finding("NOT-A-RULE", "x.py", 1, "m")


def test_allowlist_suppresses_and_reports_stale():
    allow = findings.Allowlist([
        {"rule": "TA-UPCAST", "path": "trace:*", "reason": "f32 softmax"},
        {"rule": "KC-VMEM", "path": "never.py", "reason": "stale entry"},
        {"rule": "KC-LOC", "path": "x.py"},               # missing reason
    ])
    fs = allow.suppress([findings.Finding("TA-UPCAST", "trace:decode", 0,
                                          "bf16->f32 convert")])
    assert fs[0].suppressed and fs[0].justification == "f32 softmax"
    probs = allow.problems()
    assert any("stale" in p for p in probs)
    assert any("missing" in p for p in probs)
