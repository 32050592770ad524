"""Unit tests of the chip benchmark's yardstick: traffic, trace reduction,
metric arithmetic, discovery by name, and the refusal to run off the chip."""

from __future__ import annotations

import collections
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench_tiny import ROOT

from chipbench import flops, harness, peaks, trace_reduce, traffic

CHAT = traffic.load("chat")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def _sched(seed, rate=4.0):
    return traffic.schedule(CHAT, rate=rate, seed=seed, vocab=50272,
                            phases=[("warmup", 5.0), ("window", 20.0),
                                    ("tail", 3.0)])


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 11, 3 * 2 ** 32 + 5])
def test_traffic_is_byte_identical_for_a_seed(seed):
    assert traffic.fingerprint(_sched(seed)) == traffic.fingerprint(
        _sched(seed))


def test_traffic_seeds_permute_the_same_work():
    a, b = _sched(1), _sched(2)
    assert traffic.fingerprint(a) != traffic.fingerprint(b)
    for phase in ("warmup", "window", "tail"):
        ra = [r for r in a if r.phase == phase]
        rb = [r for r in b if r.phase == phase]
        assert len(ra) == len(rb)
        assert sorted(len(r.prompt) for r in ra) == sorted(
            len(r.prompt) for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
        ga = np.sort(np.diff([r.due for r in ra]))
        gb = np.sort(np.diff([r.due for r in rb]))
        assert ga.sum() == pytest.approx(gb.sum(), rel=0.2)
    win = [r for r in a if r.phase == "window"]
    assert len(win) == 80
    assert all(5.0 <= r.due < 25.0 for r in win)
    assert all(20 <= len(r.prompt) <= 170 and 88 <= r.max_new <= 341
               for r in a)


def test_chat_mix_matches_its_published_means_and_fits_max_len():
    """LMSYS-Chat-1M (arXiv:2309.11998, Table 1): 69.5 tokens per prompt,
    214.5 per response; the longest pair fits the cell's max_len."""
    assert "2309.11998" in CHAT["source"]
    for n in (51, 1000):
        assert traffic.lengths(CHAT["prompt_len"], n).mean() == pytest.approx(
            69.5, rel=0.01)
        assert traffic.lengths(CHAT["output_len"], n).mean() == pytest.approx(
            214.5, rel=0.01)
    cell = harness.load_cell("opt30b-4l-s80.chat")
    assert (CHAT["prompt_len"]["hi"] + CHAT["output_len"]["hi"]
            < cell.params["max_len"])


def test_length_quantiles():
    u = traffic.lengths({"dist": "uniform", "lo": 16, "hi": 64}, 1000)
    assert u.min() == 16 and u.max() == 64
    assert u.mean() == pytest.approx(40.0, abs=0.6)
    lu = traffic.lengths({"dist": "log_uniform", "lo": 256, "hi": 1024},
                         1000)
    assert lu.min() == 256 and lu.max() == 1024
    assert np.median(lu) == pytest.approx(512, rel=0.02)


# ---------------------------------------------------------------------------
# trace reduction (a small recorded timeline in the profiler's shape)
# ---------------------------------------------------------------------------

Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


K3 = ('%lscd_spmm.3 = bf16[128,64]{1,0:T(8,128)(2,1)S(1)} custom-call('
      'u32[4,4,8,128]{3,2,1,0:T(8,128)} %w, bf16[128,64]{1,0:T(8,128)S(1)} '
      '%x), custom_call_target="tpu_custom_call", operand_layout_constraints'
      '={u32[4,4,8,128]{3,2,1,0}}')
ALLOC = ('%custom-call.1 = bf16[8,128]{1,0} custom-call(), '
         'custom_call_target="AllocateBuffer"')
LOOP = '%while.5 = (s32[], bf16[64,8]) while((s32[], bf16[64,8]) %t)'


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("chipbench.window", 1000, 10000, []),
        Ev("chipbench.step", 1000, 4000, []),
        Ev("chipbench.wait", 5000, 3000, []),
        Ev("chipbench.step", 8000, 3000, []),
        Ev("unrelated", 0, 20000, []),
    ])])
    ops = [Ev("%fusion.1 = f32[8] fusion()", 500, 1500, []),
           Ev(LOOP, 2000, 2500, []),
           Ev(K3, 2000, 2000, []),
           Ev(ALLOC, 2100, 10, []),
           Ev("%fusion.2 = f32[8] fusion()", 3500, 1000, []),
           Ev(K3, 9000, 1000, []),
           Ev("%fusion.9 = f32[8] fusion()", 10500, 2000, [])]
    modules = [Ev("jit_decode(1)", 400, 4200, []),
               Ev("jit_decode(1)", 8900, 1200, []),
               Ev("jit_other(2)", 10400, 2200, [])]
    dev = Plane("/device:TPU:0", [Line("Steps", [Ev("1", 0, 20000, [])]),
                                  Line("XLA Modules", modules),
                                  Line("XLA Ops", ops)])
    return [host, dev]


def test_trace_reduction_on_a_small_timeline():
    r = trace_reduce.reduce_planes(_planes())
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(10000e-9)
    # busy inside [1000, 11000): 1000-4500, 9000-10000, 10500-11000
    assert r["busy_s"] == pytest.approx(5000e-9)
    assert r["pallas_s"] == pytest.approx(3000e-9)
    assert r["device_ops"][0] == ["lscd_spmm.3", pytest.approx(3000e-9)]
    assert "while.5" not in dict(r["device_ops"])
    # the kernel's words are in HBM, its activation and result on chip
    words = 4 * 4 * 8 * 128 * 4
    assert r["kernels"] == {"jit_decode(1)": {
        "runs": 2, "seconds": pytest.approx(3000e-9),
        "hbm_bytes": 2 * words,
        "families": {"lscd_spmm": {"calls": 2,
                                   "seconds": pytest.approx(3000e-9),
                                   "hbm_bytes": 2 * words}}}}
    # idle gaps: 4500-9000 (host waiting mostly), 10000-10500 (step)
    assert r["idle_gaps"][0] == ["wait", pytest.approx(4500e-9)]
    assert r["idle_gaps"][1] == ["step", pytest.approx(500e-9)]


def test_trace_reduction_of_a_recorded_chip_window():
    """0.5 s of a traced opt30b-4l-s80.chat window on a TPU v5e: four
    decode steps of 64 slots through the LSCD kernels."""
    r = trace_reduce.reduce_file(os.path.join(
        os.path.dirname(__file__), "data", "opt_chat_window.xplane.pb"))
    assert r["window_s"] == pytest.approx(0.5)
    assert 0.45 < r["busy_s"] <= r["window_s"]
    assert 0.2 < r["pallas_s"] < r["busy_s"]
    assert r["device_ops"][0][0].startswith("lscd_spmm")
    assert {g[0] for g in r["idle_gaps"]} <= {"step", "submit", "wait",
                                               "host"}
    (decode,) = r["kernels"].values()
    assert decode["runs"] == 4
    share = harness.load_reader("lscd_decode_roofline")(
        {"trace": r, "device_kind": "TPU v5 lite"})
    assert 1.0 < share < 100.0


# The recorded window's per-layer readings before the trace was split by
# kernel family: the split adds to the reduction and changes none of them.
RECORDED_READINGS = {"lscd_decode_roofline": 5.77372154606043,
                     "pallas_busy_share": 57.40404851429859,
                     "device_idle_share": 2.6134203999999994,
                     "step_mfu": 4.169890473885949}


def test_kernel_families_of_the_recorded_window_sum_to_their_program():
    r = trace_reduce.reduce_file(os.path.join(
        os.path.dirname(__file__), "data", "opt_chat_window.xplane.pb"))
    for prog in r["kernels"].values():
        fams = prog["families"]
        assert sum(f["seconds"] for f in fams.values()) == pytest.approx(
            prog["seconds"], rel=1e-12)
        assert sum(f["hbm_bytes"] for f in fams.values()) == prog[
            "hbm_bytes"]
        assert all(f["calls"] > 0 and f["seconds"] > 0
                   for f in fams.values())
    (decode,) = r["kernels"].values()
    assert {"lscd_spmm", "lscd_spmm_splitk",
            "lscd_spmm_splitk_grouped"} <= set(decode["families"])
    assert not any(trace_reduce._SUFFIX.search(f) for f in decode["families"])
    rec = {"trace": r, "device_kind": "TPU v5 lite", "required_flops": 4e12}
    for name, want in RECORDED_READINGS.items():
        assert harness.load_reader(name)(rec) == want, name


def test_trace_reduction_finds_nothing_without_a_window_or_device():
    planes = _planes()
    assert trace_reduce.reduce_planes(planes[1:]) is None
    assert trace_reduce.reduce_planes(planes[:1]) is None


def test_union_of_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


# ---------------------------------------------------------------------------
# metric arithmetic on a fixed step log
# ---------------------------------------------------------------------------

def _record():
    model = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv": 4,
             "d_ff": 256, "vocab": 512, "mlp_kind": "gelu",
             "mlp_bias": True, "qkv_bias": True, "norm_kind": "layernorm",
             "sparsity": 0.8}
    return {"window_s": 2.0, "tokens": 100, "steps_in_window": 40,
            "decode_launches": 32, "host_step_s": 1.8,
            "itl_s": [0.1] * 10 + [0.2] * 10,
            "delta": {"slot_steps": 160.0, "active_slot_steps": 120.0,
                      "admit_time_s": 0.5, "decode_time_s": 1.2,
                      "prefill_tokens": 250.0},
            "required_flops": 3.94e12, "weight_bytes": 3 * 2 ** 29,
            "device_kind": "TPU v5 lite", "model": model,
            "trace": {"window_s": 2.0, "busy_s": 1.5, "pallas_s": 0.9,
                      "kernels": {"jit_decode(1)": {
                          "runs": 30, "seconds": 1.0,
                          "hbm_bytes": 40.95e9},
                          "jit_prefill(2)": {"runs": 2, "seconds": 0.5,
                                             "hbm_bytes": 1e9}}}}


@pytest.mark.parametrize("name,want", [
    ("batch_occupancy", 75.0),
    ("host_ms_per_step", 1e3 * (1.8 - 0.5 - 1.2) / 40),
    ("itl_tail_p95_ms", 200.0),
    ("decode_ms", 1e3 * 1.2 / 32),
    ("prefill_ms_per_token", 1e3 * 0.5 / 250),
    ("device_idle_share", 25.0),
    ("pallas_busy_share", 60.0),
    ("step_mfu", 100.0 * 3.94e12 / (1.5 * 197e12)),
    ("weight_gib", 1.5),
    ("lscd_decode_roofline", 5.0),
])
def test_metric_reader(name, want):
    assert harness.load_reader(name)(_record()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["device_idle_share", "pallas_busy_share",
                                  "lscd_decode_roofline", "step_mfu"])
def test_trace_metrics_are_silent_without_a_trace(name):
    rec = dict(_record(), trace=None)
    assert harness.load_reader(name)(rec) is None


def test_every_listed_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_required_flops_count_kept_weights_only():
    m = _record()["model"]
    kept = sum(round(a * b * 0.2) for a, b in
               [(128, 128)] * 4 + [(256, 128), (128, 256)])
    assert flops.projection_flops(m) == 2 * kept * 2
    assert flops.decode_flops(m, 10) == (2 * kept * 2 + 4 * 128 * 10 * 2
                                         + 2 * 512 * 128)
    assert flops.prefill_flops(m, 3) == (3 * 2 * kept * 2
                                         + 4 * 128 * 6 * 2 + 2 * 512 * 128)


# The opt30b-4l-s80 cell's per-layer layout and required operations,
# computed before configurations could name their own reference.
OPT_LAYER_SHAPES = {
    "attn.wq.w": (7168, 7168), "attn.wk.w": (7168, 7168),
    "attn.wv.w": (7168, 7168), "attn.wo.w": (7168, 7168),
    "attn.wq.b": (7168,), "attn.wk.b": (7168,), "attn.wv.b": (7168,),
    "mlp.up.w": (28672, 7168), "mlp.down.w": (7168, 28672),
    "mlp.up.b": (28672,), "mlp.down.b": (7168,),
    "pre_norm.scale": (7168,), "pre_norm.bias": (7168,),
    "mlp_norm.scale": (7168,), "mlp_norm.bias": (7168,)}
OPT_PROJECTION_FLOPS = 986500304.0
OPT_DECODE_FLOPS_300 = 1741606096.0
OPT_PREFILL_FLOPS_100 = 99949904192.0


def test_opt_cell_keeps_its_reference_layout_and_required_flops():
    cell = harness.load_cell("opt30b-4l-s80.chat")
    assert "reference" not in cell.config
    assert cell.reference.__file__ == os.path.join(ROOT, "chipbench",
                                                   "reference.py")
    m = cell.model
    assert cell.reference.layer_shapes(m) == OPT_LAYER_SHAPES
    harness.check_layout(cell, harness._param_shapes(
        harness.model_config(cell)))
    for ref in ((), (cell.reference,)):
        assert flops.projection_flops(m, *ref) == OPT_PROJECTION_FLOPS
        assert flops.decode_flops(m, 300, *ref) == OPT_DECODE_FLOPS_300
        assert flops.prefill_flops(m, 100, *ref) == OPT_PREFILL_FLOPS_100


def test_peaks_refuse_an_unknown_chip():
    assert peaks.for_kind("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


# ---------------------------------------------------------------------------
# found by name: a new configuration, mix or metric is a new file
# ---------------------------------------------------------------------------

def _copy(tmp_path):
    """The benchmark's files in ``tmp_path``: (chipbench dir, bench)."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return tmp_path / "chipbench", json.load(f)


def test_new_config_mix_and_metric_are_found_without_edits(tmp_path):
    pkg, bench = _copy(tmp_path)
    (pkg / "configs" / "newmodel.json").write_text(json.dumps(
        {"model": {"n_layers": 1}, "sparsity": 0.5, "weight_seed": 3}))
    (pkg / "traffic" / "bursty.json").write_text(json.dumps(
        {"prompt_len": {"dist": "uniform", "lo": 4, "hi": 8},
         "output_len": {"dist": "uniform", "lo": 1, "hi": 2}}))
    (pkg / "cells" / "newmodel.bursty.json").write_text(json.dumps(
        {"n_slots": 2, "rate_per_s": 1.0}))
    (pkg / "metrics" / "queue_wait_ms.py").write_text(
        "def read(rec):\n    return 7.0\n")
    bench["workloads"].append({"name": "newmodel.bursty",
                               "config": "newmodel", "traffic": "bursty",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "scheduler",
                               "moves": "ttft_p50_ms",
                               "workloads": ["newmodel.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("newmodel.bursty", root=str(tmp_path))
    assert cell.config["sparsity"] == 0.5
    assert cell.traffic["prompt_len"]["hi"] == 8
    assert cell.params["n_slots"] == 2
    assert "queue_wait_ms" in [m["name"] for m in cell.per_layer]
    assert harness.load_reader("queue_wait_ms", root=str(tmp_path))({}) == 7.0
    other = harness.load_cell("opt30b-4l-s80.chat", root=str(tmp_path))
    assert "queue_wait_ms" not in [m["name"] for m in other.per_layer]

    # A later program's new counter, gauge, span and kernel reach a new reader
    # through the harness's own record, trace reduction and span record.
    (pkg / "metrics" / "expert_probe.py").write_text(EXPERT_READER)
    rec = harness.window_record(cell, _run_with_new_mechanism())
    rec["trace"] = trace_reduce.reduce_planes(_expert_planes())
    got = harness.load_reader("expert_probe", root=str(tmp_path))(rec)
    assert got == (42.0, 61.0, 2, 3, pytest.approx(600e-9),
                   3 * 2 * 64 * 128 * 4)


# A reader of a counter, a gauge, a span and a kernel family that the
# program under test does not have yet.
EXPERT_READER = '''
from chipbench import program_spans


def read(rec):
    recs, t0, t1 = program_spans.records(rec)
    routes = [r for r in program_spans.spans(recs, "route")
              if t0 <= r["ts"] < t1]
    fam = [p["families"]["lscd_expert_grouped"]
           for p in rec["trace"]["kernels"].values()
           if "lscd_expert_grouped" in p["families"]]
    return (rec["delta"]["expert_tokens"],
            rec["counters"]["close"]["experts_resident"], len(routes),
            sum(f["calls"] for f in fam), sum(f["seconds"] for f in fam),
            sum(f["hbm_bytes"] for f in fam))
'''


def _run_with_new_mechanism():
    """A window log whose program has counters and a span this program
    lacks: the counter ``expert_tokens`` and the gauge ``experts_resident``
    in its metrics, ``route`` spans in its tracer."""
    import dataclasses
    from repro.obs.trace import Tracer
    from repro.serving.scheduler import SchedulerMetrics

    from chipbench import program_spans
    Metrics = dataclasses.make_dataclass(
        "Metrics", [("expert_tokens", int, 0), ("experts_resident", int, 0)],
        bases=(SchedulerMetrics,))
    clock = iter([10.5, 10.8, 12.0]).__next__
    tr = Tracer(clock=clock).enable()
    tr.span("step", "route", "engine", 10.1)
    tr.span("step", "route", "engine", 10.6)
    tr.span("step", "route", "engine", 11.5)
    return {"origin": 10.0, "requests": [], "end_t": 11.0, "steps": [],
            "lateness": [], "queue": {},
            "open": {"t": 10.0, "counters": harness.counters(
                Metrics(expert_tokens=8, experts_resident=64, steps=3))},
            "close": {"t": 11.0, "counters": harness.counters(
                Metrics(expert_tokens=50, experts_resident=61, steps=9))},
            "program": program_spans.program_record(tr, 10.0, 11.0)}


def _expert_planes():
    """A traced window in which one program runs a new kernel three
    times beside LSCD."""
    op = ('%lscd_expert_grouped.{} = bf16[64,128]{{1,0:T(8,128)(2,1)S(1)}} '
          'custom-call(u32[2,64,128]{{2,1,0}} %w), '
          'custom_call_target="tpu_custom_call"')
    host = Plane("/host:CPU", [Line("python", [
        Ev("chipbench.window", 0, 10000, [])])])
    ops = [Ev(op.format(7), 1000, 200, []), Ev(K3, 1300, 300, []),
           Ev(op.format(7), 2000, 200, []), Ev(op.format(12), 3000, 200, [])]
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_decode(1)", 900, 3000, [])]),
        Line("XLA Ops", ops)])
    return [host, dev]


# A reference for a family the default reference does not know: latent
# attention with a query bottleneck, routed experts stacked [E, out, in]
# (leaves ``weights`` has no rule for) and shared experts.
PROBE_REFERENCE = '''
import jax.numpy as jnp

from chipbench import weights

ROWS = [{"gap": 0.125, "tokens": 3, "agree": 2},
        {"gap": 0.5, "tokens": 4, "agree": 4}]
PROJECTION = 12345.0
ATTENTION = 100.0


def layer_shapes(m):
    d, h = m["d_model"], m["n_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    e, f = m["n_routed_experts"], m["d_expert"]
    fs = m["d_shared_expert"] * m["n_shared_experts"]
    return {"attn.w_dq.w": (qr, d), "attn.w_uq.w": (h * (dn + dr), qr),
            "attn.w_dkv.w": (kvr + dr, d),
            "attn.w_ukv.w": (h * (dn + dv), kvr), "attn.wo.w": (d, h * dv),
            "moe.router.w": (e, d), "moe.gate": (e, f, d),
            "moe.up": (e, f, d), "moe.down": (e, d, f),
            "moe.shared.gate.w": (fs, d), "moe.shared.up.w": (fs, d),
            "moe.shared.down.w": (d, fs),
            "pre_norm.scale": (d,), "mlp_norm.scale": (d,)}


def leaf(seed, name, layer, shape, dtype):
    return jnp.stack([weights.leaf(seed, f"{name}.{e}.w", layer, shape[1:],
                                   dtype) for e in range(shape[0])])


def projection_flops(m):
    return PROJECTION


def attention_flops(m, context):
    return ATTENTION * context


def gaps(m, seed, prompts, served, control=False):
    return [dict(r) for r in ROWS[:len(prompts)]]
'''
PROBE_MODEL = {"family": "moe", "n_layers": 2, "d_model": 128, "n_heads": 4,
               "n_kv": 4, "vocab": 512, "attn_kind": "mla",
               "q_lora_rank": 64, "kv_lora_rank": 32, "qk_nope_dim": 16,
               "qk_rope_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
               "top_k": 2, "d_expert": 32, "n_shared_experts": 2,
               "d_shared_expert": 64, "norm_kind": "rmsnorm",
               "tie_embeddings": True, "dtype": "bfloat16"}


def _probe_cell(tmp_path, model=PROBE_MODEL):
    pkg, bench = _copy(tmp_path)
    (pkg / "ref_probe.py").write_text(PROBE_REFERENCE)
    (pkg / "configs" / "probe.json").write_text(json.dumps(
        {"reference": "ref_probe", "model": model, "sparsity": 0.8,
         "weight_seed": 5}))
    (pkg / "cells" / "probe.chat.json").write_text(json.dumps(
        {"n_slots": 4, "max_len": 64, "block_size": 16, "n_blocks": 64,
         "backend": "xla", "rate_per_s": 1.0, "warmup_s": 1,
         "check_requests": 2, "gap_limit": 0.01}))
    bench["workloads"].append({"name": "probe.chat", "config": "probe",
                               "traffic": "chat", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.load_cell("probe.chat", root=str(tmp_path))


def _assert_encoded_as_the_rule_names(dense, served):
    """Exactly the leaves ``serve.should_sparsify`` names, whatever that rule
    is, are Tiled-CSL in the served tree: every other leaf is served as
    drawn, and the encoded matrices number the named ones (the reformat
    may group several named leaves into one)."""
    import math

    import jax
    from repro.core.tiled_csl import TiledCSL
    from repro.launch import serve

    def is_csl(x):
        return isinstance(x, TiledCSL)

    named = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(dense)[0]:
        if serve.should_sparsify(jax.tree_util.keystr(path)):
            named += math.prod(leaf.shape[:-2])
        else:
            np.testing.assert_array_equal(
                np.asarray(harness._dig(served, path), np.float32),
                np.asarray(leaf, np.float32))
    encoded = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            served, is_leaf=is_csl)[0]:
        if is_csl(leaf):
            encoded += math.prod(leaf.words.shape[:-4])
        else:
            assert not serve.should_sparsify(jax.tree_util.keystr(path))
    assert encoded == named > 0


def _probe_serves(tmp_path, monkeypatch, model):
    """Load, draw, layout check, reformat, checkpoint, check and FLOP count
    of the probe: (cell, dense tree, served tree)."""
    cell = _probe_cell(tmp_path, model)
    ref = cell.reference
    assert ref.__file__ == str(tmp_path / "chipbench" / "ref_probe.py")
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    cfg = harness.model_config(cell)
    dense = harness.dense_params(cell, harness._param_shapes(cfg))
    setup = {}
    params = harness.served_params(cell, cfg, setup)
    assert setup["checkpoint"] == "written"
    _assert_encoded_as_the_rule_names(dense, params)
    again = {}
    harness.served_params(cell, cfg, again)
    assert again["checkpoint"] == "hit"

    sample = [harness.Served(0.0, np.arange(5), 3, "window", tokens=[1, 2, 3]),
              harness.Served(0.0, np.arange(6), 4, "window",
                             tokens=[4, 5, 6, 7])]
    chk = harness.check(cell, sample)
    assert chk["rows"] == ref.ROWS
    assert (chk["gap"], chk["tokens"], chk["agree"]) == (0.5, 7, 6)

    from repro.serving.scheduler import SchedulerMetrics
    zero = harness.counters(SchedulerMetrics())
    req = harness.Served(0.05, np.arange(5), 3, "window",
                         times=[10.1, 10.2, 10.3], tokens=[1, 2, 3])
    rec = harness.window_record(cell, {
        "origin": 10.0, "open": {"t": 10.0, "counters": zero},
        "close": {"t": 11.0, "counters": zero}, "requests": [req],
        "end_t": 11.0, "steps": [], "lateness": [], "queue": {}})
    head = flops.head_flops(cell.model)
    assert rec["required_flops"] == (
        (5 * ref.PROJECTION + ref.ATTENTION * 15 + head)
        + (ref.PROJECTION + ref.ATTENTION * 6 + head)
        + (ref.PROJECTION + ref.ATTENTION * 7 + head))
    return cell, dense, params


def test_new_architecture_brings_its_own_reference(tmp_path, monkeypatch):
    """Load, draw, layout check, reformat, check and FLOP count of a model
    whose reference is new files only."""
    from repro.core.tiled_csl import TiledCSL

    from chipbench import weights
    _, dense, params = _probe_serves(tmp_path, monkeypatch, PROBE_MODEL)
    np.testing.assert_array_equal(
        np.asarray(dense["layers"]["moe"]["gate"][1, 3], np.float32),
        np.asarray(weights.leaf(5, "moe.gate.3.w", 1, (32, 128)), np.float32))
    assert isinstance(params["layers"]["moe"]["shared"]["down"]["w"],
                      TiledCSL)
    assert isinstance(params["layers"]["attn"]["wo"]["w"], TiledCSL)


def test_probe_passes_when_the_program_prunes_its_routed_experts(
        tmp_path, monkeypatch):
    """A program whose rule also prunes the [E, out, in] expert stacks of a
    list of layers (a stack whose layers differ is held so) serves the
    probe with no edit to the benchmark."""
    from repro.core.tiled_csl import TiledCSL
    from repro.launch import serve

    from chipbench import weights
    rule = serve.should_sparsify

    def experts_too(path):
        return rule(path) or ("['moe']" in path and path.endswith(
            ("['gate']", "['up']", "['down']")))

    monkeypatch.setattr(serve, "should_sparsify", experts_too)
    _, dense, params = _probe_serves(tmp_path, monkeypatch,
                                     dict(PROBE_MODEL, scan_layers=False))
    np.testing.assert_array_equal(
        np.asarray(dense["layers"][1]["moe"]["gate"][3], np.float32),
        np.asarray(weights.leaf(5, "moe.gate.3.w", 1, (32, 128)), np.float32))
    for layer in params["layers"]:
        for name in ("gate", "up", "down"):
            assert isinstance(layer["moe"][name], TiledCSL)
        assert not isinstance(layer["moe"]["router"]["w"], TiledCSL)


def test_layout_check_refuses_a_reference_that_disagrees(tmp_path):
    import dataclasses
    import types
    cell = _probe_cell(tmp_path)
    shapes = harness._param_shapes(harness.model_config(cell))
    good = cell.reference.layer_shapes(cell.model)
    per_layer = dataclasses.replace(cell, reference=types.SimpleNamespace(
        layer_shapes=lambda m: [good, good]))
    harness.check_layout(per_layer, shapes)
    wrong = dict(good, **{"moe.gate": (8, 128, 32)})
    for shapes_of in (lambda m: wrong, lambda m: [good, wrong],
                      lambda m: [good]):
        bad = dataclasses.replace(cell, reference=types.SimpleNamespace(
            layer_shapes=shapes_of))
        with pytest.raises(RuntimeError, match="layer"):
            harness.check_layout(bad, shapes)


# ---------------------------------------------------------------------------
# the command refuses to run without the chip or without the program
# ---------------------------------------------------------------------------

def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "opt30b-4l-s80.chat", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_on_a_cpu_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
