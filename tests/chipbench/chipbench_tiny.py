"""A tiny cell of the chip benchmark that the CPU can run: the same harness,
traffic generator, reference and checks as the chip cells, at a size a test
holds (two layers, width 128, a 512-token vocabulary)."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODELS = {
    "gelu": {"family": "dense", "n_layers": 2, "d_model": 128,
             "n_heads": 4, "n_kv": 4, "d_ff": 256, "vocab": 512,
             "mlp_kind": "gelu", "mlp_bias": True, "qkv_bias": True,
             "norm_kind": "layernorm", "tie_embeddings": True,
             "rope_theta": 10000.0, "dtype": "bfloat16"},
    "swiglu": {"family": "dense", "n_layers": 2, "d_model": 128,
               "n_heads": 4, "n_kv": 2, "d_ff": 256, "vocab": 512,
               "mlp_kind": "swiglu", "qkv_bias": True,
               "norm_kind": "rmsnorm", "tie_embeddings": True,
               "rope_theta": 1e6, "dtype": "bfloat16"},
}
# The gelu model with its layers as a list of per-layer trees, as a
# non-uniform stack is held.
MODELS["gelu_list"] = dict(MODELS["gelu"], scan_layers=False)
TRAFFIC = {"prompt_len": {"dist": "log_uniform", "lo": 8, "hi": 32},
           "output_len": {"dist": "uniform", "lo": 8, "hi": 16}}
# Readings at this size (CPU, xla backend, seeds 11-13): program gaps
# 0-0.00036, float8 control gaps 0.030-0.047. The limit sits between.
GAP_LIMIT = 0.01


def cell(kind: str = "gelu", *, backend: str = "xla", rate: float = 2.0):
    from chipbench import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.chat", "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    params = {"n_slots": 4, "max_len": 64, "block_size": 16,
              "n_blocks": 64, "backend": backend, "rate_per_s": rate,
              "warmup_s": 1, "check_requests": 4, "gap_limit": GAP_LIMIT}
    conf = {"model": MODELS[kind], "sparsity": 0.8, "weight_seed": 7}
    return harness.load_cell("tiny.chat", bench=bench, config=conf,
                             traffic=TRAFFIC, params=params)
