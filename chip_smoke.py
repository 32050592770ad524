#!/usr/bin/env python3
"""Chip smoke: the serving path on one TPU at Qwen2-1.5B's published width.

    python chip_smoke.py                # one chip: dense and 80%-sparse serving
    python chip_smoke.py --four-chips   # 2x2 mesh: sharded train step vs one chip

A smoke run, not a benchmark: it proves that the system starts on the chip,
compiles its Pallas kernels there and answers correctly. The timings it
prints include compilation. It exits non-zero, printing no result, when
JAX's first device is not a TPU or when any phase fails. On success the last
line of standard output is one JSON object naming the device.

One chip (Qwen2-1.5B: 28 layers, d_model 1536, d_ff 8960, GQA 12/2, vocab
151936; random bf16 weights from ``--seed``):

* finding — time of the LSCD kernel (``spmm.lscd_spmm``, compare-select
  transform), of the one-hot MXU transform candidate (``onehot_spmm``) and
  of the dense Pallas GEMM at the down projection's shape;
* dense — 8 requests (prompts of 128-512 tokens, 32 new tokens each)
  through ``api.StreamingServer`` with a paged cache, 8 slots, max_len 1024;
* sparse — the same traffic on weights pruned to 80% and reformatted to
  Tiled-CSL (``pruning.sparsify_params`` + ``group_projections``), with
  ``backend="pallas"``; then one prefill and one decode step are compared
  with the ``backend="xla"`` reference on the same weights.

Four chips: three steps of the ``launch/train.py --mesh`` train step on a
2x2 (data x model) mesh at Qwen2-1.5B widths, depth cut so that the
one-chip train state fits, against the same steps on one chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch import compile_cache  # noqa: E402

SPARSITY = 0.8
N_REQUESTS = 8
PROMPT_LEN = (128, 512)
MAX_NEW = 32
SLOTS = 8
MAX_LEN = 1024
CHUNK = 128
# Sparse-vs-reference logits: both paths multiply the same bf16 weights and
# activations with f32 accumulation and differ in summation order and in
# where bf16 outputs round. Bound the max error by 2% of the largest
# reference logit.
PARITY_RTOL = 2e-2
# Four-chip phase: depth cut so params + AdamW moments + grads fit one chip.
TRAIN_LAYERS = 4
TRAIN_STEPS = 3
TRAIN_BATCH = 8
TRAIN_SEQ = 128
LOSS_ATOL = 1e-2
# Parity check: one prefill of this many tokens per row, then one decode.
PARITY_BATCH = 2
PARITY_LEN = 256


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _timed_us(fn, *args, reps: int = 10) -> float:
    import jax
    jax.block_until_ready(fn(*args))            # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def onehot_spmm(t, b, *, n_tb: int, interpret: bool = False):
    """The transform candidate that ``spmm.lscd_spmm`` was chosen over: the
    one-hot MXU expansion of the same column-slotted words. Slot ``s`` adds
    ``OneHot_s @ diag(v_s)`` to the dense tile, ``OneHot_s[i, c] = (row_s[c]
    == i)``, which costs 2·m_tb·k_tb·k_tb MXU FLOPs per slot. Kept only for
    the timing finding (no epilogue, split-K or empty-tile skip)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import tiled_csl

    m_tb, k_tb, slots, q = t.m_tb, t.k_tb, t.slots, tiled_csl.SLOT_QUANTUM
    (m, _), n = t.shape, b.shape[1]
    mt, kt = t.grid

    def kernel(words_ref, b_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        row_ids = jax.lax.broadcasted_iota(jnp.int32, (m_tb, k_tb), 0)
        diag = (jax.lax.broadcasted_iota(jnp.int32, (k_tb, k_tb), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (k_tb, k_tb), 1))

        def slab(i, a):
            w = words_ref[pl.ds(pl.multiple_of(i * q, q), q), :]
            rows = (w & 0xFFFF).astype(jnp.int32)
            vals = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                                jnp.float32)
            for j in range(q):
                hot = (row_ids == rows[j:j + 1]).astype(jnp.bfloat16)
                d = jnp.where(diag, vals[j:j + 1], 0.0).astype(jnp.bfloat16)
                a = a + jnp.dot(hot, d, preferred_element_type=jnp.float32)
            return a

        a = jax.lax.fori_loop(0, slots // q, slab,
                              jnp.zeros((m_tb, k_tb), jnp.float32))
        acc_ref[...] += jnp.dot(a.astype(b_ref.dtype), b_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == kt - 1)
        def _flush():
            o_ref[...] = acc_ref[...]

    return pl.pallas_call(
        kernel, grid=(mt, n // n_tb, kt),
        in_specs=[pl.BlockSpec((pl.squeezed, pl.squeezed, slots, k_tb),
                               lambda i, j, kk: (i, kk, 0, 0)),
                  pl.BlockSpec((k_tb, n_tb), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((m_tb, n_tb), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((m_tb, n_tb), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(t.words, b)


def transform_finding(m: int, k: int, *, seed: int) -> None:
    """The LSCD kernel's compare-select transform vs the one-hot MXU
    candidate vs the dense Pallas GEMM at one projection shape (a finding
    printed for the record, not a result)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import tiled_csl
    from repro.kernels import gemm, spmm

    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)).astype(np.float32)
    a[rng.random((m, k)) < SPARSITY] = 0.0
    t = tiled_csl.encode(jnp.asarray(a, jnp.bfloat16))
    a_dense = jnp.asarray(tiled_csl.decode(t), jnp.bfloat16)
    parts = []
    for n in (8, 512):
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)
        n_tb = min(n, 128)
        sparse = jax.jit(functools.partial(
            spmm.lscd_spmm, n_tb=n_tb, interpret=False))
        onehot = jax.jit(functools.partial(onehot_spmm, n_tb=n_tb))
        dense = jax.jit(functools.partial(
            gemm.dense_gemm, n_tb=n_tb, interpret=False))
        yd = np.asarray(dense(a_dense, b))
        for name, y in (("lscd_spmm", sparse(t, b)), ("one-hot", onehot(t, b))):
            err = float(np.max(np.abs(np.asarray(y) - yd)) / np.max(np.abs(yd)))
            _check(err < 1e-3, f"{name} vs dense_gemm at N={n}: rel err {err}")
        parts.append(f"N={n}: compare-select (lscd) "
                     f"{_timed_us(sparse, t, b):.1f} us, one-hot MXU "
                     f"{_timed_us(onehot, t, b):.1f} us, dense_gemm "
                     f"{_timed_us(dense, a_dense, b):.1f} us")
    print(f"finding: transform timing at {m}x{k}, {SPARSITY:.0%} sparse, "
          f"{t.slots} slots, {t.bytes_per_nonzero:.2f} B/nonzero "
          f"(dense bf16 {2 / (1 - SPARSITY):.2f}): " + "; ".join(parts),
          flush=True)


def make_prompts(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
            .astype(np.int64) for _ in range(N_REQUESTS)]


def serve_phase(label: str, params, cfg, *, backend: str, prompts) -> None:
    """Serve ``prompts`` through the streaming server on a paged cache with
    chunked prefill; every request must finish with its full token budget
    and no slot may be quarantined for non-finite logits."""
    from repro.serving import api
    from repro.serving.config import SchedulerConfig, ServeConfig

    config = ServeConfig(
        scheduler=SchedulerConfig(n_slots=SLOTS, max_len=MAX_LEN,
                                  chunked_prefill=True, chunk_size=CHUNK,
                                  chunk_budget=4 * CHUNK),
        cache_kind="paged", block_size=16, backend=backend).validate()
    server = api.StreamingServer(params, cfg, config=config)
    t0 = time.perf_counter()
    for p in prompts:
        server.submit(api.GenerationRequest(prompt=p, max_new_tokens=MAX_NEW))
    responses = server.run_until_drained()
    wall = time.perf_counter() - t0
    m = server.metrics
    done = [r for r in responses if r.finish_reason == "max_new_tokens"
            and len(r.tokens) == MAX_NEW]
    _check(len(done) == len(prompts) and m.quarantined == 0,
           f"{label}: {len(done)}/{len(prompts)} requests complete, "
           f"{m.quarantined} quarantined, reasons "
           f"{sorted({r.finish_reason for r in responses})}")
    n_tok = sum(len(r.tokens) for r in responses)
    print(f"smoke[{label}]: {len(done)}/{len(prompts)} requests complete, "
          f"{sum(len(p) for p in prompts)} prompt + {n_tok} generated "
          f"tokens in {wall:.3f} s wall incl. compile (backend={backend}; "
          f"smoke run, not a benchmark)", flush=True)


def parity_check(params, cfg, *, backend: str, seed: int) -> None:
    """One prefill + one decode step on ``backend`` against ``xla`` on the
    same weights; on the chip the compiled decode step must hold the
    Pallas kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import engine

    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (PARITY_BATCH, PARITY_LEN)),
                         jnp.int32)
    pos = jnp.asarray(PARITY_LEN, jnp.int32)
    out = {}
    for be in ("xla", backend):
        prefill = jax.jit(functools.partial(
            engine.prefill, cfg=cfg, max_len=PARITY_LEN + 8, backend=be))
        logits, cache = prefill(params, tokens)
        if be == "xla":
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        step = jax.jit(functools.partial(engine.serve_step, cfg=cfg,
                                         backend=be))
        compiled = step.lower(params, cache, nxt, pos).compile()
        if be == "pallas":
            _check("tpu_custom_call" in compiled.as_text(),
                   "sparse decode step compiled without a Pallas kernel")
        dec, _ = compiled(params, cache, nxt, pos)
        out[be] = (np.asarray(logits, np.float32), np.asarray(dec, np.float32))
    errs = []
    for i, name in enumerate(("prefill", "decode")):
        ref, got = out["xla"][i], out[backend][i]
        _check(bool(np.isfinite(got).all()), f"{name} logits not finite")
        errs.append((name, float(np.max(np.abs(got - ref))),
                     float(np.max(np.abs(ref)))))
    print("parity: " + "; ".join(
        f"{n} max|{backend} - xla| = {e:.6g} (max|xla| {s:.6g}, tolerance "
        f"{PARITY_RTOL * s:.6g} = {PARITY_RTOL} x max|xla|)"
        for n, e, s in errs), flush=True)
    for n, e, s in errs:
        _check(e <= PARITY_RTOL * s, f"{n} logits off the xla reference: "
               f"{e} > {PARITY_RTOL} x {s}")


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2 ** 30:.3f} GiB"


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core import pruning
    from repro.launch import serve, specs
    from repro.models import transformer

    dev = jax.devices()[0]
    cfg = configs.get("qwen2_1_5b")
    transform_finding(cfg.d_model, cfg.d_ff, seed=seed)

    params = transformer.init_model(jax.random.PRNGKey(seed), cfg,
                                    dtype=jnp.bfloat16)
    prompts = make_prompts(cfg.vocab, seed)
    serve_phase("dense", params, cfg, backend="auto", prompts=prompts)
    print(f"memory: peak HBM after dense phase {peak_hbm(dev)}", flush=True)

    t0 = time.perf_counter()
    sparse = pruning.group_projections(pruning.sparsify_params(
        params, SPARSITY, should_sparsify=serve.should_sparsify))
    jax.block_until_ready(sparse)
    print(f"reformat: pruned to {SPARSITY:.0%} and encoded Tiled-CSL in "
          f"{time.perf_counter() - t0:.3f} s; weights "
          f"{specs.struct_weight_bytes(params) / 2 ** 30:.3f} GiB dense -> "
          f"{specs.struct_weight_bytes(sparse) / 2 ** 30:.3f} GiB sparse",
          flush=True)
    del params
    serve_phase("sparse", sparse, cfg, backend="pallas", prompts=prompts)
    parity_check(sparse, cfg, backend="pallas", seed=seed)
    print(f"memory: peak HBM {peak_hbm(dev)}", flush=True)


def four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.launch import mesh as mesh_mod
    from repro.launch import train
    from repro.training import data as data_mod
    from repro.training import optimizer as opt_mod
    from repro.training import train_loop

    devs = jax.devices()
    _check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    full = configs.get("qwen2_1_5b")
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    print(f"four-chip: Qwen2-1.5B widths (d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, GQA {cfg.n_heads}/{cfg.n_kv}, vocab {cfg.vocab}); "
          f"depth cut {full.n_layers} -> {TRAIN_LAYERS} layers so the "
          f"one-chip f32 train state fits; batch {TRAIN_BATCH} x seq "
          f"{TRAIN_SEQ}, {TRAIN_STEPS} steps", flush=True)
    opt = opt_mod.AdamW(lr=3e-4)
    stream = data_mod.SyntheticLM(cfg.vocab, TRAIN_SEQ, TRAIN_BATCH,
                                  seed=seed)
    batches = [jax.tree.map(jnp.asarray, stream.next_batch())
               for _ in range(TRAIN_STEPS)]
    step_fn = train_loop.make_train_step(cfg, opt)
    key = jax.random.PRNGKey(seed)

    def run(step, state):
        losses = []
        for b in batches:
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))
        return state, losses

    t0 = time.perf_counter()
    state, one = run(jax.jit(step_fn, donate_argnums=(0,)),
                     train_loop.init_train_state(key, cfg, opt))
    del state
    t1 = time.perf_counter()
    mesh = mesh_mod.make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    state = train_loop.init_train_state(key, cfg, opt)
    with jax.set_mesh(mesh):
        state, four = run(train.sharded_step(step_fn, state, mesh), state)
    t2 = time.perf_counter()

    per_dev = {d: 0 for d in devs[:4]}
    total = 0
    for leaf in jax.tree.leaves(state.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device] += shard.data.nbytes
    spread = {str(d.id): round(b / 2 ** 20, 3) for d, b in per_dev.items()}
    print(f"four-chip: losses one chip {one}, 2x2 mesh {four}; wall "
          f"{t1 - t0:.3f} s / {t2 - t1:.3f} s incl. compile", flush=True)
    print(f"four-chip: param MiB per device {spread} of "
          f"{total / 2 ** 20:.3f} MiB", flush=True)
    diff = float(np.max(np.abs(np.asarray(one) - np.asarray(four))))
    _check(bool(np.isfinite(four).all()) and diff <= LOSS_ATOL,
           f"sharded losses {four} differ from one chip {one} by {diff}")
    _check(all(b > 0 for b in per_dev.values())
           and max(per_dev.values()) <= 0.75 * total,
           f"params not spread over the mesh: {spread}")
    print(f"four-chip: max |loss diff| {diff:.6g} <= {LOSS_ATOL}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on a 2x2 mesh "
                         "and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = compile_cache.enable()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x {len(jax.devices())} "
          f"({dev.platform}); compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"total wall {time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": 4 if args.four_chips else 1}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
