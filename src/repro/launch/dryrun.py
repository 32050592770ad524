import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["REPRO_BF16_DOT_F32_ACC"] = "1"   # MXU-true bf16 dots (compile-only)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count on first init). Do NOT replicate this env var globally — smoke tests
and benches see the real single device.

Per cell this produces (written incrementally to results/dryrun/*.json):
  * compiled.memory_analysis()  — proves the cell fits per-device HBM
  * compiled.cost_analysis()    — FLOPs / bytes for §Roofline
  * collective bytes parsed from the optimized HLO — §Roofline third term
  * wall compile time

Usage:
  python -m repro.launch.dryrun --all                    # every cell
  python -m repro.launch.dryrun --arch deepseek_coder_33b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod        # 2x16x16 mesh
  python -m repro.launch.dryrun --all --weight-mode sparse_xla
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro import configs
from repro.core import roofline
from repro.launch import mesh as mesh_mod
from repro.launch import specs as specs_mod

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def cell_path(arch: str, shape: str, multi_pod: bool, weight_mode: str,
              tag: str = "") -> str:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    suffix = f".{tag}" if tag else ""
    return os.path.abspath(os.path.join(
        RESULTS_DIR, f"{arch}.{shape}.{mesh_name}.{weight_mode}{suffix}.json"))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             weight_mode: str = "dense", sparsity: float = 0.8,
             remat: str | None = None, tag: str = "",
             microbatches: int = 1, force: bool = False) -> dict:
    out_path = cell_path(arch, shape_name, multi_pod, weight_mode, tag)
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = 2 * 16 * 16 if multi_pod else 16 * 16
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "weight_mode": weight_mode, "sparsity": sparsity,
        "remat": remat, "microbatches": microbatches,
        "chips": chips, "status": "error",
    }
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            cell = specs_mod.build_cell(
                cfg, shape, mesh, weight_mode=weight_mode,
                sparsity=sparsity, remat=remat, microbatches=microbatches)
            lowered = jax.jit(
                cell.fn, in_shardings=cell.in_shardings,
                donate_argnums=cell.donate).lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            try:
                mem = compiled.memory_analysis()
                mem_rec = {
                    "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                              None),
                    "output_bytes": getattr(mem, "output_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                    "generated_code_bytes":
                        getattr(mem, "generated_code_size_in_bytes", None),
                }
            except Exception as e:  # CPU backend may not implement it
                mem_rec = {"unavailable": str(e)}
            cost = roofline.cost_analysis_dict(compiled)
            hlo = compiled.as_text()
            coll = roofline.parse_collective_bytes(hlo)
            # scan-corrected costs via unrolled probe extrapolation
            ecost, ecoll, probe_meta = _probe_costs(
                cfg, shape, mesh, weight_mode=weight_mode,
                sparsity=sparsity, remat=remat, microbatches=microbatches)

        record.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": mem_rec,
            "cost_raw": {k: float(v) for k, v in dict(cost).items()
                         if isinstance(v, (int, float))},
            "collective_bytes_raw": coll,
            "cost": ecost,
            "collective_bytes": ecoll,
            "probe": probe_meta,
            "model_flops": _model_flops(cfg, shape),
            "label": cell.label,
        })
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_s"] = round(time.time() - t0, 2)

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path + ".tmp", "w") as f:
        json.dump(record, f, indent=1)
    os.replace(out_path + ".tmp", out_path)
    return record


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for train (N = active matmul params, D = tokens);
    2·N_active per generated token for decode; 2·N·D for prefill.
    Embedding-gather-only params are excluded (no FLOPs)."""
    n_active = cfg.matmul_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per slot


def _probe_costs(cfg, shape, mesh, *, weight_mode, sparsity, remat,
                 microbatches: int = 1):
    """XLA's cost_analysis counts while-loop (lax.scan) bodies ONCE, so
    scanned stacks undercount FLOPs/bytes/collectives by ~L x. We compile
    the same cell UNROLLED at two small depths (one and two pattern
    periods... kept small for compile time) and extrapolate linearly:
        cost(L) = intercept + per_layer * L
    which exactly recovers embed/head costs (intercept) + L x body costs.

    Returns (cost_dict_at_full_L, collective_dict_at_full_L, probe_meta).
    """
    period = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    l1, l2 = 2 * period, 4 * period
    if cfg.n_layers <= l2:  # small model: trust an unrolled full compile
        l1, l2 = None, None
    vals = {}
    for li in filter(None, (l1, l2)):
        pcfg = dataclasses.replace(cfg, n_layers=li, scan_layers=False)
        cell = specs_mod.build_cell(pcfg, shape, mesh,
                                    weight_mode=weight_mode,
                                    sparsity=sparsity, remat=remat,
                                    microbatches=microbatches)
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           donate_argnums=cell.donate) \
            .lower(*cell.args).compile()
        cost = {k: float(v)
                for k, v in roofline.cost_analysis_dict(compiled).items()
                if isinstance(v, (int, float))}
        coll = roofline.parse_collective_bytes(compiled.as_text())
        vals[li] = (cost, coll)
    if not vals:
        pcfg = dataclasses.replace(cfg, scan_layers=False)
        cell = specs_mod.build_cell(pcfg, shape, mesh,
                                    weight_mode=weight_mode,
                                    sparsity=sparsity, remat=remat,
                                    microbatches=microbatches)
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           donate_argnums=cell.donate) \
            .lower(*cell.args).compile()
        cost = {k: float(v)
                for k, v in roofline.cost_analysis_dict(compiled).items()
                if isinstance(v, (int, float))}
        coll = roofline.parse_collective_bytes(compiled.as_text())
        return cost, coll, {"mode": "unrolled_full"}

    (c1, k1), (c2, k2) = vals[l1], vals[l2]
    L = cfg.n_layers

    def extrap(v1, v2):
        per = (v2 - v1) / (l2 - l1)
        return max(v1 + (L - l1) * per, 0.0)

    cost = {k: extrap(c1.get(k, 0.0), c2.get(k, 0.0))
            for k in set(c1) | set(c2)}
    coll = {k: extrap(k1.get(k, 0.0), k2.get(k, 0.0))
            for k in set(k1) | set(k2)}
    return cost, coll, {"mode": "extrapolated", "probe_layers": [l1, l2]}


def iter_cells(multi_pod: bool, weight_mode: str):
    for arch in configs.ARCH_IDS:
        for shape in configs.cells(arch):
            yield arch, shape.name, multi_pod, weight_mode


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--weight-mode", default="dense",
                    choices=["dense", "sparse_xla"])
    ap.add_argument("--sparsity", type=float, default=0.8)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    jobs = []
    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            jobs += list(iter_cells(mp, args.weight_mode))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        jobs = [(args.arch, args.shape, args.multi_pod, args.weight_mode)]

    ok = failed = 0
    for arch, shape, mp, wm in jobs:
        rec = run_cell(arch, shape, multi_pod=mp, weight_mode=wm,
                       sparsity=args.sparsity, remat=args.remat,
                       microbatches=args.microbatches,
                       tag=args.tag, force=args.force)
        status = rec["status"]
        ok += status == "ok"
        failed += status != "ok"
        mesh_name = "2x16x16" if mp else "16x16"
        extra = ""
        if status == "ok":
            mb = (rec["memory"]["temp_bytes"] or 0) / 2**20
            extra = (f"compile={rec.get('compile_s', 0):.1f}s "
                     f"temp={mb:.0f}MiB "
                     f"flops={rec['cost'].get('flops', 0):.3g}")
        else:
            extra = rec.get("error", "")[:160]
        print(f"[{status:5s}] {arch:22s} {shape:12s} {mesh_name:8s} {wm:10s} "
              f"{extra}", flush=True)
    print(f"\n{ok} ok / {failed} failed")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
