#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, in one process.

    python3 chipbench/limits.py --workload <cell> --seconds <s> \
        --seeds 1,2,...  [--control-seeds 1,2,3]

For every seed: a fresh server on the cell's own weights, slots and load,
its warm-up and a ``--seconds`` window, then the same seeded sample of
finished requests that a benchmark run checks. Prints, per seed, the widest
gap of a served token below the best logit of the configuration's reference
(``Cell.reference``; the float32 ``chipbench/reference.py`` unless the
configuration names another) -- the program's reading -- and, for the
control seeds, the widest gap of the token that the reference's
lower-precision control puts first (the control's reading). The last line of
standard output is a JSON summary: ``lower`` is the largest program reading,
``upper`` the smallest control reading. The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seeds, control_seeds, seconds):
    """Per seed: program gap, control gap (or None), served tokens
    checked."""
    from chipbench import harness
    cfg = harness.model_config(cell)
    params = harness.served_params(cell, cfg, {})
    out = []
    for seed in seeds:
        server = harness.make_server(params, cfg, cell)
        harness.warm_shapes(server, cell, cfg.vocab)
        reqs = harness.build_requests(cell, seed, seconds, cfg.vocab)
        run = harness.serve(server, reqs, open_at=cell.params["warmup_s"],
                            seconds=seconds)
        del server
        gc.collect()
        sample = harness.sample_finished(run, cell.params["check_requests"],
                                         seed)
        chk = harness.check(cell, sample, control=seed in control_seeds)
        row = {"seed": seed, "gap": chk["gap"], "tokens": chk["tokens"],
               "agree": chk.get("agree", 0), "requests": len(sample),
               "unanswered": harness.unanswered(run),
               "control_gap": chk.get("control_gap")}
        harness.log("reading " + json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        print("chipbench: limits need the accelerator", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = readings(cell, seeds + sorted(ctl - set(seeds)), ctl,
                    args.seconds)
    gaps = [r["gap"] for r in rows if r["gap"] is not None]
    ctl_gaps = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "lower": max(gaps) if gaps else None,
                      "upper": min(ctl_gaps) if ctl_gaps else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
