#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate it serves without a growing
backlog, by serving the cell's traffic at several fixed rates in one
process.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \
        --rates 2,3,4,5 [--seed n]

Prints one JSON line per rate: requests due in the window, those that got a
first token in it, the queue depth when the window opened and closed,
output tokens per second, and the 80th-percentile time to first token. The
knee is the last rate whose queue does not grow across the window. The cell
file's ``rate_per_s`` is set to about four fifths of it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        print("chipbench: the sweep needs the accelerator", file=sys.stderr)
        return 3
    cfg = harness.model_config(cell)
    params = harness.served_params(cell, cfg, {})
    for rate in (float(r) for r in args.rates.split(",")):
        server = harness.make_server(params, cfg, cell)
        harness.warm_shapes(server, cell, cfg.vocab)
        reqs = harness.build_requests(cell, args.seed, args.seconds,
                                      cfg.vocab, rate=rate)
        run = harness.serve(server, reqs, open_at=cell.params["warmup_s"],
                            seconds=args.seconds, tail_s=0.0)
        rec = harness.window_record(cell, run)
        due = [r for r in reqs if r.phase == "window"]
        t1 = run["close"]["t"]
        print(json.dumps({
            "rate": rate, "due": len(due),
            "first_token_in_window": sum(
                1 for r in due if r.times and r.times[0] < t1),
            "queue_open": rec["queue"]["open"],
            "queue_close": rec["queue"]["close"],
            "active_slots_close": len(server.batcher.sched.active_slot_ids()),
            "tokens_per_s": rec["tokens"] / rec["window_s"],
            "ttft_p80_ms": harness.pctl(rec["ttft_s"], 80) * 1e3,
            "decode_ms": 1e3 * rec["delta"]["decode_time_s"]
            / max(rec["decode_launches"], 1),
            "preempted": rec["delta"]["preemptions"]}), flush=True)
        del server, run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
