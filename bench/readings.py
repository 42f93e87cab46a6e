#!/usr/bin/env python3
"""Correctness readings on the chip: the served tokens' widest logit gap
(the number ``run.py`` compares) and the control's, over many seeds in one
process.

    python3 bench/readings.py --workload <name> --seconds <s> --seeds 1 2 3 ...

One engine is built and warmed once; for each seed the weights are made
anew from that seed and handed to it, the cell's traffic (its own mix and
rate, drawn from the seed) runs for ``--seconds`` plus the drain, and a
sample of the finished requests is compared with the plain reference in
float32 (the program's reading) and in fp8 (the control's reading: at
each position of the same prompts and served tokens, the gap of the token
that fp8 ranks first).  One JSON line per seed.  The limit in the
configuration file lies between the largest program reading and the
smallest control reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as R
from lib import check, client, traffic
from lib.spec import load_cell, reference_module


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    R.require_chips(cell.workload["chips"])
    R.use_compile_cache()
    conf, mix = cell.config, cell.traffic
    ref = reference_module(conf)
    m = ref.dims(conf["model"])
    weights = ref.make_weights(conf["model"], args.seeds[0])
    eng = R.warm_engine(conf, mix, weights, 0)
    corr = conf["correctness"]
    for seed in args.seeds:
        t = time.perf_counter()
        # Drop the last seed's weights before making the next: both do not
        # fit beside the pool for the larger configuration.
        eng.params = weights = None
        gc.collect()
        eng.params = weights = ref.make_weights(conf["model"], seed)
        reqs = traffic.requests(mix, seed, args.seconds, m["vocab"])
        prompts = {r.index: r.prompt for r in reqs}
        fe = R.build_frontend(conf, eng)
        lead = traffic.lead_s(mix)
        recs = client.run(fe, lambda t0: client.open_loop(
            fe, reqs, t0, args.seconds + mix.get("drain_s", 0.0)),
            lambda: time.perf_counter() + lead)
        recs = [r for r in recs if r.due_s >= 0]
        sample = check.draw_sample(recs, seed, corr["min_tokens"],
                                   corr["max_requests"], corr["min_requests"])
        prog = check.widest_gap(ref, conf["model"], weights, sample, prompts)
        ctrl = check.control_gap(ref, conf["model"], weights, sample,
                                 prompts, args.control)
        print(json.dumps({
            "seed": seed, "program_gap": prog, "control_gap": ctrl,
            "control": args.control, "requests": len(sample),
            "served_tokens": sum(len(r.tokens) for r in sample),
            "attempted": len(recs),
            "failed": sum(r.failed for r in recs),
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
