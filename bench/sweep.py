#!/usr/bin/env python3
"""Knee sweep on the chip: the open-loop cell's traffic at several fixed
rates, one process, one warm engine.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 1 2 3 ...

Each rate runs for the mix's lead-in and then ``--seconds`` with no drain:
what has not finished when the window closes is the backlog, and what has
no first token yet was still queued or prefilling.  Only the window's
arrivals are counted.  One JSON line per rate: requests sent,
finished and left over, client time to first token (median, 90th
percentile, and the medians over the first and the last third of the
arrivals, whose ratio grows with the backlog), the 99th-percentile token
gap and tokens/s.  The knee is the highest rate, with every rate below
it, whose backlog does not grow through the window: no request that
arrived in the window's first four fifths still without a first token at
the close, and the last third's median time to first token under 1.5
times the first third's.  The last line gives it and
the cell's rate, 0.8 of it, which goes into the mix's file.  With
``--trace-rate``, that rate's window is traced and its line also gives the
device's busy and window seconds, the top device operations and the
longest idle gaps.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import sys
import time

import jax
from jax.profiler import TraceAnnotation

import run as R
from lib import client, trace, traffic
from lib.spec import load_cell, reference_module
from lib.stats import nearest_rank


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trace-rate", type=float, default=None)
    args = ap.parse_args()

    cell = load_cell(args.workload)
    R.require_chips(cell.workload["chips"])
    R.use_compile_cache()
    conf, mix = cell.config, dict(cell.traffic)
    ref = reference_module(conf)
    m = ref.dims(conf["model"])
    weights = ref.make_weights(conf["model"], args.seed)
    eng = R.warm_engine(conf, mix, weights, 0)
    knee, held = None, True
    for rate in sorted(args.rates):
        mix["rate_req_s"] = rate
        reqs = traffic.open_loop(mix, args.seed, args.seconds, m["vocab"])
        fe = R.build_frontend(conf, eng)
        lead = traffic.lead_s(mix)
        traced = rate == args.trace_rate
        logdir = R.CACHE / "trace" / "sweep"
        if traced:
            jax.profiler.start_trace(str(logdir))

        async def body(t0):
            sent = asyncio.ensure_future(
                client.open_loop(fe, reqs, t0, args.seconds))
            await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
            with TraceAnnotation(trace.WINDOW_SPAN):
                return await sent

        recs = client.run(fe, body, lambda: time.perf_counter() + lead)
        recs = [r for r in recs if r.due_s >= 0]
        extra = {}
        if traced:
            jax.profiler.stop_trace()
            red = trace.reduce(str(logdir))
            extra = {"busy_s": red.busy_s, "window_s": red.window_s,
                     "device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
        ttft = [r.ttft_s for r in recs]
        third = max(1, len(recs) // 3)
        gaps = [g for r in recs for g in r.gaps_s]
        head, tail = (statistics.median(ttft[:third]),
                      statistics.median(ttft[-third:]))
        stuck = sum(not r.token_s and r.due_s < 0.8 * args.seconds
                    for r in recs)
        held = held and stuck == 0 and tail < 1.5 * head
        if held:
            knee = rate
        print(json.dumps({
            "rate": rate, "sent": len(recs),
            "finished": sum(r.status == "completed" for r in recs),
            "backlog": sum(r.status == "unfinished" for r in recs),
            "no_first_token": sum(not r.token_s for r in recs),
            "rejected": sum(r.status == "rejected" for r in recs),
            "ttft_p50_s": nearest_rank(ttft, 50), "ttft_p90_s":
            nearest_rank(ttft, 90),
            "ttft_first_third_s": head, "ttft_last_third_s": tail,
            "itl_p99_s": nearest_rank(gaps, 99) if gaps else None,
            "tok_s": sum(len(r.tokens) for r in recs) / args.seconds,
            "breaker_opens": fe.breaker.opens, **extra}), flush=True)
    print(json.dumps({"knee": knee, "rate": knee and round(0.8 * knee, 3)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
