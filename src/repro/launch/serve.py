"""Serving launcher: batched requests against a (reduced) model.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 16 --max-new 32

``--frontend async`` switches from the in-process closed loop to the
``AsyncFrontend`` service posture: requests arrive on an open-loop
Poisson clock (``--arrival-rate`` req/s) through admission control
(``--max-queue-depth`` backpressure, ``--breaker-*`` circuit-breaker
knobs) and the run reports client-side latency percentiles plus
goodput under ``--slo-ttft``:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --frontend async --arrival-rate 8 --max-queue-depth 8

With ``--replicas N`` the trace runs against a fault-tolerant fleet:
``--fault-crash-replica`` / ``--fault-seed`` inject deterministic
replica failures (the router fails in-flight requests over, bit-identical
under greedy sampling) and ``--drain-replica`` starts one replica
administratively drained — the run's ``fault_tolerance`` block reports
deaths, failovers, and failover TTFT percentiles.
"""
from __future__ import annotations

import argparse
import json
import warnings

import jax
import numpy as np

from repro.configs.base import get_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import replica_meshes
from repro.models import kv_quant
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.faults import FaultPlan, FaultyEngine
from repro.serving.frontend import CircuitBreaker
from repro.serving.openloop import poisson_trace, run_open_loop
from repro.serving.router import ROUTER_POLICIES, run_open_loop_router
from repro.serving.sampler import SamplerConfig
from repro.serving.spec import SPEC_DECODE_MODES
from repro.serving.warmup import trace_prompt_lens, warmup_prefill


def resolve_attn_kernel_arg(attn_kernel, decode_kernel) -> str:
    """Fold the deprecated ``--decode-kernel`` spelling into
    ``--attn-kernel`` (with a DeprecationWarning), defaulting to "auto"."""
    if decode_kernel is not None:
        warnings.warn(
            "--decode-kernel is deprecated; the knob now selects the "
            "prefill kernel too — use --attn-kernel",
            DeprecationWarning, stacklevel=2)
        if attn_kernel is not None and attn_kernel != decode_kernel:
            raise SystemExit(
                f"conflicting --attn-kernel {attn_kernel} and "
                f"--decode-kernel {decode_kernel}")
        return decode_kernel
    return attn_kernel or "auto"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "continuous", "wave"],
                    help="scheduler: continuous batching (attention "
                         "families) or the lockstep wave baseline")
    ap.add_argument("--block-size", type=int, default=8,
                    help="tokens per paged-KV block (>= max_len degenerates "
                         "to one stripe per request)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV block pool size (default: max_batch stripes' "
                         "worth)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="max prompt tokens prefilled per scheduler step "
                         "(0 = whole prompt in one call)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="share KV blocks across requests with a common "
                         "prompt prefix (--no-prefix-cache disables; "
                         "retired blocks then free immediately instead of "
                         "lingering in the LRU pool)")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="decode iterations per jitted step / host sync "
                         "(masked early-exit on retirement; >1 amortizes "
                         "dispatch latency over several tokens)")
    ap.add_argument("--attn-kernel", default=None,
                    choices=["auto", "on", "off"],
                    help="attention-kernel implementation for BOTH paged "
                         "hot paths (flash-decode and flash-prefill — "
                         "each walks the block table straight out of the "
                         "shared KV pool; the prefill kernel also fuses "
                         "the new-token K/V scatter): Pallas kernels on "
                         "TPU with 'auto' (default), forced everywhere "
                         "with 'on' (interpret mode off-TPU), or the jnp "
                         "references with 'off'")
    ap.add_argument("--decode-kernel", default=None,
                    choices=["auto", "on", "off"],
                    help="DEPRECATED alias of --attn-kernel (the knob now "
                         "selects the prefill kernel too)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=list(kv_quant.KV_DTYPES),
                    help="paged KV pool representation: fp/bf16 (dense "
                         "compute-dtype blocks), f8 (dense float8 "
                         "stripes), or the SCLAD compressed encodings "
                         "int8/fp8 (payload + per-position fp32 scales; "
                         "~2x token context per device byte, dequantized "
                         "on the load path by references and kernels "
                         "alike).  Default: the config's setting")
    ap.add_argument("--spec-decode", default="off",
                    choices=list(SPEC_DECODE_MODES),
                    help="speculative multi-token decoding: 'ngram' drafts "
                         "continuations from each request's own history, "
                         "verifies them in one chunked-prefill pass and "
                         "rolls rejected K/V back — outputs stay "
                         "bit-identical to 'off'; wins on repetitive/"
                         "structured output, neutral on random text")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens proposed per lane per step "
                         "(with --spec-decode; up to spec_k+1 tokens emit "
                         "per verify pass)")
    ap.add_argument("--preempt-policy", default="youngest",
                    choices=["youngest", "largest", "deadline"],
                    help="which in-flight request pool pressure preempts: "
                         "most recently submitted, most KV blocks held, "
                         "or latest deadline")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens to "
                         "every request (exercises the prefix cache)")
    ap.add_argument("--frontend", default="sync",
                    choices=["sync", "async"],
                    help="'sync': submit everything up front and run the "
                         "engine closed-loop to drain; 'async': the "
                         "AsyncFrontend service posture — open-loop "
                         "Poisson arrivals through streaming admission "
                         "control, reporting client-side tail latency "
                         "and goodput-under-SLO")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="[async] open-loop Poisson arrival rate, "
                         "requests/second (the clock does NOT wait for "
                         "the scheduler — saturate it and the breaker "
                         "sheds)")
    ap.add_argument("--max-queue-depth", type=int, default=32,
                    help="[async] max accepted-but-unfinished requests; "
                         "submits beyond it are rejected 503-style "
                         "(backpressure) instead of queueing unboundedly")
    ap.add_argument("--slo-ttft", type=float, default=2.0,
                    help="[async] client-side TTFT SLO (seconds) for the "
                         "goodput-under-SLO report")
    ap.add_argument("--breaker-window", type=int, default=16,
                    help="[async] circuit breaker: sliding window of "
                         "scheduler ticks scanned for pressure")
    ap.add_argument("--breaker-trip", type=int, default=4,
                    help="[async] pressure ticks (preemption or pool "
                         "saturation) within the window that trip the "
                         "breaker open")
    ap.add_argument("--breaker-sat", type=float, default=1.0,
                    help="[async] live-block pool saturation fraction "
                         "that counts a tick as pressure")
    ap.add_argument("--breaker-cooldown", type=int, default=8,
                    help="[async] ticks the breaker stays open before "
                         "half-opening to admit probes")
    ap.add_argument("--breaker-probes", type=int, default=1,
                    help="[async] probe requests admitted half-open; this "
                         "many clean completions close the breaker")
    ap.add_argument("--replicas", type=int, default=1,
                    help="[async] data-parallel scale-out: run this many "
                         "independent engine replicas behind a "
                         "prefix-affinity router (each replica is its own "
                         "controller — own scheduler, KV pool, breaker; "
                         "requests route to the replica already holding "
                         "their prefix blocks, else least-loaded)")
    ap.add_argument("--router-policy", default="affinity",
                    choices=list(ROUTER_POLICIES),
                    help="[async, --replicas > 1] placement policy: "
                         "'affinity' (prefix-cache match, then "
                         "least-loaded) or the 'round_robin' baseline")
    ap.add_argument("--fault-crash-replica", type=int, default=None,
                    help="[async, --replicas > 1] kill this replica "
                         "mid-run: its engine crashes at "
                         "--fault-crash-tick and the router fails its "
                         "in-flight requests over (outputs stay "
                         "bit-identical under greedy sampling)")
    ap.add_argument("--fault-crash-tick", type=int, default=24,
                    help="[async] engine-step index at which "
                         "--fault-crash-replica dies (deterministic: "
                         "idle pump ticks do not advance it)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="[async] wrap every replica in a seeded chaos "
                         "plan (transient hangs / step errors / "
                         "slowdowns, no crashes; replica i uses "
                         "seed + i) — same seed replays the same faults")
    ap.add_argument("--drain-replica", type=int, default=None,
                    help="[async, --replicas > 1] start with this "
                         "replica administratively drained: it takes no "
                         "placements while its peers serve the trace")
    ap.add_argument("--retry-budget", type=int, default=3,
                    help="[async] max failover re-homings per request "
                         "after replica deaths; exhaustion ends the "
                         "stream with a timeout-kind rejection")
    args = ap.parse_args()

    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.replicas > 1 and args.frontend != "async":
        raise SystemExit("--replicas requires --frontend async (the "
                         "router fronts AsyncFrontend replicas)")
    for flag, val in (("--fault-crash-replica", args.fault_crash_replica),
                      ("--drain-replica", args.drain_replica)):
        if val is not None:
            if args.replicas < 2:
                raise SystemExit(f"{flag} needs --replicas >= 2 (a peer "
                                 f"must absorb the traffic)")
            if not 0 <= val < args.replicas:
                raise SystemExit(f"{flag} {val} out of range for "
                                 f"--replicas {args.replicas}")
    if args.fault_seed is not None and args.frontend != "async":
        raise SystemExit("--fault-seed requires --frontend async")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))

    def make_engine(mesh=None):
        return ServingEngine(
            cfg, params, max_batch=args.max_batch, mesh=mesh,
            max_len=64 + args.shared_prefix + args.max_new, mode=args.mode,
            seed=args.seed,
            block_size=args.block_size, num_blocks=args.num_blocks,
            prefill_chunk=args.prefill_chunk or None,
            prefix_cache=args.prefix_cache, decode_steps=args.decode_steps,
            attn_kernel=resolve_attn_kernel_arg(args.attn_kernel,
                                                args.decode_kernel),
            preempt_policy=args.preempt_policy, kv_dtype=args.kv_dtype,
            spec_decode=args.spec_decode, spec_k=args.spec_k,
            sampler=SamplerConfig(temperature=args.temperature, top_k=50))

    # Replicas each on their own device (round-robin when there are fewer
    # devices than replicas); a single engine keeps the default device.
    engines = [make_engine(m) for m in replica_meshes(args.replicas)] \
        if args.replicas > 1 else [make_engine()]
    engine = engines[0]
    rng = np.random.default_rng(args.seed)
    system = rng.integers(1, cfg.vocab_size, size=args.shared_prefix)

    if args.frontend == "async":
        if engine.mode != "continuous":
            raise SystemExit("--frontend async requires the continuous "
                             "scheduler (got mode=wave)")
        trace = poisson_trace(
            rng, args.requests, args.arrival_rate, cfg.vocab_size,
            prompt_len=(4, 16), budget=(args.max_new, args.max_new),
            shared_prefix=system if args.shared_prefix else None,
            prefix_fraction=0.5 if args.shared_prefix else 0.0)
        # Warm the jit caches closed-loop first so the open-loop clock
        # measures serving latency, not compilation — the SAME
        # (group-size, chunk-bucket) coverage rule the bench uses,
        # derived from the actual trace (see serving.warmup).
        lens = trace_prompt_lens(trace, engine,
                                 extra=(16 + args.shared_prefix,))
        for e in engines:
            warmup_prefill(e, cfg.vocab_size, prompt_lens=lens)

        # Fault injection wraps AFTER warmup so the plan's step clock
        # starts at the trace, not at cache priming.
        plans = {}
        if args.fault_seed is not None:
            for i in range(len(engines)):
                plans[i] = FaultPlan.seeded(args.fault_seed + i)
        if args.fault_crash_replica is not None:
            i = args.fault_crash_replica
            plans[i] = plans.get(i, FaultPlan()) \
                + FaultPlan.crash_at(args.fault_crash_tick)
        if plans:
            engines = [FaultyEngine(e, plans[i]) if i in plans else e
                       for i, e in enumerate(engines)]
        engine = engines[0]

        def breaker():
            return CircuitBreaker(
                window=args.breaker_window,
                trip_pressure=args.breaker_trip,
                sat_threshold=args.breaker_sat,
                cooldown_ticks=args.breaker_cooldown,
                probes=args.breaker_probes)

        if args.replicas > 1:
            report, router = run_open_loop_router(
                engines, trace, policy=args.router_policy,
                max_queue_depth=args.max_queue_depth,
                breaker_factory=breaker,
                retry_budget=args.retry_budget,
                drain=() if args.drain_replica is None
                else (args.drain_replica,))
            out = report.summary(args.slo_ttft)
            out["routing"] = router.routing_report()
            if plans:
                out["fault_plans"] = {
                    str(i): p.describe() for i, p in sorted(plans.items())}
            print(json.dumps(out, indent=2))
            return
        report = run_open_loop(engine, trace,
                               max_queue_depth=args.max_queue_depth,
                               breaker=breaker())
        print(json.dumps(report.summary(args.slo_ttft), indent=2))
        return

    for i in range(args.requests):
        plen = int(rng.integers(4, 17))
        prompt = np.concatenate(
            [system, rng.integers(1, cfg.vocab_size, size=plen)])
        deadline = None
        if args.preempt_policy == "deadline":
            # Demo deadlines: arrival order + a work proxy, so requests
            # with more remaining work have more slack and are the ones
            # preempted under pool pressure (without this the policy
            # would see only deadline-less requests and degenerate to
            # youngest-first).
            deadline = float(i + len(prompt) + args.max_new)
        engine.submit(prompt, max_new_tokens=args.max_new,
                      deadline=deadline)
    results = engine.run()
    for uid, toks in sorted(results.items())[:4]:
        print(f"req {uid}: {toks[:16]}{'...' if len(toks) > 16 else ''}")
    s = engine.stats
    paged = (f" ({s.prefill_chunks} chunks, prefix hit-rate "
             f"{s.prefix_hit_rate:.0%})",
             f", KV utilization {s.block_utilization:.0%}, "
             f"{s.preemptions} preemptions") \
        if engine.mode == "continuous" else ("", "")
    print(f"prefill {s.prefill_tokens} tok in {s.prefill_s:.2f}s "
          f"({s.prefill_tokens_per_s:.1f} tok/s, mean TTFT "
          f"{s.mean_ttft_s * 1e3:.1f}ms){paged[0]}; "
          f"generated {s.generated_tokens} tok in {s.decode_s:.2f}s "
          f"({s.tokens_per_s:.1f} tok/s, mode={engine.mode}, "
          f"lane occupancy {s.slot_occupancy:.0%}{paged[1]})")
    if engine.spec_decode != "off":
        print(f"spec[{engine.spec_decode}] {s.spec_passes} verify passes, "
              f"draft acceptance {s.spec_acceptance_rate:.0%} "
              f"({s.spec_accepted}/{s.spec_proposed})")


if __name__ == "__main__":
    main()
