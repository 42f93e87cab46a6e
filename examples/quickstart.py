"""Quickstart: build an architecture, run a forward pass, generate tokens.

    PYTHONPATH=src python examples/quickstart.py [--arch tinyllama-1.1b]

Uses the reduced (smoke) config so it runs on the CPU in seconds, with
the Pallas kernels in interpret mode.  The full-width model on one TPU v5e
chip, through the same engine and kernels, is ``python chip_smoke.py``
(see README.md).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, list_archs
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.sampler import SamplerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    print(f"arch={cfg.name} family={cfg.family} "
          f"params={M.param_count(cfg):,}")

    # 1. Initialize parameters and run one forward pass.
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32)[None] % cfg.vocab_size}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros((1, cfg.num_patches, cfg.d_model),
                                          jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((1, cfg.encdec.encoder_seq_len,
                                     cfg.d_model), jnp.bfloat16)
    logits, _ = M.forward(cfg, params, batch)
    print(f"forward: logits {logits.shape}, "
          f"finite={bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))}")

    # 2. Generate through the continuous-batching serving engine: submit
    # requests with different prompt AND completion lengths, then step the
    # scheduler — each step() admits queued work, prefills (at most) one
    # prompt chunk, and runs decode_steps jitted masked decode iterations
    # across all lanes.
    #
    # KV memory is a PAGED, REF-COUNTED block store: requests address one
    # pool of fixed-size token blocks through per-lane block tables, and
    # requests sharing a prompt prefix (system prompts, few-shot headers)
    # SHARE its blocks — admission matches the longest cached prefix, so
    # prefill only runs the uncached tail, and retired requests' blocks
    # linger in an LRU pool for future hits.  Admission is optimistic (no
    # worst-case reservation): if decode growth runs the pool dry, the
    # youngest request is preempted and recomputed later, bit-identically.
    # Knobs:
    #   block_size    — tokens per KV block; small (8-16) minimizes
    #                   fragmentation AND sharing granularity (only full
    #                   blocks are shared); >= max_len degenerates to one
    #                   stripe per request (the old slot engine);
    #   num_blocks    — pool size (default: max_batch stripes' worth);
    #   prefill_chunk — max prompt tokens prefilled per step, so a long
    #                   prompt's admission interleaves with in-flight
    #                   decodes instead of stalling them (None = whole
    #                   prompt at once);
    #   prefix_cache  — block sharing on/off (greedy outputs are
    #                   bit-identical either way);
    #   decode_steps  — decode iterations per host sync (masked early
    #                   exit on retirement; amortizes dispatch latency);
    #   attn_kernel   — attention-kernel implementation for BOTH paged hot
    #                   paths: "auto" runs the Pallas kernels on TPU and
    #                   the jnp references elsewhere; "on" forces the
    #                   kernels (interpret mode off-TPU), "off" the
    #                   references.  Decode walks each lane's blocks
    #                   through its table straight out of the shared pool
    #                   (KV bytes stream once per token, no dense per-lane
    #                   gather); chunked prefill streams the cached
    #                   context the same way, derives its causal/left-pad
    #                   mask from scalars in-kernel (no dense (B, S, S)
    #                   mask) and scatters the chunk's new K/V into the
    #                   pool inside the same kernel call.  All scheduling
    #                   invariants (prefix sharing, preemption,
    #                   decode_steps) hold bit-identically WITHIN either
    #                   implementation; across them, logits agree to dtype
    #                   tolerance (fp32 online softmax vs bf16 two-pass
    #                   reference).  decode_kernel= is the deprecated
    #                   PR-4 spelling (DeprecationWarning);
    #   preempt_policy— pool-pressure victim selection: "youngest"
    #                   (default), "largest" (most KV blocks held) or
    #                   "deadline" (latest submit(deadline=...) first);
    #   kv_dtype      — paged-pool encoding (SCLAD: store-as-compressed,
    #                   load-as-dense).  "fp" (default) keeps the fp-exact
    #                   bf16 pool; "int8" / "fp8" store a compressed
    #                   payload + per-token-per-head fp32 scales and every
    #                   reader (jnp references AND Pallas kernels, so it
    #                   composes with attn_kernel) dequantizes on load.
    #                   ~1.88x blocks per pool byte at head_dim=64 ->
    #                   more concurrent requests before preemption.  The
    #                   whole scheduling matrix (prefix cache, chunk
    #                   sizes, preemption recompute) stays bit-identical
    #                   WITHIN an encoding — quantization is path-
    #                   independent, and prefix-cache chain roots are
    #                   namespaced per encoding so pools never share
    #                   blocks across kv_dtypes.  Vs the fp-exact pool,
    #                   last-token logits stay within the documented
    #                   gates (tests/test_kv_quant.py: int8 <= 0.15,
    #                   fp8 <= 0.35 max abs error on the smoke configs);
    #   spec_decode   — speculative multi-token decoding: "off" (default)
    #                   or "ngram" (suffix-match draft proposer).  Each
    #                   step drafts up to spec_k tokens per lane from the
    #                   lane's own history, scores anchor+drafts in ONE
    #                   flash-prefill pass, keeps the longest prefix that
    #                   matches what plain decode would sample, and rolls
    #                   rejected K/V back (BlockStore.truncate).  Outputs
    #                   are bit-identical to spec_decode="off" on the
    #                   reference attention path — speculation only
    #                   changes tokens-per-host-sync, never a token;
    #   spec_k        — max drafted tokens per lane per verify pass.
    #                   The win scales with draft ACCEPTANCE RATE: text
    #                   that revisits its own n-grams (code, JSON, chat
    #                   templates, repetitive suffixes) accepts most
    #                   drafts and can approach (1 + spec_k) tokens per
    #                   sync; adversarially random output accepts ~none
    #                   and pays only the slightly wider verify pass.
    #                   stats.spec_acceptance_rate tells you which regime
    #                   a workload is in — below ~0.2, leave spec off.
    eng = ServingEngine(cfg, params, max_batch=2, max_len=48, eos_id=-1,
                        block_size=8, prefill_chunk=16, prefix_cache=True,
                        decode_steps=1,
                        sampler=SamplerConfig(temperature=0.7, top_k=20))
    eng.submit(np.arange(1, 9), max_new_tokens=8)
    eng.submit(np.arange(5, 18), max_new_tokens=5)
    eng.submit(np.arange(2, 8), max_new_tokens=6)  # waits for a freed slot
    done = {}
    if eng.mode == "continuous":
        while len(done) < 3:
            for uid, toks in eng.step():
                done[uid] = toks
    else:  # ssm/hybrid/audio fall back to lockstep wave batching
        done = eng.run()
    for uid, toks in sorted(done.items()):
        print(f"generated[{uid}]: {toks}")
    blocks = (f", KV utilization {eng.stats.block_utilization:.0%}, "
              f"prefix hit-rate {eng.stats.prefix_hit_rate:.0%}") \
        if eng.mode == "continuous" else ""
    print(f"decode throughput: {eng.stats.tokens_per_s:.1f} tok/s, "
          f"prefill {eng.stats.prefill_tokens_per_s:.1f} tok/s, "
          f"mean TTFT {eng.stats.mean_ttft_s * 1e3:.1f}ms, "
          f"lane occupancy {eng.stats.slot_occupancy:.0%}{blocks} (CPU)")

    # 3. The network-facing layer: AsyncFrontend wraps the same engine in
    # an asyncio streaming API with admission control.  submit() returns
    # a TokenStream (async-iterate tokens as the scheduler emits them;
    # aclose() cancels and frees the request's KV blocks); a background
    # pump drives engine.step() off the event loop through a one-worker
    # executor.  Admission: at most max_queue_depth requests in flight
    # (beyond it submit raises RejectedError(kind="backpressure")), and a
    # closed/open/half-open CircuitBreaker sheds arrivals
    # (kind="breaker") while preemption churn or pool saturation
    # persists — deadline=/priority= map onto preempt_policy="deadline"
    # so prioritized traffic is preempted last.  Streamed tokens are
    # bit-identical to the closed-loop run() path (tests/test_frontend.py);
    # `python -m benchmarks.serving_bench` drives Poisson open-loop
    # traces through this layer and reports p50/p99 TTFT/ITL and
    # goodput-under-SLO.
    if eng.mode == "continuous":
        import asyncio
        from repro.serving.frontend import AsyncFrontend

        async def stream_demo():
            async with AsyncFrontend(eng, max_queue_depth=8) as fe:
                stream = await fe.submit(np.arange(3, 12),
                                         max_new_tokens=6, priority=1)
                async for tok in stream:
                    print(f"streamed[{stream.uid}]: {tok}")
                return fe.stats

        fstats = asyncio.run(stream_demo())
        print(f"frontend: accepted={fstats.accepted} "
              f"completed={fstats.completed} "
              f"p99 TTFT {eng.stats.p99_ttft_s * 1e3:.1f}ms, "
              f"p99 ITL {eng.stats.p99_itl_s * 1e3:.1f}ms")

    # 4. Scale out — two rungs on top of one engine:
    #
    #   TENSOR scale-up: pass a ("data", "model") mesh to
    #   ServingEngine(mesh=...).  Weights shard per the serve specs, and
    #   the paged KV pool's kv-head axis (payload AND SCLAD scale
    #   leaves) shards over "model" — both paged attention paths then
    #   run under shard_map with block tables / lengths / starts
    #   broadcast and the per-shard kernel body unchanged, so there is
    #   NO pool-sized collective on the hot path.  Greedy outputs are
    #   bit-identical to the meshless engine (on CPU parity runs use
    #   float32 params: bf16 tensor-parallel psum reduction order can
    #   flip a greedy near-tie).  Try it without a TPU via forced host
    #   devices:
    #     PYTHONPATH=src python -m benchmarks.sharded_probe --model-parallel 2
    #     PYTHONPATH=src python -m repro.launch.dryrun --serving-smoke
    #
    #   DATA-PARALLEL scale-out: N independent replicas (each its own
    #   scheduler, pool, and breaker — nothing shared) behind
    #   serving.router.ReplicaRouter, one submit() surface.  Placement
    #   is prefix-AFFINITY by default: every replica's prefix cache is
    #   probed with the SAME hash chain admission uses, the request goes
    #   to the deepest match (block pools don't gossip — only the
    #   replica holding your system prompt's blocks can skip its
    #   prefill), and no-match traffic falls back to least-loaded.
    #   RejectedError surfaces only when EVERY replica rejected.
    #   The launcher exposes the same path:
    #     python -m repro.launch.serve --frontend async --replicas 2 \
    #         [--router-policy affinity|round_robin]
    if eng.mode == "continuous":
        import asyncio

        from repro.serving.router import ReplicaRouter

        def make_replica():
            return ServingEngine(cfg, params, max_batch=2, max_len=32,
                                 eos_id=-1, block_size=8,
                                 prefill_chunk=None)

        async def fleet_demo():
            async with ReplicaRouter([make_replica(),
                                      make_replica()]) as router:
                system = np.arange(5, 13)  # shared "system prompt"
                # Drain the first request so its prefix blocks commit —
                # affinity can only follow blocks that exist.
                first = await router.submit(
                    np.concatenate([system, [3, 4]]), max_new_tokens=3)
                async for _ in first:
                    pass
                streams = [await router.submit(
                    np.concatenate([system, tail]), max_new_tokens=3)
                    for tail in ([6, 7], [8, 9])]
                for st in streams:
                    async for _ in st:
                        pass
                return router.routing_report()

        rep = asyncio.run(fleet_demo())
        print(f"router: replicas={rep['replicas']} "
              f"per_replica={rep['per_replica_requests']} "
              f"affinity_hit_rate={rep['affinity_hit_rate']:.2f} "
              f"prefix_hit_rate={rep['prefix_hit_rate']:.2f}")

    # 5. Surviving failures.  Replicas die; the fleet should not drop
    # requests when they do.  Three pieces compose:
    #
    #   FAULT INJECTION (serving.faults): FaultyEngine wraps any engine
    #   and injects a seeded FaultPlan — crash (permanent death), hang
    #   (a step that "takes" N ticks), raise (transient exception), slow
    #   (skipped beats) — at the step() BOUNDARY only, counted in step
    #   ticks, never wall clock.  The same plan replays the same chaos
    #   bit-for-bit, so every failure scenario is a deterministic test
    #   (FaultPlan.seeded(seed) draws a reproducible schedule).
    #
    #   HEALTH TRACKING (serving.router.ReplicaHealth): each replica
    #   walks healthy -> suspect -> dead from tick-counted signals — a
    #   step whose cost exceeds deadline_ticks trips the watchdog, and
    #   crash_threshold consecutive step errors declare death.  Suspect
    #   replicas take only a probe request (success revives them);
    #   dead and router.drain(i)'d replicas are excluded from placement
    #   (drain also lets you take a replica down for maintenance and
    #   undrain(i) it back).
    #
    #   BIT-IDENTICAL FAILOVER: when a replica dies, its in-flight
    #   requests are resubmitted to a healthy replica as
    #   prompt + tokens-already-emitted — the same recompute path
    #   preemption uses — and the client's TokenStream continues
    #   SEAMLESSLY from the next token: no duplicates, no gaps, and the
    #   completed greedy output is bit-identical to a failure-free run.
    #   Each request retries at most retry_budget times before its
    #   stream surfaces RejectedError(kind="timeout").
    if eng.mode == "continuous":
        import asyncio

        from repro.serving.faults import FaultPlan, FaultyEngine
        from repro.serving.router import ReplicaRouter

        async def chaos_demo():
            # Replica 0 will crash at step tick 2 — mid-decode for the
            # request below; replica 1 stays healthy as failover target.
            doomed = FaultyEngine(make_replica(), FaultPlan.crash_at(2))
            async with ReplicaRouter([doomed, make_replica()],
                                     policy="round_robin") as router:
                stream = await router.submit(np.arange(4, 12),
                                             max_new_tokens=6)
                toks = [t async for t in stream]
                return toks, router.fault_report()

        toks, ft = asyncio.run(chaos_demo())
        print(f"failover: tokens={toks} "
              f"deaths={ft['replica_deaths']} "
              f"failovers={ft['failovers']} "
              f"health={ft['health']}")
        # The launcher exposes the same chaos knobs end to end:
        #   python -m repro.launch.serve --frontend async --replicas 3 \
        #       --fault-crash-replica 0 --fault-crash-tick 24 \
        #       [--fault-seed 7] [--drain-replica 2] [--retry-budget 3]
        # and its report gains availability + fault_tolerance blocks.


if __name__ == "__main__":
    main()
