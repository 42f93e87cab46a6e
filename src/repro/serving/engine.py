"""Serving engine: continuous batching over a ref-counted paged KV cache.

The engine prices exactly what the paper's TCO/token metric prices: the
generate stage under heavy multi-tenant load.  PR 1 replaced the seed's
lockstep wave batcher with Orca-style iteration-level scheduling; PR 2 made
KV memory block-granular (paged allocation + chunked prefill).  This version
makes the block pool a **shared, content-addressed store** and drops the
worst-case reservation:

  * the KV cache is ONE pool of fixed-size token blocks
    (``model.init_paged_cache``) addressed through per-lane block tables in
    the jitted decode/prefill steps — unchanged from PR 2;
  * **prefix caching**: full blocks are registered in a hash-chained prefix
    index (``serving.paged.BlockStore``).  ``admit`` matches the longest
    cached prefix of the prompt and the lane STARTS with those blocks —
    prefill runs only the uncached tail, entering the existing chunked
    continuation path with ``start = cached_len``.  Requests sharing a
    system prompt or few-shot header therefore share its KV bytes and skip
    its prefill compute.  At least one prompt token is always recomputed
    (decode needs the last-token logits);
  * retired requests' full blocks linger in an **LRU pool** (still
    matchable) until allocation pressure evicts them, so a request admitted
    after its prefix donor finished still hits;
  * **copy-on-write**: before any write the engine runs a write barrier
    (``ensure_writable``) — a block another lane can read is swapped for a
    fresh block and its device payload copied, so sharing is never
    observable through the attention gather.  (With full-block-only sharing
    writes land past the shared prefix by construction; the barrier makes
    that an enforced invariant rather than an accident.)
  * **optimistic admission + preemption**: nothing is reserved.  A request
    is admitted when the store can cover its *uncached prompt* plus one
    decode block; decode growth may then run the pool dry
    (``OutOfBlocks``), and the engine **preempts the youngest request** —
    release its blocks, re-queue it at the head with its generated tokens
    appended to the prompt, recompute on re-admission.  Its full blocks
    usually survive in the LRU pool, so the recompute is mostly prefix-cache
    hits.  Sampling keys are POSITIONAL — token p of request uid samples
    with ``fold_in(fold_in(seed, uid), p)`` — so stochastic outputs are
    independent of co-tenants AND unchanged by preemption, with O(1)
    resume;
  * **multi-step decode** (``decode_steps=k``): the jitted step runs k
    decode iterations per host sync (``lax.scan`` with masked early-exit on
    EOS/budget retirement), amortizing dispatch + device->host latency over
    k tokens.  Defaults to 1 (bit-identical to the single-step engine);
  * **fused paged attention, decode AND prefill**: the jitted decode
    step's attention reads go through
    ``kernels.flash_decode.ops.decode_attention`` and every prefill
    chunk's through ``kernels.flash_prefill.ops.prefill_attention`` — on
    TPU the Pallas kernels walk each lane's blocks through its table
    straight out of the shared pool (KV bytes streamed exactly once, the
    CC-MEM contract), instead of first gathering a dense O(B*T*bs*Hk*D)
    per-lane copy of the pool; the prefill kernel additionally derives
    the causal/left-pad mask from scalars (no dense (B, S, S) mask) and
    scatters the chunk's new K/V into the pool INSIDE the same kernel
    invocation (``input_output_aliases`` — no separate HBM round-trip).
    ``attn_kernel`` selects the implementation for both paths
    ("auto"/"on"/"off"; "on" uses Pallas interpret mode off-TPU — the CI
    parity path); ``decode_kernel=`` is accepted as a deprecated alias.

Correctness contract (pinned by tests/test_continuous_batching.py): greedy
outputs are bit-identical with prefix caching on or off, across concurrent
prefix sharers, LRU revivals and preemption-recompute.

Knobs (see also examples/quickstart.py):
  * ``block_size`` — tokens per KV block.  Small blocks (8-16) minimize
    fragmentation AND maximize prefix-sharing granularity (only FULL blocks
    are shared); ``block_size >= max_len`` degenerates to one stripe per
    request.
  * ``num_blocks`` — pool size; defaults to ``max_batch`` full-length
    stripes' worth.
  * ``prefill_chunk`` — max prompt tokens prefilled per scheduler
    iteration (None = whole prompt in one call).
  * ``prefix_cache`` — block sharing on/off (off: every block exclusive,
    released blocks return straight to the free list).
  * ``decode_steps`` — decode iterations per jitted step / host sync.
  * ``attn_kernel`` — attention-kernel implementation for the paged
    decode AND chunked-prefill hot paths ("auto" = kernels on TPU /
    references elsewhere; "on" forces the kernels, interpret mode
    off-TPU; "off" forces the jnp references — the pre-kernel gather
    paths).  ``decode_kernel`` is the deprecated PR-4 spelling.
  * ``preempt_policy`` — pool-pressure victim selection: "youngest"
    (default), "largest" (most blocks held) or "deadline".  Under
    "deadline" eviction order is STRICT on ``submit(deadline=...)``:
    the latest deadline (most slack) is evicted first, and a request
    with ``deadline=None`` is treated as infinitely late — evicted
    before ANY request that named a deadline (ties broken youngest-
    first).  This makes ``deadline=`` the admission-priority surface:
    the async frontend (``serving.frontend``) maps request priorities
    onto it, so deadline-less best-effort traffic is always shed ahead
    of SLO-carrying traffic.  Pinned by
    tests/test_decode_dispatch.py::test_preempt_policy_deadline_strict_order.
  * ``kv_dtype`` — on-device KV pool representation.  "fp"/"bf16" store
    dense compute-dtype blocks; "int8"/"fp8" store the SCLAD compressed
    pool (``models.kv_quant``: int8 / float8_e4m3fn payload + per-
    position-per-head fp32 scales) — every reader dequantizes on load,
    so a fixed device byte budget holds ~2x the token context.  The
    prefix-cache hash chain is namespaced per encoding
    (``paged.chain_root_for``), so pools with different kv_dtype
    settings can never false-share blocks.  Composes with
    ``attn_kernel``: both the jnp references and the Pallas kernels
    fuse the dequant into their block-streaming loops.

vlm note: the patch prefix is part of each lane's cache, so its positions
enter the hash chain as sentinel ids and the PATCH-EMBEDDING DIGEST seeds
the lane's chain root: requests submitted with the same image (or both
with the zero stub, the default) share the prefix; identical token ids
with different images can never false-share.

Families with attention KV caches (dense, moe, vlm) run this continuous
path.  SSM/hybrid/audio recurrent state cannot be left-pad-masked without
polluting the scan state, so those families fall back to the seed's wave
batching; ``mode="wave"`` forces that path for any family.

On a multi-device mesh, pass ``mesh=``: parameters and the cache are placed
with the serve shardings from ``parallel.sharding`` (mode="serve").  Axis
state is ENGINE-SCOPED (``sharding.use_axes`` wraps every jitted-function
body), so several engines with different meshes can coexist in one process
and nothing leaks into ambient sharding state.

Tracing: each ``step()`` writes ``jax.profiler.TraceAnnotation`` host spans
— ``serve.step`` around it; inside, ``serve.admit`` per admission attempt
(``uid=``), ``serve.grow`` for block growth and copy-on-write,
``serve.prefill.inputs`` / ``serve.prefill.run`` (``rows=``),
``serve.decode.inputs`` / ``serve.decode.run`` and ``serve.emit``.  With a
profiler running they land on its host plane, on the device trace's clock;
without one each costs about a microsecond.  ``EngineStats`` counts the
matching times (queue wait, admission to first token, step host time).
"""
from __future__ import annotations

import functools
import hashlib
import math
import time
import warnings
from dataclasses import dataclass, field, replace as dc_replace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.kernels.flash_prefill.ops import ATTN_KERNEL_MODES
from repro.models import kv_quant
from repro.models import model as M
from repro.parallel import sharding
from repro.serving.paged import (BlockStore, CHAIN_ROOT, OutOfBlocks,
                                 TRASH_BLOCK, chain_hashes, chain_root_for)
from repro.serving.sampler import SamplerConfig, positional_keys, sample
from repro.serving.spec import SPEC_DECODE_MODES, make_proposer

# Families whose KV cache supports block-level admission (see module doc).
CONTINUOUS_FAMILIES = ("dense", "moe", "vlm")

#: Victim-selection policies for pool-pressure preemption.
PREEMPT_POLICIES = ("youngest", "largest", "deadline")


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32 — the ORIGINAL prompt
    max_new_tokens: int
    output: List[int] = field(default_factory=list)
    done: bool = False
    #: Soft completion deadline (any monotone unit; only ORDER matters) —
    #: consumed by preempt_policy="deadline".  None = no deadline.
    deadline: Optional[float] = None
    #: vlm only: per-request patch embeddings (num_patches, d_model); None
    #: = the engine-constant zero stub.
    patch_embeds: Optional[np.ndarray] = None
    #: sha256 chain-root seed derived from patch_embeds (vlm) or the
    #: global CHAIN_ROOT — two requests may share prefix blocks only if
    #: their seeds agree, so identical token ids with different images
    #: never false-share.
    chain_seed: bytes = CHAIN_ROOT


@dataclass
class _Prefilling:
    """A request mid-admission: its prompt is entering the cache in chunks.

    ``tokens`` is the EFFECTIVE prompt (original prompt plus any tokens
    generated before a preemption — recompute replays them as prompt).
    ``consumed`` counts effective-prompt tokens already in the cache; it
    starts at the prefix-cache hit length, so prefill begins at the
    uncached tail.  ``cached_len`` is the cache-position hit length
    (including any vlm patch prefix) — nonzero means the first chunk uses
    the continuation path (the cached context is gathered, patches are NOT
    re-embedded)."""
    req: Request
    lane: int
    budget: int  # decode budget remaining (clamped; minus pre-preemption output)
    tokens: np.ndarray
    consumed: int = 0
    cached_len: int = 0
    counted_cached: int = 0  # cached tokens credited to stats at admission


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    prefill_chunks: int = 0
    cached_prompt_tokens: int = 0  # prompt tokens skipped via prefix cache
    generated_tokens: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    decode_steps: int = 0
    admissions: int = 0
    preemptions: int = 0
    # Concurrency capacity (continuous mode): peak simultaneously DECODING
    # lanes — requests that finished prefill and hold every block they
    # need.  Admission is optimistic (lanes fill before blocks are
    # consumed), so under pool pressure this — not admissions — is what
    # the pool caps: preemption evicts the overflow during the prefill
    # storm and the survivors decode together.  The SCLAD capacity claim
    # is exactly this number at a fixed pool byte budget — a compressed
    # pool affords more blocks, so more lanes sustain concurrently.
    peak_decode_lanes: int = 0
    # Time-to-first-token (submit -> first generated token observed at a
    # host sync), summed over finished-first-token requests.  The paged
    # flash-prefill work prices exactly this: TTFT is the prefill-side
    # latency metric the decode-side tokens_per_s cannot see.
    ttft_s_sum: float = 0.0
    ttft_count: int = 0
    # Per-request latency DISTRIBUTIONS (open-loop serving prices tails,
    # not means — a p99 TTFT SLO is what admission control defends):
    #   ttft_history — one submit->first-token sample per request;
    #   itl_history  — inter-token latency samples at OBSERVATION
    #     granularity: tokens are released to the host at decode-window
    #     syncs, so each token after a request's first records the gap
    #     since that request's previous observation, divided evenly over
    #     the tokens released in the same window (with decode_steps=1
    #     every sample is a real host-sync gap; a preemption recompute
    #     shows up as one honest, long gap — exactly the client's stall).
    ttft_history: List[float] = field(default_factory=list)
    itl_history: List[float] = field(default_factory=list)
    # Requests aborted by the caller mid-flight (async frontend
    # cancellation); their blocks are released like a retirement.
    cancellations: int = 0
    # Peak PHYSICAL pool occupancy: blocks referenced by >= 1 lane at the
    # worst moment (retired-but-resident LRU blocks do NOT count — they
    # are reclaimable).  This is the number CC-MEM capacity planning
    # prices.  kv_block_bytes is device bytes per block across all
    # layers, K+V (filled in by the engine).
    peak_live_blocks: int = 0
    kv_block_bytes: int = 0
    # Occupancy: active lanes summed over decode steps vs. lane capacity.
    occupied_slot_steps: int = 0
    slot_steps: int = 0
    # KV memory: live LOGICAL tokens summed over decode steps vs. pool
    # tokens.  With prefix sharing the ratio can exceed 1.0 — lanes are
    # serving more token-context than the pool physically stores.
    used_token_steps: int = 0
    pool_token_steps: int = 0
    # Speculative decoding (spec_decode != "off"): verify passes run, draft
    # tokens proposed, and draft tokens accepted (the emitted-ahead-of-
    # plain-decode tokens; the per-pass anchor token is not a draft and
    # counts in neither).  acceptance = accepted / proposed is the knob
    # benchmarks watch: every accepted draft amortizes one full-pool KV
    # sweep, every rejected one cost a wasted optimistic write + rollback.
    spec_passes: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    # Where a request's time goes before its first token, and the host's
    # share of a step; each sum has its count:
    #   handoff_s / submitted — the caller's arrival stamp
    #     (``submit(arrived_t=)``, the async front door's acceptance) to
    #     engine submit, over stamped submits only;
    #   queue_wait_s / admissions — engine queue to a lane (a preempted
    #     request waits again from its re-queue);
    #   admit_to_first_s / first_tokens — first admission to first token,
    #     so with no preemption queue wait plus this is the TTFT sum;
    #   prefill_left_out / prefill_member_ticks — per prefill step, the
    #     requests mid-prompt whose chunk did not run (they are in the
    #     other first/continuation cohort), over the requests mid-prompt;
    #   step_s / engine_steps — wall time of ``step()``, host work and
    #     the prefill/decode intervals above included;
    #   between_steps_s / between_steps — end of a step that left work
    #     pending to the start of the next: the caller's own overhead.
    submitted: int = 0
    handoff_s: float = 0.0
    queue_wait_s: float = 0.0
    admit_to_first_s: float = 0.0
    first_tokens: int = 0
    prefill_left_out: int = 0
    prefill_member_ticks: int = 0
    step_s: float = 0.0
    engine_steps: int = 0
    between_steps_s: float = 0.0
    between_steps: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / max(self.decode_s, 1e-9)

    @property
    def prefill_tokens_per_s(self) -> float:
        """Prompt tokens prefilled per second of prefill wall time (cached
        prefix tokens are skipped work — they do not count)."""
        return self.prefill_tokens / max(self.prefill_s, 1e-9)

    @property
    def mean_ttft_s(self) -> float:
        """Mean submit->first-token latency over requests that produced at
        least one token."""
        return self.ttft_s_sum / max(self.ttft_count, 1)

    @staticmethod
    def percentile(history: List[float], q: float) -> float:
        """Nearest-rank percentile: the ceil(q/100 * n)-th order statistic
        (q in (0, 100]).  Always an OBSERVED sample — no interpolation —
        so unit pins on hand-built histories are exact.  Empty history
        returns 0.0 (no traffic, no tail)."""
        if not history:
            return 0.0
        if not 0.0 < q <= 100.0:
            raise ValueError(f"percentile q={q} outside (0, 100]")
        xs = sorted(history)
        return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]

    @property
    def p50_ttft_s(self) -> float:
        return self.percentile(self.ttft_history, 50.0)

    @property
    def p99_ttft_s(self) -> float:
        return self.percentile(self.ttft_history, 99.0)

    @property
    def p50_itl_s(self) -> float:
        return self.percentile(self.itl_history, 50.0)

    @property
    def p99_itl_s(self) -> float:
        return self.percentile(self.itl_history, 99.0)

    @property
    def slot_occupancy(self) -> float:
        return self.occupied_slot_steps / max(self.slot_steps, 1)

    @property
    def mean_active_requests(self) -> float:
        """Concurrent in-decode requests averaged over decode steps."""
        return self.occupied_slot_steps / max(self.decode_steps, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens served from the prefix cache instead
        of being prefilled (recompute after preemption counts as prefill,
        so thrash shows up here too)."""
        seen = self.cached_prompt_tokens + self.prefill_tokens
        return self.cached_prompt_tokens / max(seen, 1)

    @property
    def block_utilization(self) -> float:
        """Live logical tokens vs. pool token capacity, averaged over
        decode steps.  >1.0 means prefix sharing is serving more context
        than the pool stores — the capacity win §4.2 prices into
        TCO/token."""
        return self.used_token_steps / max(self.pool_token_steps, 1)

    @property
    def peak_pool_bytes(self) -> int:
        """Peak device bytes held by live KV blocks."""
        return self.peak_live_blocks * self.kv_block_bytes

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted draft tokens over proposed draft tokens (0.0 when
        speculation is off or never proposed anything)."""
        return self.spec_accepted / max(self.spec_proposed, 1)


def _bucket(n: int, cap: int) -> int:
    """Smallest power-of-two >= n (min 8), capped at cap."""
    p = 8
    while p < n:
        p *= 2
    return min(p, cap)


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 256, eos_id: int = 0,
                 sampler: Optional[SamplerConfig] = None,
                 mode: str = "auto", pad_id: int = 0, seed: int = 0,
                 mesh=None, block_size: int = 8,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = 32,
                 prefix_cache: bool = True,
                 decode_steps: int = 1,
                 attn_kernel: Optional[str] = None,
                 decode_kernel: Optional[str] = None,
                 preempt_policy: str = "youngest",
                 kv_dtype: Optional[str] = None,
                 spec_decode: str = "off", spec_k: int = 4):
        """mode: "auto" (continuous where the family supports it),
        "continuous" (error if unsupported) or "wave" (force the legacy
        lockstep baseline).

        mesh: optional device mesh for tensor scale-up.  One engine is ONE
        controller over one mesh — the single-controller-per-replica model:
        the host-side scheduler (queue, BlockStore, block tables, preempt/
        admit decisions) runs unreplicated on this process, and the mesh
        only widens the jitted device work.  What shards over the mesh's
        ``model`` axis: the weights (``param_specs(mode="serve")``), the
        paged KV pool's KV heads — payload AND SCLAD scale leaves,
        co-placed by ``cache_specs(paged=True)`` — and, through the
        ``shard_map`` wrappers in ``kernels.*.ops``, the attention heads
        of both paged hot paths.  What broadcasts: block tables, lengths/
        start vectors and every other scalar-prefetch operand, sampled
        tokens, logits, and all scheduler state.  Replica scale-OUT (many
        engines, each with its own mesh or none) lives one level up in
        ``serving.router.ReplicaRouter``.

        block_size / num_blocks / prefill_chunk / prefix_cache /
        decode_steps: paged-KV and scheduler knobs, see the module
        docstring.

        attn_kernel: overrides ``cfg.attn_kernel`` — the implementation of
        BOTH paged attention hot paths (flash-decode and flash-prefill):
        "auto" (Pallas kernels on TPU, jnp references elsewhere), "on"
        (always the kernels; interpret mode off-TPU) or "off" (always the
        references).  None keeps the config's setting.  ``decode_kernel=``
        is the deprecated PR-4 spelling and maps onto ``attn_kernel`` with
        a DeprecationWarning.

        preempt_policy: which in-flight request pool pressure evicts —
        "youngest" (highest uid; the default, matches prior behavior),
        "largest" (most KV blocks held: frees the most memory per
        eviction) or "deadline" (latest ``submit(deadline=...)`` first;
        requests without a deadline are evicted before any with one).

        kv_dtype: overrides ``cfg.kv_dtype`` — the paged pool's on-device
        representation: "fp"/"bf16" (dense compute-dtype blocks, the
        default), "f8" (dense float8 stripes, legacy), or the SCLAD
        compressed encodings "int8"/"fp8" (payload + per-position fp32
        scales; ~2x token context per device byte, dequantized on load
        by references and kernels alike).  None keeps the config's
        setting.  See the module docstring.

        spec_decode / spec_k: speculative multi-token decoding ("off" or
        "ngram"; continuous mode only).  Per scheduler step each lane
        samples its next token as usual, then a draft proposer
        (``serving.spec``) proposes up to ``spec_k`` continuation tokens
        from the request's own history; the (anchor + drafts) chunk is
        scored in ONE pass through the chunked-prefill path (drafted K/V
        written into the pool optimistically) and the engine keeps the
        longest draft prefix matching what plain decode would have
        sampled, rolling the rejected tail back via
        ``BlockStore.truncate``.  Correctness contract: emitted tokens
        are BIT-IDENTICAL to ``spec_decode="off"`` for greedy AND
        stochastic sampling — the verify pass re-samples each drafted
        position with the SAME positional PRNG key plain decode would
        have used (``sampler.positional_keys``: the token at position p
        of request uid draws from ``fold_in(fold_in(seed, uid), p)``).
        The PRNG "fast-forward" rule falls out of that: positions only
        advance by ACCEPTED tokens, so the stochastic stream never skips
        ahead over rejected drafts — speculation changes throughput,
        never outputs.  With speculation on, each ``step()`` runs one
        verify pass (up to ``spec_k + 1`` tokens per lane) and
        ``decode_steps`` window batching is not used.

        Scope of the bit-identity contract: it is EXACT on the jnp
        reference path (``attn_kernel="off"``, or "auto" off-TPU).
        Under ``attn_kernel="on"`` speculation moves decode-position
        scoring from the flash-decode kernel into the flash-prefill
        kernel, whose online-softmax accumulation tiles keys differently
        (context blocks split at ``start`` plus one in-chunk tile vs
        block-aligned tiles) — the same cross-implementation situation
        as kernel-vs-reference, and the same contract applies: logits
        agree to dtype tolerance, a near-tie greedy argmax can flip, and
        all scheduling invariants (prefix sharing, preemption recompute,
        chunked prefill) still hold bit-identically WITHIN the
        speculative configuration.
        """
        if decode_steps < 1:
            raise ValueError("decode_steps must be >= 1")
        if spec_decode not in SPEC_DECODE_MODES:
            raise ValueError(
                f"spec_decode {spec_decode!r} not in {SPEC_DECODE_MODES}")
        if spec_k < 1:
            raise ValueError("spec_k must be >= 1")
        if preempt_policy not in PREEMPT_POLICIES:
            raise ValueError(
                f"preempt_policy {preempt_policy!r} not in "
                f"{PREEMPT_POLICIES}")
        if decode_kernel is not None:
            warnings.warn(
                "ServingEngine(decode_kernel=...) is deprecated; the knob "
                "now selects the prefill kernel too — use attn_kernel=",
                DeprecationWarning, stacklevel=2)
            if attn_kernel is not None and attn_kernel != decode_kernel:
                raise ValueError(
                    f"conflicting attn_kernel={attn_kernel!r} and "
                    f"decode_kernel={decode_kernel!r}")
            attn_kernel = decode_kernel
        if attn_kernel is not None:
            if attn_kernel not in ATTN_KERNEL_MODES:
                raise ValueError(
                    f"attn_kernel (nee decode_kernel) {attn_kernel!r} not "
                    f"in {ATTN_KERNEL_MODES}")
            cfg = dc_replace(cfg, attn_kernel=attn_kernel)
        if kv_dtype is not None:
            if kv_dtype not in kv_quant.KV_DTYPES:
                raise ValueError(
                    f"kv_dtype {kv_dtype!r} not in {kv_quant.KV_DTYPES}")
            cfg = dc_replace(cfg, kv_dtype=kv_dtype)
        #: Prefix-cache chain root, namespaced by the pool encoding so an
        #: int8 pool can never revive/share blocks hashed for an fp pool.
        self._chain_root = chain_root_for(cfg.kv_dtype)
        self.preempt_policy = preempt_policy
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.sampler = sampler or SamplerConfig()
        self.stats = EngineStats()
        #: Poisoned-engine flag (see ``step()``): True once a failing
        #: step left the ``BlockStore`` inconsistent.  A poisoned engine
        #: refuses step()/submit() — its pool may hold half-applied
        #: state — and its replica must be failed over, not retried.
        self.poisoned = False
        self._queue: List[Request] = []
        self._instant: List[Tuple[int, List[int]]] = []  # zero-budget retires
        #: uid -> submit wall time, consumed when its first token lands.
        self._submit_t: Dict[int, float] = {}
        #: uid -> time it entered the queue (submit or preemption
        #: re-queue), consumed at admission.
        self._queued_t: Dict[int, float] = {}
        #: uid -> time of its first admission, consumed at its first token.
        self._admit_t: Dict[int, float] = {}
        #: End of the last step() if it left work pending, else None.
        self._busy_end_t: Optional[float] = None
        #: uid -> host time of the request's latest observed token (feeds
        #: the inter-token-latency history).
        self._last_obs_t: Dict[int, float] = {}
        #: Optional per-token hook ``on_token(uid, token)`` — called on
        #: the engine's (caller's) thread for EVERY generated token as it
        #: is observed at a host sync, before the owning request
        #: finishes.  This is the streaming surface the async frontend
        #: rides (``serving.frontend``); leave None to skip the calls.
        #: Preemption recompute replays tokens as PROMPT, so no token is
        #: ever re-announced.
        self.on_token: Optional[Callable[[int, int], None]] = None
        #: uid -> (content length, chain digests): a queue head waiting
        #: for capacity is re-matched every scheduler step — hash its
        #: prompt once, not once per step.
        self._digest_cache: Dict[int, Tuple[int, List[bytes]]] = {}
        self._uid = 0

        if mode == "auto":
            mode = "continuous" if cfg.family in CONTINUOUS_FAMILIES \
                else "wave"
        if mode == "continuous" and cfg.family not in CONTINUOUS_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} has no block-addressable KV cache; "
                f"use mode='wave'")
        if spec_decode != "off" and mode != "continuous":
            raise ValueError(
                "spec_decode requires the continuous (paged) engine: the "
                "verifier is the paged chunked-prefill path and rollback "
                "is a BlockStore operation")
        self.mode = mode
        self.spec_decode = spec_decode
        self.spec_k = spec_k
        self._proposer = make_proposer(spec_decode)
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.decode_steps = decode_steps

        self.params = params
        self._mesh = mesh
        self._axes = sharding.AxisState.from_mesh(mesh)
        if mesh is not None:
            self.params = self._place_serve(mesh, params)

        # CPU backend has no buffer donation; skip it to avoid warnings.
        donate = jax.default_backend() != "cpu"

        # Legacy wave path (also the fallback for recurrent-state families).
        self._prefill = jax.jit(
            self._scoped(lambda p, b: M.prefill(cfg, p, b, max_len)))
        self._decode = jax.jit(
            self._scoped(lambda p, c, t, pos: M.decode_step(cfg, p, c, t,
                                                            pos)))

        if self.mode == "continuous":
            self._init_continuous(donate, seed)

    def _scoped(self, fn):
        """Run ``fn`` (a to-be-jitted body) under THIS engine's axis state,
        so trace-time sharding anchors see the engine's mesh — not whatever
        ambient state the process happens to carry."""
        axes = self._axes

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with sharding.use_axes(axes):
                return fn(*args, **kwargs)
        return wrapped

    # -- public API ----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               deadline: Optional[float] = None,
               patch_embeds: Optional[np.ndarray] = None,
               arrived_t: Optional[float] = None) -> int:
        """Queue a request.  ``deadline`` feeds preempt_policy="deadline";
        ``patch_embeds`` (vlm only, (num_patches, d_model)) is the
        request's image frontend — its digest seeds the prefix-cache hash
        chain, so only requests with the SAME image (or both the zero
        stub) can share prefix blocks.  ``arrived_t`` is the
        ``time.perf_counter()`` at which a front door accepted the
        request; the wait until this call adds to ``stats.handoff_s``."""
        if self.poisoned:
            raise RuntimeError(
                "engine is poisoned: an earlier step() failure left the "
                "block store inconsistent; build a fresh engine")
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) >= self.max_len:
            # Same bound in both modes (and regardless of budget): wave
            # prefill would otherwise fail deep in cache padding (or
            # silently emit nothing at exactly max_len).
            raise ValueError(
                f"prompt length {len(prompt)} leaves no decode room in a "
                f"{self.max_len}-token cache")
        if patch_embeds is not None:
            if self.cfg.family != "vlm":
                raise ValueError(
                    f"patch_embeds is vlm-only (family is "
                    f"{self.cfg.family!r})")
            patch_embeds = np.asarray(patch_embeds, np.float32)
            want = (self.cfg.num_patches, self.cfg.d_model)
            if patch_embeds.shape != want:
                raise ValueError(
                    f"patch_embeds shape {patch_embeds.shape} != {want}")
        self._uid += 1
        uid = self._uid
        if self.mode == "continuous" and max_new_tokens >= 1:
            worst = self._worst_case_tokens(prompt, max_new_tokens)
            need = self._alloc.blocks_for(worst)
            cap = min(self._alloc.num_blocks, self._alloc.max_blocks_per_slot)
            if need > cap:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool/block "
                    f"table caps at {cap}; it can never be admitted "
                    f"(raise num_blocks or shorten the prompt/budget)")
        now = time.perf_counter()
        if arrived_t is not None:
            self.stats.submitted += 1
            self.stats.handoff_s += now - arrived_t
        if max_new_tokens < 1:
            # A zero-budget request retires immediately with an empty
            # output: it never touches the scheduler or the block pool.
            self._instant.append((uid, []))
            return uid
        self._submit_t[uid] = now
        if self.mode == "continuous":
            self._queued_t[uid] = now
        self._queue.append(Request(
            uid, prompt, max_new_tokens, deadline=deadline,
            patch_embeds=patch_embeds,
            chain_seed=self._chain_seed(patch_embeds)))
        return uid

    def _note_tokens(self, uid: int, m: int, now: float) -> None:
        """Record latency samples for ``m`` tokens of request ``uid``
        observed at host time ``now``: the request's first token ever is a
        TTFT sample; every later token an inter-token-latency sample at
        observation granularity (see ``EngineStats.itl_history``) — the
        host-sync gap spread evenly over the ``m`` tokens of the window.

        Every multi-token emission path shares this one rule: a
        ``decode_steps > 1`` window passes the tokens the window released,
        and a speculative verify pass passes the ACCEPTED count (anchor +
        accepted drafts) — never the proposed count, so rejected drafts
        cannot dilute the distribution with tokens the client never
        received.  Pinned in tests/test_latency_stats.py."""
        if m <= 0:
            return
        prev = self._last_obs_t.get(uid)
        if prev is None:
            t0 = self._submit_t.pop(uid, None)
            if t0 is not None:
                self.stats.ttft_s_sum += now - t0
                self.stats.ttft_count += 1
                self.stats.ttft_history.append(now - t0)
            ta = self._admit_t.pop(uid, None)
            if ta is not None:
                self.stats.admit_to_first_s += now - ta
                self.stats.first_tokens += 1
            # Any further tokens in this first window left the same host
            # sync as the first token: there is no measurable gap, so
            # they contribute no ITL samples (they are part of TTFT).
        else:
            self.stats.itl_history.extend([(now - prev) / m] * m)
        self._last_obs_t[uid] = now

    def _chain_seed(self, patch_embeds: Optional[np.ndarray]) -> bytes:
        """Per-request prefix-cache chain root.  Non-vlm content is fully
        determined by token ids -> the global root; vlm K/V additionally
        depends on the image, so the patch embeddings' digest is folded in
        (the None zero-stub gets its own constant seed, preserving
        stub-to-stub sharing).  All digests grow from the engine's
        kv_dtype-namespaced chain root: quantized pools store DIFFERENT
        bytes for the same token ids, so their content addresses must
        never collide with an fp pool's."""
        if self.cfg.family != "vlm":
            return self._chain_root
        if patch_embeds is None:
            return hashlib.sha256(
                self._chain_root + b"|vlm-zero-stub").digest()
        return hashlib.sha256(
            self._chain_root + patch_embeds.tobytes()).digest()

    def step(self) -> List[Tuple[int, List[int]]]:
        """One scheduler iteration: admit queued requests onto free lanes
        (prefix-cache matched), run ONE prefill chunk for admitting
        prompts, then ``decode_steps`` jitted masked decode iterations
        across all lanes.  Block-pool pressure anywhere in here preempts
        the youngest request (see module docstring).

        Returns the requests finished this iteration as (uid, tokens).

        Exception safety — the POISONED-ENGINE contract: when the step
        body raises, the engine re-checks the ``BlockStore`` invariants
        before re-raising.  If they hold, the failure was transient and
        the engine stays usable (every request keeps its lane/blocks; the
        next ``step()`` resumes where this one stopped).  If they do NOT
        hold, the engine marks itself ``poisoned`` and every later
        ``step()``/``submit()`` raises immediately — a half-applied
        scheduler iteration must never be stepped again (it could serve
        corrupt KV), and the caller (the replica router's health layer)
        must fail its requests over to a healthy replica instead.
        """
        if self.mode != "continuous":
            raise RuntimeError(
                f"step() requires mode='continuous' (engine is in "
                f"{self.mode!r} mode); use run()")
        if self.poisoned:
            raise RuntimeError(
                "engine is poisoned: an earlier step() failure left the "
                "block store inconsistent; build a fresh engine")
        t0 = time.perf_counter()
        if self._busy_end_t is not None:
            self.stats.between_steps_s += t0 - self._busy_end_t
            self.stats.between_steps += 1
        try:
            with TraceAnnotation("serve.step"):
                return self._step()
        except Exception:
            try:
                self._alloc.check_invariants()
            except Exception:
                self.poisoned = True
            raise
        finally:
            end = time.perf_counter()
            self.stats.step_s += end - t0
            self.stats.engine_steps += 1
            # Time until the next step counts as the caller's overhead
            # only while work is waiting; an empty engine waits on load.
            self._busy_end_t = end if self.has_pending_work() else None

    def _step(self) -> List[Tuple[int, List[int]]]:
        finished: List[Tuple[int, List[int]]] = list(self._instant)
        self._instant = []
        self._admit()
        self._prefill_step()
        if not self._host_active.any():
            return finished
        if self._proposer is not None:
            return self._spec_step(finished)

        K = self.decode_steps
        # Hand each about-to-decode lane the blocks its next (up to K)
        # tokens land in.  Growth is optimistic: OutOfBlocks preempts the
        # youngest request and retries — possibly preempting the growing
        # lane itself.
        with TraceAnnotation("serve.grow"):
            for i in np.nonzero(self._host_active)[0]:
                i = int(i)
                if not self._host_active[i]:
                    continue  # preempted while an earlier lane grew
                steps_i = min(K, int(self._host_rem[i]))
                lo = self._prefix + int(self._host_pos[i])
                self._grow_for_writes(
                    i, lo, lo + steps_i,
                    alive=lambda i=i: bool(self._host_active[i]))
        if not self._host_active.any():
            return finished
        self._note_peak()
        with TraceAnnotation("serve.decode.inputs"):
            tables = jnp.asarray(self._alloc.block_table())

        with TraceAnnotation("serve.decode.run"):
            t0 = time.perf_counter()
            (self._cache, self._logits, self._pos, self._active,
             self._budget, host_out) = self._decode_fn(
                self.params, self._cache, self._logits, self._pos,
                self._active, self._budget, self._keys, tables)
            host = np.asarray(host_out)  # (2, K, B): the per-window sync
            tok_h, active_h = host[0], host[1].astype(bool)
            self.stats.decode_s += time.perf_counter() - t0
        with TraceAnnotation("serve.emit"):
            self._emit_window(finished, tok_h, active_h)
        return finished

    def _emit_window(self, finished: List[Tuple[int, List[int]]],
                     tok_h: np.ndarray, active_h: np.ndarray) -> None:
        """Hand a decode window's tokens (``tok_h``/``active_h``, (K, B))
        to their requests: stream, record latency, commit full blocks and
        retire finished lanes into ``finished``."""
        K = self.decode_steps
        was = self._host_active.copy()
        self.stats.peak_decode_lanes = max(self.stats.peak_decode_lanes,
                                           int(was.sum()))
        self.stats.decode_steps += K
        self.stats.slot_steps += self.max_batch * K
        self.stats.used_token_steps += self._alloc.live_tokens * K
        self.stats.pool_token_steps += self._alloc.num_blocks \
            * self._alloc.block_size * K

        bs = self._alloc.block_size
        now = time.perf_counter()
        for i in np.nonzero(was)[0]:
            i = int(i)
            r = self._slot_req[i]
            pos_before = self._prefix + int(self._host_pos[i])
            alive, emitted = True, 0
            for j in range(K):
                if not alive:
                    break
                tok = int(tok_h[j, i])
                r.output.append(tok)
                emitted += 1
                if self.on_token is not None:
                    self.on_token(r.uid, tok)
                self._host_pos[i] += 1
                self._host_rem[i] -= 1
                self.stats.generated_tokens += 1
                self.stats.occupied_slot_steps += 1
                alive = bool(active_h[j, i])
            self._note_tokens(r.uid, emitted, now)
            if self.prefix_cache and \
                    (self._prefix + int(self._host_pos[i])) // bs \
                    != pos_before // bs:
                # A block boundary was crossed: the freshly-filled full
                # block(s) become matchable for future admissions.  (The
                # store's chain cache makes this O(new blocks), and the
                # boundary check keeps the common no-new-block window from
                # paying even the content-array concat.)
                self._alloc.commit_full(i, self._content_ids(r))
            if not alive:
                r.done = True
                finished.append((r.uid, r.output))
                self._slot_req[i] = None
                self._host_active[i] = False
                self._last_obs_t.pop(r.uid, None)
                # References drop; exclusive full blocks retire into the
                # LRU pool (still matchable), partial ones go blank.
                self._alloc.release(i)

    def _spec_step(self, finished: List[Tuple[int, List[int]]]
                   ) -> List[Tuple[int, List[int]]]:
        """One speculative decode pass across all decoding lanes.

        1. Obtain each lane's ANCHOR token — exactly what plain decode
           would sample.  In steady state it was already computed by the
           PREVIOUS verify pass (``anchor_next``, cached per lane), so no
           extra dispatch runs; only lanes whose logits were never scored
           by a verify pass (fresh prefill, preemption recompute) fall
           back to the ``_spec_anchor_fn`` dispatch.
        2. Host: the proposer drafts up to ``spec_k`` continuations from
           the request's own history (none past EOS or the budget).
        3. Grow + write-barrier each lane's blocks for the whole chunk
           (optimistic: pool pressure preempts, exactly like decode).
        4. ONE fixed-shape verify pass (``_spec_verify_fn``) scores every
           lane's [anchor | drafts] chunk through chunked prefill,
           writing drafted K/V through to the pool, and returns how many
           drafts plain decode would have emitted.
        5. Emit the accepted prefix through ``on_token`` (stopping at
           EOS/budget exactly like decode), rewind ``_host_pos`` past
           nothing — positions only ever advanced by accepted tokens —
           and ``BlockStore.truncate`` the rejected tail's K/V.

        The anchor's NEXT sample is not emitted here: the verify pass
        hands back the last accepted position's logits, so the next
        pass's anchor IS that token — engine logits state stays exactly
        plain decode's, which is what makes the bit-identity contract
        compositional across passes.
        """
        t0 = time.perf_counter()
        with TraceAnnotation("serve.decode.run"):
            passed = self._spec_verify_pass()
            self.stats.decode_s += time.perf_counter() - t0
        if passed is None:
            return finished
        with TraceAnnotation("serve.emit"):
            self._emit_spec(finished, *passed)
        return finished

    def _emit_spec(self, finished: List[Tuple[int, List[int]]],
                   chunks: Dict[int, List[int]], lengths: np.ndarray,
                   v: np.ndarray, anext: np.ndarray) -> None:
        """Step 5 of ``_spec_step``: emit each lane's accepted prefix,
        roll back the rejected tail and retire finished lanes into
        ``finished``."""
        B = self.max_batch
        self.stats.peak_decode_lanes = max(self.stats.peak_decode_lanes,
                                           int((lengths > 0).sum()))
        self.stats.spec_passes += 1
        self.stats.decode_steps += 1
        self.stats.slot_steps += B
        self.stats.used_token_steps += self._alloc.live_tokens
        self.stats.pool_token_steps += self._alloc.num_blocks \
            * self._alloc.block_size

        bs = self._alloc.block_size
        now = time.perf_counter()
        for i in np.nonzero(lengths > 0)[0]:
            i = int(i)
            r = self._slot_req[i]
            chunk = chunks[i]
            lo = self._prefix + int(self._host_pos[i])
            emitted, alive = 0, True
            # Accepted tokens are EXACTLY what plain decode would emit, so
            # the retirement walk is the same: stop at EOS or budget zero.
            for j in range(int(v[i]) + 1):
                tok = chunk[j]
                r.output.append(tok)
                emitted += 1
                if self.on_token is not None:
                    self.on_token(r.uid, tok)
                self._host_pos[i] += 1
                self._host_rem[i] -= 1
                self.stats.generated_tokens += 1
                if tok == self.eos_id or self._host_rem[i] <= 0:
                    alive = False
                    break
            self.stats.spec_accepted += emitted - 1
            self.stats.occupied_slot_steps += 1
            # One host sync released `emitted` tokens: the ITL window gap
            # spreads over ACCEPTED tokens (rejected drafts never reached
            # the client, so they must not dilute the distribution).
            self._note_tokens(r.uid, emitted, now)
            if emitted < len(chunk):
                # Rejected-tail rollback: the optimistic writes past the
                # accepted prefix are un-committed (refcount/chain-safe).
                self._alloc.truncate(i, lo + emitted)
            if self.prefix_cache and (lo + emitted) // bs != lo // bs:
                self._alloc.commit_full(i, self._content_ids(r))
            if not alive:
                r.done = True
                finished.append((r.uid, r.output))
                self._slot_req[i] = None
                self._host_active[i] = False
                self._last_obs_t.pop(r.uid, None)
                self._alloc.release(i)
            else:
                # The lane consumed its whole accepted prefix (emitted ==
                # v + 1), so its position is exactly where `anchor_next`
                # was sampled — carry it as next pass's anchor.
                self._spec_next[i] = int(anext[i])

    def _spec_verify_pass(self):
        """Steps 1-4 of ``_spec_step``: anchors, drafts, growth and the
        verify dispatch with its host sync.  Returns ``(chunks, lengths,
        accepted drafts, next anchors)``, or None when growth preempted
        every lane."""
        B = self.max_batch
        live = [int(i) for i in np.nonzero(self._host_active)[0]]
        # Anchors are popped (not read): a lane that doesn't survive to
        # the end of this pass re-derives its anchor from replayed logits
        # next time, so a stale cache entry can never outlive its request.
        cached = {i: self._spec_next.pop(i) for i in list(self._spec_next)}
        if any(i not in cached for i in live):
            anchors = np.asarray(self._spec_anchor_fn(
                self._logits, self._keys,
                jnp.asarray(self._host_pos, jnp.int32),
                jnp.asarray(self._host_active)))
        chunks: Dict[int, List[int]] = {}
        for i in live:
            r = self._slot_req[i]
            chunk = [cached[i] if i in cached else int(anchors[i])]
            rem_after = int(self._host_rem[i]) - 1
            if chunk[0] != self.eos_id and rem_after > 0:
                k = min(self.spec_k, rem_after)
                hist = [int(t) for t in r.prompt] + r.output + chunk
                chunk += [int(d) for d in
                          self._proposer.propose(hist, k)[:k]]
            chunks[i] = chunk
        with TraceAnnotation("serve.grow"):
            for i in chunks:
                if not self._host_active[i]:
                    continue  # preempted while an earlier lane grew
                lo = self._prefix + int(self._host_pos[i])
                self._grow_for_writes(
                    i, lo, lo + len(chunks[i]),
                    alive=lambda i=i: bool(self._host_active[i]))
        if not self._host_active.any():
            return None
        self._note_peak()

        P = self.spec_k + 1
        tokens = np.full((B, P), self.pad_id, np.int32)
        lengths = np.zeros(B, np.int32)
        starts = np.zeros(B, np.int32)
        for i, chunk in chunks.items():
            if not self._host_active[i]:
                continue  # its anchor is discarded; recompute replays it
            lengths[i] = len(chunk)
            tokens[i, P - len(chunk):] = chunk
            starts[i] = self._prefix + int(self._host_pos[i])
            self.stats.spec_proposed += len(chunk) - 1
        tables = jnp.asarray(self._alloc.block_table())
        self._cache, self._logits, v_dev, anext_dev = self._spec_verify_fn(
            self.params, self._cache, jnp.asarray(tokens),
            jnp.asarray(lengths), tables, jnp.asarray(starts),
            self._keys, self._logits)
        return chunks, lengths, np.asarray(v_dev), np.asarray(anext_dev)

    def has_pending_work(self) -> bool:
        """True while any request is queued, prefilling, decoding or
        waiting to be retired — i.e. while ``step()`` can make progress."""
        if self.mode != "continuous":
            return bool(self._queue or self._instant)
        return bool(self._queue or self._prefilling or self._instant
                    or self._host_active.any())

    @property
    def pool_saturation(self) -> float:
        """Live (ref-counted) blocks over pool capacity, right now — the
        saturation signal the frontend's circuit breaker watches."""
        if self.mode != "continuous":
            return 0.0
        return self._alloc.live_blocks / max(self._alloc.num_blocks, 1)

    @property
    def live_blocks(self) -> int:
        """Blocks currently referenced by some in-flight lane (the load
        half of the replica router's least-loaded fallback)."""
        if self.mode != "continuous":
            return 0
        return self._alloc.live_blocks

    def match_cached_blocks(self, prompt, patch_embeds=None) -> int:
        """How many leading blocks of ``prompt`` this engine's prefix cache
        could serve RIGHT NOW, without admitting or touching any state.

        The replica router's affinity probe: it hashes the prompt with the
        SAME chain (vlm patch sentinels + per-request chain seed +
        kv_dtype-namespaced root) admission uses, so a nonzero answer here
        is exactly a nonzero ``cached_len`` if the request were admitted
        here next.  0 when the engine is not continuous or prefix caching
        is off."""
        if self.mode != "continuous" or not self.prefix_cache:
            return 0
        content = np.concatenate([
            np.full(self._prefix, -1, np.int64),
            np.asarray(prompt, np.int64)])
        digests = chain_hashes(content, self._alloc.block_size,
                               seed=self._chain_seed(patch_embeds))
        return self._alloc.match_digests(
            digests,
            max_cached_tokens=self._prefix + len(prompt) - 1,
            min_cached_tokens=self._prefix)[0]

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it currently is — queued, mid-prefill
        or decoding — releasing its KV blocks exactly like a retirement
        (non-shared blocks free, full exclusive blocks retire into the LRU
        pool).  Returns True if the request was found in flight; False if
        it already finished (or was never submitted).  Tokens generated
        before the cancel are simply dropped — the caller streamed them
        already.  Continuous mode only (the wave path has no per-request
        scheduler state to unwind)."""
        if self.mode != "continuous":
            raise RuntimeError("cancel() requires mode='continuous'")
        for d in (self._submit_t, self._queued_t, self._admit_t,
                  self._last_obs_t):
            d.pop(uid, None)
        for i, (u, _) in enumerate(self._instant):
            if u == uid:
                self._instant.pop(i)
                self.stats.cancellations += 1
                return True
        for i, r in enumerate(self._queue):
            if r.uid == uid:
                self._queue.pop(i)
                self._digest_cache.pop(uid, None)
                self.stats.cancellations += 1
                return True
        for s in self._prefilling:
            if s.req.uid == uid:
                self._prefilling.remove(s)
                self._alloc.release(s.lane)
                # The abandoned admission's prefix-cache credit never
                # served anything (same rollback as a preemption).
                self.stats.cached_prompt_tokens -= s.counted_cached
                self.stats.cancellations += 1
                return True
        for i, r in enumerate(self._slot_req):
            if r is not None and r.uid == uid:
                self._slot_req[i] = None
                self._host_active[i] = False
                self._host_rem[i] = 0
                self._active = self._active.at[i].set(False)
                if self._proposer is not None:
                    self._spec_next.pop(i, None)
                self._alloc.release(i)
                self.stats.cancellations += 1
                return True
        return False

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue; returns uid -> generated tokens."""
        if self.mode != "continuous":
            return self._run_waves()
        results: Dict[int, List[int]] = {}
        while (self._queue or self._prefilling or self._instant
               or self._host_active.any()):
            for uid, toks in self.step():
                results[uid] = toks
        return results

    # -- continuous internals ------------------------------------------------
    def _init_continuous(self, donate: bool, seed: int) -> None:
        cfg, B = self.cfg, self.max_batch
        self._prefix = cfg.num_patches if cfg.family == "vlm" else 0
        ctx = self.max_len + self._prefix
        bs = self.block_size
        table_width = -(-ctx // bs)
        if self.num_blocks is None:
            self.num_blocks = B * table_width
        self._alloc = BlockStore(self.num_blocks, bs, B, table_width,
                                 prefix_cache=self.prefix_cache,
                                 kv_dtype=cfg.kv_dtype)
        # +1 device block: id 0 is the dead-lane trash sink.  With a mesh
        # the pool lands pre-sharded on its KV heads (payload + scale
        # leaves co-placed) so the shard_map'd kernels read it in place.
        self._cache = M.init_paged_cache(cfg, self.num_blocks + 1, bs,
                                         mesh=self._mesh)
        # Device bytes per pool block, all layers, K+V, summed over EVERY
        # cache leaf (axis 1 is blocks for payload and scale leaves
        # alike) — so a quantized pool's number is the true compressed
        # footprint: int8/fp8 payload bytes PLUS the fp32 scale metadata,
        # not a dense-equivalent estimate.
        self.kv_block_bytes = sum(
            int(np.prod(x.shape)) // x.shape[1] * x.dtype.itemsize
            for x in self._cache.values())
        ldtype = self.params["embed"].dtype
        self._logits = jnp.zeros((B, cfg.vocab_size), ldtype)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._active = jnp.zeros((B,), bool)
        self._budget = jnp.zeros((B,), jnp.int32)
        self._base_key = jax.random.PRNGKey(seed)
        self._keys = jnp.zeros((B,) + self._base_key.shape,
                               self._base_key.dtype)
        self._slot_req: List[Optional[Request]] = [None] * B
        self._prefilling: List[_Prefilling] = []
        self._host_active = np.zeros(B, bool)
        self._host_pos = np.zeros(B, np.int64)
        self._host_rem = np.zeros(B, np.int64)  # decode budget remaining

        sampler, eos_id, pad_id = self.sampler, self.eos_id, self.pad_id
        K = self.decode_steps

        def decode_window(params, cache, last_logits, pos, active, budget,
                          keys, tables):
            def one_step(carry, _):
                cache, logits, pos, active, budget = carry
                # Inactive lanes (retired mid-window, mid-chunked-prefill,
                # preempted) run as masked no-op rows with their tables
                # pointed at the trash block, so their writes cannot
                # clobber a live or partially prefilled block.
                tbl = jnp.where(active[:, None], tables, TRASH_BLOCK)
                # Positional per-lane keys: the token at position p of
                # request uid samples with fold_in(fold_in(seed, uid), p)
                # — reproducible per request regardless of co-tenants, and
                # preemption/speculation-invariant by construction (a
                # recompute resamples position p with the same key; no
                # stream fast-forwarding needed).
                sub = positional_keys(keys, pos)
                tok = sample(sampler, logits, sub, active=active,
                             pad_id=pad_id)
                budget = budget - active.astype(jnp.int32)
                retire = active & ((tok == eos_id) | (budget <= 0))
                # All lanes run the model (a retired/free lane is a masked
                # no-op — the occupancy loss the stats report); the active
                # mask keeps dead lanes out of MoE expert capacity.
                logits2, cache = M.decode_step(cfg, params, cache,
                                               tok[:, None], pos,
                                               active=active,
                                               block_tables=tbl,
                                               mesh=self._mesh)
                pos = pos + active.astype(jnp.int32)
                new_active = active & ~retire
                return ((cache, logits2[:, 0], pos, new_active, budget),
                        (tok, new_active.astype(jnp.int32)))

            carry = (cache, last_logits, pos, active, budget)
            carry, (toks, actives) = jax.lax.scan(one_step, carry, None,
                                                  length=K)
            cache, logits, pos, active, budget = carry
            # One packed (2, K, B) buffer -> a single device->host read per
            # decode window in the scheduler loop.
            host_out = jnp.stack([toks, actives])
            return cache, logits, pos, active, budget, host_out

        self._decode_fn = jax.jit(
            self._scoped(decode_window),
            donate_argnums=(1, 2, 3, 4, 5) if donate else ())
        # One jit per (first/continuation) handles every (group size,
        # bucket) shape combination; power-of-two buckets keep the number
        # of retraces small.  vlm first chunks take the cohort's (possibly
        # per-request) patch embeddings explicitly.
        mesh = self._mesh

        def prefill_first(params, cache, tokens, lengths, tables,
                          patch_embeds=None):
            return M.prefill_slots(cfg, params, cache, tokens, lengths,
                                   tables, patch_embeds=patch_embeds,
                                   mesh=mesh)

        def prefill_cont(params, cache, tokens, lengths, tables, starts):
            return M.prefill_slots(cfg, params, cache, tokens, lengths,
                                   tables, start=starts, mesh=mesh)

        self._prefill_first = jax.jit(
            self._scoped(prefill_first),
            donate_argnums=(1,) if donate else ())
        self._prefill_cont = jax.jit(
            self._scoped(prefill_cont),
            donate_argnums=(1,) if donate else ())

        if self._proposer is not None:
            pfx = self._prefix
            # lane -> anchor token carried from the previous verify pass
            # (see _spec_step); invalidated whenever a request leaves its
            # lane (retire, preempt, cancel).
            self._spec_next: Dict[int, int] = {}

            def spec_anchor(logits, keys, pos, active):
                """The pass's first token — EXACTLY decode's sampling rule
                (same positional key, same active masking)."""
                return sample(sampler, logits, positional_keys(keys, pos),
                              active=active, pad_id=pad_id)

            self._spec_anchor_fn = jax.jit(self._scoped(spec_anchor))

            def spec_verify(params, cache, tokens, lengths, tables, starts,
                            keys, last_logits):
                """Score each lane's [anchor | drafts] chunk in ONE
                chunked-prefill continuation pass (all B lanes, fixed
                (B, spec_k + 1) shape -> one trace for the whole run;
                rows with length 0 read junk and write nothing) and
                compute in-jit how many drafts plain decode would have
                emitted.  The chunk's K/V lands in the pool through the
                prefill write-through — optimistically; the host rolls
                back the rejected tail with ``BlockStore.truncate``."""
                logits_all, cache = M.prefill_slots(
                    cfg, params, cache, tokens, lengths, tables,
                    start=starts, all_logits=True, mesh=mesh)
                Bn, P = tokens.shape
                pad = (P - lengths).astype(jnp.int32)
                # Column c of row b holds the token AT token-position
                # (starts[b] - pfx) + (c - pad[b]); what plain decode
                # emits AFTER it samples logits_all[b, c] with the key of
                # the NEXT position.
                col = jnp.arange(P)[None]
                nxt = (starts - pfx)[:, None] \
                    + jnp.maximum(col - pad[:, None], 0) + 1
                flat_keys = positional_keys(
                    jnp.repeat(keys, P, axis=0), nxt.reshape(-1))
                expected = sample(
                    sampler, logits_all.reshape(Bn * P, -1),
                    flat_keys).reshape(Bn, P)
                # Longest accepted draft prefix: draft at column c+1 is
                # accepted iff it equals what decode emits after column c.
                ok = tokens[:, 1:] == expected[:, :-1]
                idx = jnp.arange(P - 1)[None]
                lead = jnp.where(idx < pad[:, None], True, ok)
                run = jnp.cumprod(lead.astype(jnp.int32), axis=1).sum(1)
                v = jnp.clip(run - pad, 0, jnp.maximum(lengths - 1, 0))
                # Next-step logits: the last ACCEPTED token's column (the
                # anchor for the next pass — its sample is next pass's
                # first token, so no logits state diverges from plain
                # decode).  Idle rows keep their previous logits.
                sel = jnp.minimum(pad + v, P - 1)
                new_logits = jnp.take_along_axis(
                    logits_all, sel[:, None, None], axis=1)[:, 0]
                new_logits = jnp.where((lengths > 0)[:, None], new_logits,
                                       last_logits)
                # `expected` at the selected column IS the next pass's
                # anchor (same logits, same positional key the anchor fn
                # would use after the accepted tokens advance the
                # position) — returning it here makes the steady-state
                # pass a SINGLE dispatch: the host caches it per lane and
                # only falls back to the anchor fn for lanes fresh out of
                # prefill/preemption, whose logits it has never scored.
                anchor_next = jnp.take_along_axis(
                    expected, sel[:, None], axis=1)[:, 0]
                return cache, new_logits, v, anchor_next

            self._spec_verify_fn = jax.jit(
                self._scoped(spec_verify),
                donate_argnums=(1,) if donate else ())

    def _clamped_budget(self, prompt, max_new_tokens: int) -> int:
        """Decode budget clamped so the sequence fits the per-request
        context — the ONE definition admission, the device budget and the
        submit guard all share."""
        return min(max_new_tokens, self.max_len - len(prompt))

    def _worst_case_tokens(self, prompt, max_new_tokens: int) -> int:
        """Total cache tokens a request can ever hold."""
        return self._prefix + len(prompt) \
            + self._clamped_budget(prompt, max_new_tokens)

    def _effective_prompt(self, r: Request) -> np.ndarray:
        """Prompt to prefill: the original prompt plus any tokens generated
        before a preemption (recompute replays them)."""
        if not r.output:
            return r.prompt
        return np.concatenate(
            [r.prompt, np.asarray(r.output, np.int32)])

    def _content_ids(self, r: Request) -> np.ndarray:
        """Token ids at each cache position, for the prefix-cache hash
        chain: sentinel -1 per vlm patch position, then prompt, then
        generated tokens.  The sentinel alone does NOT identify the patch
        content — the request's ``chain_seed`` (patch-embedding digest)
        commits the whole chain to the image, which is what makes the
        sentinel sound; do not drop the seed as redundant."""
        return np.concatenate([
            np.full(self._prefix, -1, np.int64),
            np.asarray(r.prompt, np.int64),
            np.asarray(r.output, np.int64)])

    def _remaining_budget(self, r: Request) -> int:
        return self._clamped_budget(r.prompt, r.max_new_tokens) \
            - len(r.output)

    def _prompt_digests(self, r: Request) -> List[bytes]:
        """Chain digests of the request's full content, cached by length
        (the content only ever grows — on preemption requeue — which
        naturally invalidates the entry)."""
        n = self._prefix + len(r.prompt) + len(r.output)
        hit = self._digest_cache.get(r.uid)
        if hit is not None and hit[0] == n:
            return hit[1]
        digests = chain_hashes(self._content_ids(r), self._alloc.block_size,
                               seed=r.chain_seed)
        self._digest_cache[r.uid] = (n, digests)
        return digests

    # -- preemption ----------------------------------------------------------
    def _victim_key(self, r: Request, lane: int):
        """Sort key for victim selection — the MAX key is preempted.
        Re-queued preempted requests keep their uid, so under "youngest"
        they age back into protection once re-admitted."""
        if self.preempt_policy == "largest":
            return (self._alloc.owned_blocks(lane), r.uid)
        if self.preempt_policy == "deadline":
            # Latest deadline has the most slack to absorb a recompute;
            # deadline-less requests are evicted before any with one.
            d = float("inf") if r.deadline is None else float(r.deadline)
            return (d, r.uid)
        return (r.uid,)  # youngest

    def _select_victim(self):
        """The in-flight request ``preempt_policy`` evicts under pool
        pressure: ("lane", i) or ("prefill", s), or None if nothing is in
        flight."""
        best, best_key = None, None
        for i in np.nonzero(self._host_active)[0]:
            r = self._slot_req[int(i)]
            if r is None:
                continue
            key = self._victim_key(r, int(i))
            if best_key is None or key > best_key:
                best, best_key = ("lane", int(i)), key
        for s in self._prefilling:
            key = self._victim_key(s.req, s.lane)
            if best_key is None or key > best_key:
                best, best_key = ("prefill", s), key
        return best

    def _preempt(self, victim) -> None:
        """Release the victim's blocks and re-queue it at the head for
        recompute.  Only its NON-SHARED blocks actually free (shared prefix
        blocks keep their other references); its full blocks retire into
        the LRU pool, so the recompute is usually prefix-cache hits."""
        kind, v = victim
        self.stats.preemptions += 1
        if kind == "lane":
            r = self._slot_req[v]
            self._slot_req[v] = None
            self._host_active[v] = False
            self._host_rem[v] = 0
            self._active = self._active.at[v].set(False)
            if self._proposer is not None:
                # The carried anchor belongs to the evicted request; the
                # recompute replays its logits and re-derives it.
                self._spec_next.pop(v, None)
            self._alloc.release(v)
            self._queue.insert(0, r)
        else:
            self._prefilling.remove(v)
            self._alloc.release(v.lane)
            self._queue.insert(0, v.req)
            # The abandoned admission's cache credit never served anything;
            # roll it back so prefix_hit_rate reflects thrash instead of
            # being inflated by it (re-admission re-counts its real hits).
            self.stats.cached_prompt_tokens -= v.counted_cached
            r = v.req
        self._queued_t[r.uid] = time.perf_counter()

    def _under_pressure(self, alive: Callable[[], bool],
                        op: Callable[[], None]) -> bool:
        """Run an allocator op that may raise OutOfBlocks, preempting the
        youngest request and retrying until it succeeds.  Returns False if
        the op's own request was preempted (op abandoned)."""
        while True:
            if not alive():
                return False
            try:
                op()
                return True
            except OutOfBlocks:
                victim = self._select_victim()
                # The growing request is itself in flight, so a victim
                # always exists (possibly the grower).
                assert victim is not None, "OutOfBlocks with no live request"
                self._preempt(victim)

    def _grow_for_writes(self, lane: int, lo: int, hi: int,
                         alive: Callable[[], bool]) -> bool:
        """Grow ``lane`` to ``hi`` tokens and run the copy-on-write barrier
        over the blocks covering cache positions [lo, hi).  Returns False
        if the lane was preempted along the way."""
        if not self._under_pressure(
                alive, lambda: self._alloc.grow(lane, hi)):
            return False
        bs = self._alloc.block_size
        for idx in range(lo // bs, (hi - 1) // bs + 1):
            moved: List[Tuple[int, int]] = []

            def cow(idx=idx, moved=moved):
                mv = self._alloc.ensure_writable(lane, idx * bs)
                if mv is not None:
                    moved.append(mv)

            if not self._under_pressure(alive, cow):
                return False
            for src, dst in moved:
                self._copy_block(src, dst)
        return True

    def _note_peak(self) -> None:
        self.stats.kv_block_bytes = self.kv_block_bytes
        self.stats.peak_live_blocks = max(self.stats.peak_live_blocks,
                                          self._alloc.live_blocks)

    def _copy_block(self, src: int, dst: int) -> None:
        """Device-side copy-on-write payload copy (all layers of one
        block).  Rare: only a write into a still-shared block triggers
        it."""
        self._cache = M.copy_cache_block(self._cache, src, dst)

    # -- admission / prefill -------------------------------------------------
    def _admit(self) -> None:
        """Move queued requests onto free lanes.  Admission is OPTIMISTIC:
        a request enters when the store can cover its uncached prompt tail
        plus one decode block RIGHT NOW — the decode budget is not
        reserved; preemption recovers from over-commitment.  Prefix-cache
        hits shrink the tail, so shared-prompt traffic admits far deeper
        than the pool's raw capacity."""
        owned = {s.lane for s in self._prefilling}
        free = [i for i, r in enumerate(self._slot_req)
                if r is None and i not in owned]
        while self._queue and free:
            r = self._queue[0]
            with TraceAnnotation("serve.admit", uid=r.uid):
                if not self._admit_head(r, free.pop(0)):
                    break  # FIFO: wait for blocks rather than starve it

    def _admit_head(self, r: Request, lane: int) -> bool:
        """Admit the queue head ``r`` onto ``lane`` if the store can cover
        its uncached prompt tail plus one decode block right now."""
        eff_len = len(r.prompt) + len(r.output)
        digests = self._prompt_digests(r) if self.prefix_cache else []
        cached_blocks, pooled = self._alloc.match_digests(
            digests,
            max_cached_tokens=self._prefix + eff_len - 1,
            min_cached_tokens=self._prefix)
        need_now = self._alloc.blocks_for(
            self._prefix + eff_len + 1) - cached_blocks
        # Matched-but-pooled blocks will be revived out of `available`
        # by admit, so they cannot double as allocatable headroom.
        if need_now > self._alloc.available - pooled:
            return False
        eff = self._effective_prompt(r)
        cached_len = self._alloc.admit(
            lane, digests=digests if self.prefix_cache else None,
            max_cached_tokens=self._prefix + eff_len - 1,
            min_cached_tokens=self._prefix, seed=r.chain_seed)
        self._digest_cache.pop(r.uid, None)
        consumed = max(0, cached_len - self._prefix)
        self.stats.cached_prompt_tokens += consumed
        self._prefilling.append(_Prefilling(
            r, lane, self._remaining_budget(r), eff,
            consumed=consumed, cached_len=cached_len,
            counted_cached=consumed))
        self._queue.pop(0)
        self.stats.admissions += 1
        now = time.perf_counter()
        self.stats.queue_wait_s += now - self._queued_t.pop(r.uid)
        if r.uid in self._submit_t:
            # Still waiting for its first token: keep the first
            # admission's time across preemptions.
            self._admit_t.setdefault(r.uid, now)
        return True

    def _prefill_step(self) -> None:
        """Run ONE prefill chunk for the current admission cohort."""
        if not self._prefilling:
            return

        # From-scratch first chunks embed the vlm patch prefix (a different
        # traced shape); cached or continuation chunks gather their context
        # through the block table.  Group the two separately.
        def _first(s: _Prefilling) -> bool:
            return s.consumed == 0 and s.cached_len == 0

        first = _first(self._prefilling[0])
        cohort = [s for s in self._prefilling if _first(s) == first]
        self.stats.prefill_member_ticks += len(self._prefilling)
        self.stats.prefill_left_out += len(self._prefilling) - len(cohort)
        cap = self.prefill_chunk or self.max_len

        # Grow every member's blocks (write-barriered) BEFORE assembling
        # the batch: growth can preempt cohort members (including the one
        # being grown), which drops them from this chunk.
        ready: List[Tuple[_Prefilling, int]] = []
        with TraceAnnotation("serve.grow"):
            for s in cohort:
                if s not in self._prefilling:
                    continue  # preempted as a victim of an earlier member
                take = min(cap, len(s.tokens) - s.consumed)
                lo = self._prefix + s.consumed
                if self._grow_for_writes(
                        s.lane, lo, lo + take,
                        alive=lambda s=s: s in self._prefilling):
                    ready.append((s, take))
        # A LATER member's growth may have preempted an earlier one that
        # had already grown — drop it, or its chunk would be written into
        # released blocks and the preempted request wrongly activated.
        ready = [(s, t) for (s, t) in ready if s in self._prefilling]
        self._note_peak()
        if not ready:
            return
        cohort, takes = [s for s, _ in ready], [t for _, t in ready]
        P = _bucket(max(takes), cap)
        n = len(cohort)
        with TraceAnnotation("serve.prefill.inputs"):
            tokens = np.full((n, P), self.pad_id, np.int32)
            lengths = np.empty(n, np.int32)
            starts = np.empty(n, np.int32)
            for j, (s, take) in enumerate(zip(cohort, takes)):
                tokens[j, P - take:] = s.tokens[s.consumed:s.consumed + take]
                lengths[j] = take
                starts[j] = self._prefix + s.consumed
            tables = jnp.asarray(
                self._alloc.block_table()[[s.lane for s in cohort]])
        with TraceAnnotation("serve.prefill.run", rows=n):
            t0 = time.perf_counter()
            if first:
                if self.cfg.family == "vlm":
                    # Per-request images; the zero stub for requests without.
                    pe = np.zeros((n, self.cfg.num_patches, self.cfg.d_model),
                                  np.float32)
                    for j, (s, _) in enumerate(zip(cohort, takes)):
                        if s.req.patch_embeds is not None:
                            pe[j] = s.req.patch_embeds
                    logits_new, self._cache = self._prefill_first(
                        self.params, self._cache, jnp.asarray(tokens),
                        jnp.asarray(lengths), tables,
                        jnp.asarray(pe).astype(jnp.bfloat16))
                else:
                    logits_new, self._cache = self._prefill_first(
                        self.params, self._cache, jnp.asarray(tokens),
                        jnp.asarray(lengths), tables)
            else:
                logits_new, self._cache = self._prefill_cont(
                    self.params, self._cache, jnp.asarray(tokens),
                    jnp.asarray(lengths), tables, jnp.asarray(starts))

            done_rows, done = [], []
            for j, (s, take) in enumerate(zip(cohort, takes)):
                s.consumed += take
                if self.prefix_cache:
                    self._alloc.commit_full(s.lane, self._content_ids(s.req))
                if s.consumed == len(s.tokens):
                    done_rows.append(j)
                    done.append(s)
                    self._slot_req[s.lane] = s.req
                    self._prefilling.remove(s)
            if done:
                rows = jnp.asarray(done_rows)
                lanes = jnp.asarray([s.lane for s in done])
                plens = jnp.asarray([len(s.tokens) for s in done], jnp.int32)
                budgets = jnp.asarray([s.budget for s in done], jnp.int32)
                self._logits = self._logits.at[lanes].set(logits_new[rows])
                self._pos = self._pos.at[lanes].set(plens)
                self._active = self._active.at[lanes].set(True)
                self._budget = self._budget.at[lanes].set(budgets)
                self._keys = self._keys.at[lanes].set(jnp.stack(
                    [self._request_key(s.req) for s in done]))
                for s in done:
                    self._host_active[s.lane] = True
                    self._host_pos[s.lane] = len(s.tokens)
                    self._host_rem[s.lane] = s.budget
            jax.block_until_ready(self._logits)
            self.stats.prefill_s += time.perf_counter() - t0
            self.stats.prefill_tokens += int(sum(takes))
            self.stats.prefill_chunks += 1

    def _request_key(self, r: Request):
        """The request's base PRNG key: fold_in(seed, uid).  The decode
        step folds the sampling POSITION in on top, so a preemption
        recompute resumes the same stochastic stream with no
        fast-forwarding (O(1) re-admission)."""
        return jax.random.fold_in(self._base_key, r.uid)

    # -- mesh placement ------------------------------------------------------
    def _place_serve(self, mesh, params):
        with sharding.use_axes(self._axes):
            specs = sharding.param_specs(self.cfg, params, mode="serve")
            specs = sharding.sanitize_specs(specs, params)
            return jax.device_put(params,
                                  sharding.to_shardings(mesh, specs))

    # -- legacy wave path ----------------------------------------------------
    def _run_waves(self) -> Dict[int, List[int]]:
        """Lockstep wave batching, bucketed by exact prompt length (padding
        would let real tokens attend to pads without the masked-prefill
        machinery of the continuous path)."""
        results: Dict[int, List[int]] = {uid: toks
                                         for uid, toks in self._instant}
        self._instant = []
        by_len: Dict[int, List[Request]] = {}
        for r in self._queue:
            by_len.setdefault(len(r.prompt), []).append(r)
        self._queue = []
        for _, reqs in sorted(by_len.items()):
            for i in range(0, len(reqs), self.max_batch):
                wave = reqs[i: i + self.max_batch]
                self._run_wave(wave)
                for r in wave:
                    results[r.uid] = r.output
        return results

    def _run_wave(self, wave: List[Request]) -> None:
        B = len(wave)
        S = len(wave[0].prompt)  # waves are same-length by construction
        toks = np.stack([r.prompt for r in wave]).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            pe = np.zeros((B, self.cfg.num_patches, self.cfg.d_model),
                          np.float32)
            for i, r in enumerate(wave):
                if r.patch_embeds is not None:
                    pe[i] = r.patch_embeds
            batch["patch_embeds"] = jnp.asarray(pe).astype(jnp.bfloat16)
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (B, self.cfg.encdec.encoder_seq_len, self.cfg.d_model),
                jnp.bfloat16)

        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, batch)
        logits = jax.block_until_ready(logits)
        self.stats.prefill_s += time.perf_counter() - t0
        self.stats.prefill_tokens += B * S
        self.stats.admissions += B

        max_new = min(max(r.max_new_tokens for r in wave),
                      self.max_len - S)
        key = jax.random.PRNGKey(self._uid)
        done = np.zeros(B, bool)
        t0 = time.perf_counter()
        for step in range(max_new):
            self.stats.decode_steps += 1
            self.stats.occupied_slot_steps += int((~done).sum())
            self.stats.slot_steps += self.max_batch
            key, sub = jax.random.split(key)
            next_tok = sample(self.sampler, logits.reshape(B, -1), sub)
            nt = np.asarray(next_tok)
            now = time.perf_counter()
            for i, r in enumerate(wave):
                if not done[i] and len(r.output) < r.max_new_tokens:
                    r.output.append(int(nt[i]))
                    if self.on_token is not None:
                        self.on_token(r.uid, int(nt[i]))
                    self._note_tokens(r.uid, 1, now)
                    self.stats.generated_tokens += 1
                    if nt[i] == self.eos_id:
                        done[i] = True
                if len(r.output) >= r.max_new_tokens:
                    done[i] = True
            if done.all():
                break
            logits, cache = self._decode(
                self.params, cache, next_tok[:, None], jnp.int32(S + step))
            logits = logits[:, 0]
        jax.block_until_ready(logits)
        self.stats.decode_s += time.perf_counter() - t0
        for r in wave:
            self._last_obs_t.pop(r.uid, None)
