"""Continuous batching: paged KV admission, per-row decode, bit-identity.

Acceptance: >= 2 concurrent requests with different prompt lengths AND
different completion lengths decode through one shared jitted masked step
over a PAGED (block-table) KV cache, with per-request outputs bit-identical
(greedy) to running each request alone through ``model.prefill`` +
scalar-position ``model.decode_step`` — including when prompts are
prefilled in chunks interleaved with in-flight decodes, when blocks are
SHARED through the prefix cache (concurrent sharers, LRU revival after the
donor retired), when the pool over-commits and the engine preempts, and
when ``decode_steps > 1`` amortizes the host sync.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.paged import BlockStore

MAX_LEN = 32


def _make(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def tiny():
    return _make("tinyllama-1.1b")


def solo_greedy(cfg, params, prompt, max_new):
    """Reference: one request alone via prefill + scalar-position decode."""
    batch = {"tokens": jnp.asarray(np.asarray(prompt)[None], jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros(
            (1, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    logits, cache = M.prefill(cfg, params, batch, max_len=MAX_LEN)
    toks, pos = [], len(prompt)
    for _ in range(max_new):
        t = int(jnp.argmax(logits.reshape(-1)))
        toks.append(t)
        logits, cache = M.decode_step(
            cfg, params, cache, jnp.full((1, 1), t, jnp.int32),
            jnp.int32(pos))
        logits = logits[:, 0]
        pos += 1
    return toks


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
def test_continuous_bit_identical_to_solo(arch):
    """Mixed prompt lengths AND mixed completion budgets in one batch."""
    cfg, params = _make(arch)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9, 13)]
    budgets = (4, 6, 3)
    eng = ServingEngine(cfg, params, max_batch=3, max_len=MAX_LEN, eos_id=-1)
    assert eng.mode == "continuous"
    uids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    out = eng.run()
    # All three decode concurrently through the shared step: total steps is
    # the longest budget, not the sum.
    assert eng.stats.decode_steps == max(budgets)
    for uid, p, m in zip(uids, prompts, budgets):
        assert out[uid] == solo_greedy(cfg, params, p, m)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
@pytest.mark.parametrize("block_size,chunk", [(4, 4), (8, 16)])
def test_paged_chunked_bit_identical_to_solo(arch, block_size, chunk):
    """The paged allocator + chunked prefill matrix: long and short prompts
    share the block pool, prompts longer than ``chunk`` prefill across
    several interleaved calls — outputs stay bit-identical to solo."""
    cfg, params = _make(arch)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n in (3, 17, 6, 21)]  # mixed long/short
    budgets = (5, 3, 4, 6)
    eng = ServingEngine(cfg, params, max_batch=4, max_len=MAX_LEN, eos_id=-1,
                        block_size=block_size, prefill_chunk=chunk)
    uids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    out = eng.run()
    # Long prompts really were chunked (admission interleaves with decode).
    assert eng.stats.prefill_chunks > 1
    for uid, p, m in zip(uids, prompts, budgets):
        assert out[uid] == solo_greedy(cfg, params, p, m)
    # Everything retired: every block is back on the free list.
    eng._alloc.check_invariants()
    assert eng._alloc.live_blocks == 0


def test_long_prompt_admitted_mid_decode(tiny):
    """A long prompt admitted while short requests decode must (a) not stall
    them — its prefill chunks interleave with their decode steps — and (b)
    come out bit-identical to its solo run."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    short = [rng.integers(1, cfg.vocab_size, size=4) for _ in range(2)]
    long = rng.integers(1, cfg.vocab_size, size=24)
    eng = ServingEngine(cfg, params, max_batch=3, max_len=MAX_LEN, eos_id=-1,
                        block_size=4, prefill_chunk=4)
    u_short = [eng.submit(p, max_new_tokens=8) for p in short]
    done = {}
    for _ in range(2):  # shorts are mid-decode...
        for uid, toks in eng.step():
            done[uid] = toks
    steps_before = eng.stats.decode_steps
    assert steps_before == 2
    u_long = eng.submit(long, max_new_tokens=3)  # ...when the long arrives
    while len(done) < 3:
        for uid, toks in eng.step():
            done[uid] = toks
    # The shorts kept decoding during the long prompt's 6 prefill chunks:
    # they finish their 8 tokens after 8 decode steps, strictly before the
    # long request (6 chunks + 3 decode steps from its admission).
    assert done[u_long] == solo_greedy(cfg, params, long, 3)
    for uid, p in zip(u_short, short):
        assert done[uid] == solo_greedy(cfg, params, p, 8)
    assert eng.stats.prefill_chunks >= 1 + 6  # shorts together + 24/4 chunks


def test_block_pool_admits_beyond_stripe_capacity(tiny):
    """Block-granular admission: with a pool worth 2 full stripes, THREE
    short requests run concurrently because each reserves only its own
    blocks — the fragmentation win over per-slot striping."""
    cfg, params = tiny
    rng = np.random.default_rng(4)
    # 2 stripes of MAX_LEN=32 tokens = 16 blocks of 4; each request needs
    # ceil((4 + 6)/4) = 3 blocks, so 3 requests fit with room to spare.
    prompts = [rng.integers(1, cfg.vocab_size, size=4) for _ in range(3)]
    eng = ServingEngine(cfg, params, max_batch=3, max_len=MAX_LEN, eos_id=-1,
                        block_size=4, num_blocks=2 * (MAX_LEN // 4))
    uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    assert eng.stats.decode_steps == 6  # all three decoded concurrently
    assert eng.stats.mean_active_requests == 3.0
    for uid, p in zip(uids, prompts):
        assert out[uid] == solo_greedy(cfg, params, p, 6)


def test_slot_freed_by_eos_is_reused(tiny):
    cfg, params = tiny
    p1, p2 = np.arange(1, 9), np.arange(3, 10)
    # Probe the first greedy token of p1, then make it the EOS id.
    probe = ServingEngine(cfg, params, max_batch=1, max_len=MAX_LEN,
                          eos_id=-1)
    probe.submit(p1, max_new_tokens=1)
    eos = list(probe.run().values())[0][0]

    eng = ServingEngine(cfg, params, max_batch=1, max_len=MAX_LEN,
                        eos_id=eos)
    u1 = eng.submit(p1, max_new_tokens=8)
    u2 = eng.submit(p2, max_new_tokens=3)  # queued behind the only slot
    out = eng.run()
    assert out[u1] == [eos]  # retired on EOS long before its budget
    assert eng.stats.admissions == 2  # the freed slot was re-admitted
    expect = solo_greedy(cfg, params, p2, 3)
    # p2 may also hit the probed EOS token; compare up to retirement.
    cut = expect.index(eos) + 1 if eos in expect else len(expect)
    assert out[u2] == expect[:cut]


def test_more_requests_than_slots(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(1, cfg.vocab_size, size=int(rng.integers(3, 14))),
             int(rng.integers(2, 6))) for _ in range(7)]
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN, eos_id=-1)
    uids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
    out = eng.run()
    assert eng.stats.admissions == 7  # every request got a slot eventually
    assert 0.0 < eng.stats.slot_occupancy <= 1.0
    for uid, (p, m) in zip(uids, reqs):
        assert out[uid] == solo_greedy(cfg, params, p, m)


def test_step_api_incremental(tiny):
    """step() returns finished requests as they retire, not at drain."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN, eos_id=-1)
    u_short = eng.submit(np.arange(1, 6), max_new_tokens=2)
    u_long = eng.submit(np.arange(1, 9), max_new_tokens=5)
    finished = {}
    steps_at_finish = {}
    n = 0
    while len(finished) < 2:
        n += 1
        for uid, toks in eng.step():
            finished[uid] = toks
            steps_at_finish[uid] = n
    assert steps_at_finish[u_short] == 2
    assert steps_at_finish[u_long] == 5
    assert len(finished[u_short]) == 2 and len(finished[u_long]) == 5


def test_prefill_slots_paged_primitives(tiny):
    """Block-level cache ops: the prefill writes land ONLY in the blocks
    the row's table names, in position order, bit-identical to the dense
    reference cache."""
    cfg, params = tiny
    prompt = np.arange(1, 8)  # length 7, bucket-padded to 8
    bs = 4
    alloc = BlockStore(num_blocks=8, block_size=bs, num_slots=2,
                       max_blocks_per_slot=MAX_LEN // bs)
    cache = M.init_paged_cache(cfg, alloc.num_blocks + 1, bs)
    alloc.admit(1)
    alloc.grow(1, len(prompt))  # 2 blocks: positions 0..3, 4..6
    P = 8
    toks = np.zeros((1, P), np.int32)
    toks[0, P - len(prompt):] = prompt  # left-pad
    tables = jnp.asarray(alloc.block_table()[[1]])
    logits, cache = M.prefill_slots(
        cfg, params, cache, jnp.asarray(toks),
        jnp.asarray([len(prompt)], jnp.int32), tables)

    owned = list(np.asarray(alloc.block_table()[1, :2]))
    k = np.asarray(cache["k"], np.float32)
    # Only the two owned blocks hold data: trash (0) and the free pool are
    # untouched (junk-tail writes are dropped, not spilled).
    for b in range(alloc.num_blocks + 1):
        assert np.any(k[:, b]) == (b in owned), f"block {b}"

    # Block-gathered K == the dense reference cache, position for position,
    # and the last-token logits are bit-identical to unpadded prefill.
    ref_logits, ref_cache = M.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
        max_len=MAX_LEN)
    np.testing.assert_array_equal(np.asarray(logits[0]),
                                  np.asarray(ref_logits[0]))
    # Pool rows are lane-dense: (Hk*D) lanes per token, head-major.
    gathered = k[:, owned].reshape(k.shape[0], 2 * bs, cfg.num_kv_heads,
                                   cfg.head_dim)
    np.testing.assert_array_equal(
        gathered[:, :len(prompt)],
        np.asarray(ref_cache["k"][:, 0, :len(prompt)], np.float32))

    # Release: blocks return to the pool, table row points at trash.
    freed = alloc.release(1)
    assert sorted(freed) == sorted(owned)
    assert alloc.live_blocks == 0
    assert (alloc.block_table() == 0).all()
    alloc.check_invariants()


def test_moe_dispatch_valid_mask_frees_capacity():
    """Dead lanes (retired slots, pads) must not displace live tokens from
    expert capacity buffers."""
    from repro.models import moe as moe_lib

    cfg = get_config("qwen2-moe-a2.7b").reduced()
    # 10 single-assignment tokens all routed to expert 0, capacity 4.
    experts = jnp.zeros((1, 10, 1), jnp.int32)
    C = 4
    # Dead tokens FIRST: without a mask they eat the whole capacity.
    slot_unmasked, _ = moe_lib._dispatch_indices(cfg, experts, C)
    assert int(slot_unmasked[0, 8, 0]) == C  # live token dropped
    valid = jnp.asarray([[False] * 8 + [True] * 2])
    slot, _ = moe_lib._dispatch_indices(cfg, experts, C, valid)
    assert (np.asarray(slot[0, :8, 0]) == C).all()  # dead -> drop bin
    assert int(slot[0, 8, 0]) == 0 and int(slot[0, 9, 0]) == 1  # live kept


def test_engine_threads_serve_shardings(tiny):
    """mesh= places params/cache with the serve layout; results unchanged.
    Axis state is engine-scoped (context-var), so the ambient sharding
    state is untouched by building and running a meshed engine."""
    from jax.sharding import Mesh
    from repro.parallel import sharding as sh

    cfg, params = tiny
    prompt = np.arange(1, 9)
    ref = solo_greedy(cfg, params, prompt, 3)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    ambient_before = sh.axis_state()
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        eos_id=-1, mesh=mesh)
    uid = eng.submit(prompt, max_new_tokens=3)
    assert eng.run()[uid] == ref
    assert eng._axes.sizes == (("data", 1), ("model", 1))
    assert sh.axis_state() == ambient_before, \
        "engine leaked mesh axis state into the ambient context"


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
def test_prefix_cache_bit_identical_on_vs_off(arch):
    """Greedy outputs are bit-identical with prefix caching on vs off,
    including (a) two requests sharing a prefix CONCURRENTLY and (b) a
    request admitted after its prefix donor retired (LRU revival)."""
    cfg, params = _make(arch)
    rng = np.random.default_rng(7)
    shared = rng.integers(1, cfg.vocab_size, size=13)  # > 1 full block
    prompts = [np.concatenate([shared, rng.integers(1, cfg.vocab_size,
                                                    size=n)])
               for n in (3, 5, 2)]
    budgets = (4, 3, 5)

    def run(prefix_cache):
        eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                            eos_id=-1, block_size=4, prefill_chunk=8,
                            prefix_cache=prefix_cache)
        # First two share the prefix CONCURRENTLY (2 lanes); the third is
        # admitted only after a donor retired, so its hit revives pooled
        # blocks.
        uids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, budgets)]
        out = eng.run()
        return eng, [out[u] for u in uids]

    eng_off, out_off = run(False)
    eng_on, out_on = run(True)
    assert out_on == out_off
    for out, p, m in zip(out_on, prompts, budgets):
        assert out == solo_greedy(cfg, params, p, m)
    # The cache actually did something: prompt tokens were skipped, and
    # the post-retirement admission revived pooled blocks.
    assert eng_off.stats.cached_prompt_tokens == 0
    assert eng_on.stats.cached_prompt_tokens > 0
    assert eng_on.stats.prefix_hit_rate > 0
    assert eng_on._alloc.lru_hits > 0
    eng_on._alloc.check_invariants()


def test_concurrent_sharers_hold_live_references(tiny):
    """A request admitted while its prefix donor is STILL DECODING shares
    the donor's live blocks (refcount >= 2 observed mid-run); outputs stay
    bit-identical to solo."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    shared = rng.integers(1, cfg.vocab_size, size=12)  # 3 full 4-blocks
    p1 = np.concatenate([shared, rng.integers(1, cfg.vocab_size, size=3)])
    p2 = np.concatenate([shared, rng.integers(1, cfg.vocab_size, size=2)])
    p3 = np.concatenate([shared, rng.integers(1, cfg.vocab_size, size=4)])
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        eos_id=-1, block_size=4, prefill_chunk=None)
    # p1 (long budget) and p2 (short) enter cold; p3 is admitted onto p2's
    # freed lane while p1 is still mid-decode and shares p1's live blocks.
    uids = [eng.submit(p, max_new_tokens=m)
            for p, m in zip((p1, p2, p3), (8, 2, 3))]
    done, max_ref = {}, 0
    while len(done) < 3:
        for uid, toks in eng.step():
            done[uid] = toks
        if eng._alloc._ref:
            max_ref = max(max_ref, max(eng._alloc._ref.values()))
    assert max_ref >= 2, "prefix blocks were never concurrently shared"
    for uid, p, m in zip(uids, (p1, p2, p3), (8, 2, 3)):
        assert done[uid] == solo_greedy(cfg, params, p, m)
    eng._alloc.check_invariants()


def test_preemption_recompute_bit_identical(tiny):
    """Optimistic admission over-commits a small pool; the engine preempts
    the youngest request and recomputes it — final outputs bit-identical
    to an unpressured run."""
    cfg, params = tiny
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(1, cfg.vocab_size, size=5), 16) for _ in range(3)]

    def run(num_blocks):
        eng = ServingEngine(cfg, params, max_batch=3, max_len=MAX_LEN,
                            eos_id=-1, block_size=4, num_blocks=num_blocks)
        uids = [eng.submit(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        return eng, [out[u] for u in uids]

    eng_big, ref = run(num_blocks=24)  # worst case fits: no pressure
    assert eng_big.stats.preemptions == 0
    # 3 lanes admit on prompt need (2 blocks each) but grow to
    # ceil((5+16)/4) = 6 blocks each = 18 > 10: preemption must kick in.
    eng_small, out = run(num_blocks=10)
    assert eng_small.stats.preemptions >= 1
    assert out == ref
    for out_i, (p, m) in zip(out, reqs):
        assert out_i == solo_greedy(cfg, params, p, m)
    eng_small._alloc.check_invariants()
    assert eng_small._alloc.live_blocks == 0


@pytest.mark.parametrize("k", [2, 3])
def test_decode_steps_bit_identical(tiny, k):
    """decode_steps=k runs k decode iterations per host sync with masked
    early-exit on retirement; outputs match the single-step engine even
    when budgets are not multiples of k."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (4, 9, 6)]
    budgets = (5, 7, 1)  # deliberately not multiples of k

    eng = ServingEngine(cfg, params, max_batch=3, max_len=MAX_LEN,
                        eos_id=-1, decode_steps=k)
    uids = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    out = eng.run()
    for uid, p, m in zip(uids, prompts, budgets):
        assert out[uid] == solo_greedy(cfg, params, p, m)
    # Host syncs amortize: ceil(max_budget / k) windows of k iterations.
    assert eng.stats.decode_steps == -(-max(budgets) // k) * k
    eng._alloc.check_invariants()
    assert eng._alloc.live_blocks == 0


def test_submit_rejects_impossible_request(tiny):
    """A request whose worst case exceeds what the pool/block table can
    EVER hold is rejected at submit with a clear error, not silently
    clamped or left to starve the queue."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        eos_id=-1, block_size=4, num_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(np.arange(1, 14), max_new_tokens=8)  # needs 6 > 3 blocks
    # Oversized prompts keep the dedicated message.
    with pytest.raises(ValueError, match="decode room"):
        eng.submit(np.arange(1, MAX_LEN + 2), max_new_tokens=1)
    # The pool was never touched.
    assert eng._alloc.live_blocks == 0
    assert eng.stats.admissions == 0


def test_zero_budget_request_retires_without_touching_pool(tiny):
    """max_new_tokens=0 completes immediately with an empty output — no
    admission, no blocks, no decode steps."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        eos_id=-1)
    u0 = eng.submit(np.arange(1, 6), max_new_tokens=0)
    u1 = eng.submit(np.arange(1, 6), max_new_tokens=2)
    out = eng.run()
    assert out[u0] == []
    assert out[u1] == solo_greedy(cfg, params, np.arange(1, 6), 2)
    assert eng.stats.admissions == 1  # only the real request
    # step() also delivers instant retirements when nothing else runs.
    u2 = eng.submit(np.arange(1, 4), max_new_tokens=0)
    assert eng.step() == [(u2, [])]
    assert eng._alloc.live_blocks == 0


def test_decode_step_vector_positions_paged(tiny):
    """Rows at different offsets through one block-table decode_step ==
    scalar decode on the dense reference cache."""
    cfg, params = tiny
    pa, pb = np.arange(1, 7), np.arange(2, 12)  # lengths 6 and 10

    def solo_next(prompt):
        logits, cache = M.prefill(
            cfg, params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
            max_len=MAX_LEN)
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits2, _ = M.decode_step(cfg, params, cache, t[:, None],
                                   jnp.int32(len(prompt)))
        return int(t[0]), np.asarray(logits2[0, 0])

    ta, la = solo_next(pa)
    tb, lb = solo_next(pb)

    bs = 8
    alloc = BlockStore(num_blocks=8, block_size=bs, num_slots=2,
                       max_blocks_per_slot=MAX_LEN // bs)
    cache = M.init_paged_cache(cfg, alloc.num_blocks + 1, bs)
    alloc.admit(0)
    alloc.admit(1)
    alloc.grow(0, 6)
    alloc.grow(1, 10)
    toks = np.zeros((2, 16), np.int32)
    toks[0, 16 - 6:] = pa
    toks[1, 16 - 10:] = pb
    logits, cache = M.prefill_slots(
        cfg, params, cache, jnp.asarray(toks),
        jnp.asarray([6, 10], jnp.int32), jnp.asarray(alloc.block_table()))
    t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert (int(t[0]), int(t[1])) == (ta, tb)
    alloc.grow(0, 7)
    alloc.grow(1, 11)
    logits2, _ = M.decode_step(cfg, params, cache, t[:, None],
                               jnp.asarray([6, 10], jnp.int32),
                               block_tables=jnp.asarray(alloc.block_table()))
    np.testing.assert_array_equal(np.asarray(logits2[:, 0]),
                                  np.stack([la, lb]))
