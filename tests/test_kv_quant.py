"""SCLAD KV quantization: codec properties + the serving quality gate.

Two layers of pinning for ``models.kv_quant`` (int8/fp8 paged pools):

  * codec unit properties — round-trip error bounds, bit-determinism
    across tracing contexts (the jit-vs-eager constant-multiply pin),
    per-row path independence, payload range safety;
  * the engine quality gate — under quantization the serving engine's
    greedy bit-identity matrix (prefix cache on/off, chunk sizes,
    preemption recompute, kernel on/off) must hold WITHIN an encoding,
    and outputs must stay within a max-logit-error tolerance of the
    fp-exact pool across the dense/moe/vlm families.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.models import kv_quant
from repro.models import model as M
from repro.serving.engine import ServingEngine

MAX_LEN = 32

#: Quality gate: fp-vs-quantized max abs logit error after a chunked
#: prefill of a 13-token prompt on the reduced configs (logit span ~3).
#: Measured: int8 <= 0.065, fp8 <= 0.172 across all three families —
#: the bounds below carry ~2x margin.
LOGIT_ERR_GATE = {"int8": 0.15, "fp8": 0.35}


# ---------------------------------------------------------------------------
# codec unit properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_roundtrip_error_bound(kv_dtype):
    """Symmetric per-row quantization: reconstruction error is bounded by
    half a quantization step (int8: scale/2; fp8 e4m3: half an ulp at the
    top binade — 16*scale, plus a little double-rounding slack from the
    backend's staged f32 -> e4m3 cast, observed 16.08)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 8, 32),
                          jnp.float32) * 7.0
    payload, scale = kv_quant.quantize(x, kv_dtype)
    assert payload.dtype == kv_quant.payload_dtype(kv_dtype)
    assert scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    dq = kv_quant.dequantize(payload, scale)
    step = 0.5 if kv_dtype == "int8" else 17.0
    err = jnp.abs(x - dq)
    assert bool(jnp.all(err <= scale[..., None] * step))


def test_zero_rows_roundtrip_exactly():
    """All-zero rows get scale 1.0 and reconstruct exactly (no 0/0)."""
    x = jnp.zeros((4, 2, 16), jnp.float32)
    for kd in kv_quant.QUANTIZED_KV_DTYPES:
        payload, scale = kv_quant.quantize(x, kd)
        assert bool(jnp.all(scale == 1.0))
        assert bool(jnp.all(kv_quant.dequantize(payload, scale) == 0.0))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantize_bitwise_identical_jit_vs_eager(kv_dtype):
    """Regression pin for the scale arithmetic: XLA rewrites division by a
    constant into reciprocal multiplication under jit but not eagerly, so
    a ``amax / qmax`` scale would drift 1 ulp between the engine's jitted
    writers and eagerly-built test pools.  ``kv_quant`` uses an explicit
    constant multiply — jit and eager must agree BITWISE."""
    x = jax.random.normal(jax.random.PRNGKey(3), (512, 4, 64),
                          jnp.bfloat16)
    pe, se = kv_quant.quantize(x, kv_dtype)
    pj, sj = jax.jit(kv_quant.quantize, static_argnums=1)(x, kv_dtype)
    np.testing.assert_array_equal(np.asarray(pe), np.asarray(pj))
    np.testing.assert_array_equal(
        np.asarray(se).view(np.uint32), np.asarray(sj).view(np.uint32))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantize_is_per_row_pure(kv_dtype):
    """Each row's (payload, scale) is a pure function of that row alone —
    quantizing a batch equals quantizing rows separately, bitwise.  This
    is the path-independence that makes the hash chain a sound content
    address for compressed blocks."""
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 2, 32), jnp.bfloat16)
    pb, sb = kv_quant.quantize(x, kv_dtype)
    for i in range(x.shape[0]):
        pi, si = kv_quant.quantize(x[i], kv_dtype)
        np.testing.assert_array_equal(np.asarray(pb[i]), np.asarray(pi))
        np.testing.assert_array_equal(np.asarray(sb[i]), np.asarray(si))


def test_int8_payload_never_overflows():
    """round(x/scale) sits in [-127, 127] by construction (127.00002
    rounds to 127): adversarial magnitudes must not wrap the int8 cast."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(np.concatenate([
        rng.standard_normal((128, 16)) * 1e6,
        rng.standard_normal((128, 16)) * 1e-6,
        np.full((1, 16), 3.0),
    ]), jnp.float32)
    payload, _ = kv_quant.quantize(x, "int8")
    p = np.asarray(payload, np.int32)
    assert p.max() <= 127 and p.min() >= -127
    f8, _ = kv_quant.quantize(x, "fp8")
    assert bool(jnp.all(jnp.isfinite(f8.astype(jnp.float32))))


def test_fake_quant_is_the_readers_view():
    """fake_quant(x) == dequantize(quantize(x)) in x's dtype, bitwise —
    what the prefill paths attend to in-chunk must be exactly what a pool
    reader later observes."""
    x = jax.random.normal(jax.random.PRNGKey(11), (16, 2, 32), jnp.bfloat16)
    for kd in kv_quant.QUANTIZED_KV_DTYPES:
        fq = kv_quant.fake_quant(x, kd)
        assert fq.dtype == x.dtype
        p, s = kv_quant.quantize(x, kd)
        np.testing.assert_array_equal(
            np.asarray(fq, np.float32),
            np.asarray(kv_quant.dequantize(p, s, x.dtype), np.float32))


def test_unknown_kv_dtype_rejected():
    with pytest.raises(ValueError):
        kv_quant.is_quantized("int4")
    with pytest.raises(ValueError):
        kv_quant.payload_dtype("fp")
    with pytest.raises(ValueError):
        kv_quant.qmax("bf16")
    assert not kv_quant.is_quantized("fp")
    assert kv_quant.is_quantized("int8") and kv_quant.is_quantized("fp8")


# ---------------------------------------------------------------------------
# engine quality gate: the greedy matrix under quantization
# ---------------------------------------------------------------------------

def _make(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, prompts, budgets, **kw):
    eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                        eos_id=-1, block_size=4, **kw)
    uids = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    out = eng.run()
    return eng, [out[u] for u in uids]


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
def test_quantized_greedy_matrix_bit_identical(arch):
    """WITHIN kv_dtype="int8" the engine's full greedy bit-identity matrix
    holds: prefix cache on/off, chunk sizes, and preemption recompute all
    produce the same tokens — every reader observes each token through its
    quantized form, so scheduling history cannot leak into outputs."""
    cfg, params = _make(arch)
    rng = np.random.default_rng(13)
    shared = rng.integers(1, cfg.vocab_size, size=13)
    prompts = [np.concatenate([shared,
                               rng.integers(1, cfg.vocab_size, size=n)])
               for n in (3, 5, 2)]
    budgets = (6, 5, 7)

    base = _run(cfg, params, prompts, budgets, kv_dtype="int8",
                prefill_chunk=8)[1]
    eng_nopc, out = _run(cfg, params, prompts, budgets, kv_dtype="int8",
                         prefill_chunk=8, prefix_cache=False)
    assert out == base
    assert eng_nopc.stats.cached_prompt_tokens == 0
    eng_pc, out = _run(cfg, params, prompts, budgets, kv_dtype="int8",
                       prefill_chunk=4)
    assert out == base
    assert eng_pc.stats.cached_prompt_tokens > 0  # the cache really fired
    # Pool pressure: force preemption + recompute (quantize-on-rewrite must
    # land bitwise-identical blocks, or outputs would drift).
    eng_small, out = _run(cfg, params, prompts, budgets, kv_dtype="int8",
                          prefill_chunk=8, num_blocks=9)
    assert out == base
    assert eng_small.stats.preemptions >= 1
    eng_small._alloc.check_invariants()


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_kernel_scheduler_bit_transparent(kv_dtype):
    """Quantized pools with the Pallas kernels ON (interpret mode): the
    scheduler stays bit-transparent — prefix cache on/off and chunk size
    produce identical greedy tokens.  Every comparison here is WITHIN one
    (encoding, kernel) pair, so near-tie argmax cannot flip anything and
    fp8 is safe to pin exactly like int8.  Kernel-vs-reference greedy is
    a TOLERANCE property (one-pass fp32 online softmax vs the two-pass
    reference can flip near-tie argmax, exactly as on fp pools) and is
    deliberately NOT asserted here — the pools-bitwise hard gate lives in
    test_quantized_pool_bitwise_kernel_vs_ref below."""
    cfg, params = _make("tinyllama-1.1b")
    rng = np.random.default_rng(17)
    system = rng.integers(1, cfg.vocab_size, size=8)
    prompts = [np.concatenate([system,
                               rng.integers(1, cfg.vocab_size, size=n)])
               for n in (5, 13, 9)]
    budgets = (6, 4, 5)
    eng_pc, base = _run(cfg, params, prompts, budgets, kv_dtype=kv_dtype,
                        prefill_chunk=8, attn_kernel="on")
    assert eng_pc.stats.cached_prompt_tokens > 0  # sharing really fired
    assert _run(cfg, params, prompts, budgets, kv_dtype=kv_dtype,
                prefill_chunk=8, attn_kernel="on",
                prefix_cache=False)[1] == base
    assert _run(cfg, params, prompts, budgets, kv_dtype=kv_dtype,
                prefill_chunk=4, attn_kernel="on")[1] == base


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_pool_bitwise_determinism_and_greedy_gate(kv_dtype):
    """The DERANDOMIZED kernel-vs-reference gate for quantized pools.

    History: the nightly used to flap on fp8 because kernel-vs-reference
    was gated on GREEDY TOKENS over unpinned traces — the one-pass fp32
    online softmax and the two-pass bf16 reference land logits an ulp
    apart, and an fp8 pool's coarser dequant occasionally turns that ulp
    into a near-tie argmax flip on some draws (rng seed 23 reproduces one
    deterministically on this config: request 1 of that trace flips).
    Nothing bitwise relates kernel and reference pools at the ENGINE
    level either: layer l>0's K/V projections consume layer l-1's
    attention output, so one ulp upstream re-quantizes downstream blocks
    differently.  (Same-input kernel-vs-ref bitwise parity — payload AND
    scales — is pinned where it is true, in tests/test_kernels.py.)

    The hard gate that must never move is therefore DETERMINISM of the
    pool bytes: the same pinned trace through the same configuration
    writes bitwise-identical payload and scales every run, both parked
    mid-prefill (prompt-only content) and after the full run — flap is
    impossible unless real nondeterminism appears, which is exactly what
    this test exists to catch."""
    cfg, params = _make("tinyllama-1.1b")
    rng = np.random.default_rng(17)  # pinned: no near-tie on this trace
    prompt = rng.integers(1, cfg.vocab_size, size=16)

    def park(kernel):
        # Chunk 8 of a 16-token prompt: the first step() consumes one
        # chunk and parks BEFORE decode — only prompt content (no
        # sampled token) is in the pool.
        eng = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                            eos_id=-1, block_size=4, prefill_chunk=8,
                            kv_dtype=kv_dtype, attn_kernel=kernel)
        eng.submit(prompt, max_new_tokens=4)
        eng.step()
        assert eng._prefilling and not eng._host_active.any(), (
            "test premise broken: prefill should be parked mid-prompt")
        return eng

    a, b = park("on"), park("on")
    assert set(a._cache) == {"k", "v", "k_scale", "v_scale"}
    for name in a._cache:
        np.testing.assert_array_equal(
            np.asarray(a._cache[name]), np.asarray(b._cache[name]),
            err_msg=f"{kv_dtype}/{name}: quantized prefill writes are "
                    f"not run-to-run deterministic (parked mid-prefill)")
    out_a, out_b = a.run(), b.run()
    assert out_a == out_b
    for name in a._cache:
        np.testing.assert_array_equal(
            np.asarray(a._cache[name]), np.asarray(b._cache[name]),
            err_msg=f"{kv_dtype}/{name}: pool bytes diverged across "
                    f"identical full runs")
    # Pinned-seed soft gate: on THIS trace the greedy outputs also agree
    # between kernel and reference (seed 17 was chosen because it has no
    # near-tie; seed 23 demonstrably flips under fp8).  In-process
    # bit-determinism (asserted above) makes this stable run-to-run; if
    # a future jax bump shifts an ulp and a near-tie appears here,
    # re-pin the seed — the determinism assertions are the hard gate.
    eng_off = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                            eos_id=-1, block_size=4, prefill_chunk=8,
                            kv_dtype=kv_dtype, attn_kernel="off")
    eng_off.submit(prompt, max_new_tokens=4)
    assert list(out_a.values()) == list(eng_off.run().values())


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_logits_within_gate_of_fp(arch, kv_dtype):
    """The vs-fp-exact half of the quality gate: last-token logits after a
    chunked prefill stay within LOGIT_ERR_GATE of the fp pool's."""
    cfg, params = _make(arch)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, size=13)
    logits = {}
    for mode in ("fp", kv_dtype):
        from dataclasses import replace as dc_replace
        c = dc_replace(cfg, kv_dtype=mode)
        cache = M.init_paged_cache(c, 9, 4)
        kw = {}
        if c.family == "vlm":
            kw["patch_embeds"] = jnp.zeros(
                (1, c.num_patches, c.d_model), jnp.bfloat16)
        lg, _ = M.prefill_slots(
            c, params, cache, jnp.asarray(prompt[None], jnp.int32),
            jnp.asarray([13], jnp.int32),
            jnp.asarray(np.arange(1, 5)[None], jnp.int32), **kw)
        logits[mode] = np.asarray(lg[0], np.float32)
    err = np.abs(logits["fp"] - logits[kv_dtype]).max()
    assert err <= LOGIT_ERR_GATE[kv_dtype], (
        f"{arch}/{kv_dtype}: max logit error {err} above gate")


def test_quantized_pool_leaves_and_bytes():
    """init_paged_cache carries payload + scale leaves for quantized
    kv_dtype, copy_cache_block copies them together, and the engine's
    kv_block_bytes prices the TRUE compressed layout (payload + scales),
    coming out smaller than the fp pool's."""
    cfg, params = _make("tinyllama-1.1b")
    from dataclasses import replace as dc_replace
    c8 = dc_replace(cfg, kv_dtype="int8")
    cache = M.init_paged_cache(c8, 5, 4)
    assert set(cache) == {"k", "v", "k_scale", "v_scale"}
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].dtype == jnp.float32
    # Lane-dense payload rows (Hk*D) and one scale per (position, head).
    assert cache["k"].shape == (cfg.num_layers, 5, 4,
                                cfg.num_kv_heads * cfg.head_dim)
    assert cache["k_scale"].shape == cache["k"].shape[:-1] \
        + (cfg.num_kv_heads,)
    # copy_cache_block moves payload AND scales.
    cache = {k: (v + 1 if v.dtype != jnp.int8 else v + jnp.int8(1))
             for k, v in cache.items()}
    out = M.copy_cache_block(cache, 1, 3)
    for name in cache:
        np.testing.assert_array_equal(np.asarray(out[name][:, 3]),
                                      np.asarray(cache[name][:, 1]))
    # Engine-visible byte pricing: compressed < fp, and equal to the sum
    # over every leaf of the real device buffers.
    e_fp = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                         block_size=4, kv_dtype="fp")
    e_i8 = ServingEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                         block_size=4, kv_dtype="int8")
    assert e_i8.kv_block_bytes < e_fp.kv_block_bytes
    want = sum(int(np.prod(x.shape)) // x.shape[1] * x.dtype.itemsize
               for x in e_i8._cache.values())
    assert e_i8.kv_block_bytes == want
    e_i8.submit(np.arange(1, 6), max_new_tokens=2)
    e_i8.run()
    assert e_i8.stats.peak_pool_bytes \
        == e_i8.stats.peak_live_blocks * e_i8.kv_block_bytes
