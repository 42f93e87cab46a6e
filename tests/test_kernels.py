"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

All Pallas kernels run in interpret mode (CPU executes the kernel body), as
specified for this CPU-only container; the BlockSpecs/grids are the TPU
deployment artifacts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.flash_decode import (flash_decode,
                                                    paged_flash_decode)
from repro.kernels.flash_decode.ref import decode_ref, paged_decode_ref
from repro.kernels.flash_prefill.flash_prefill import paged_flash_prefill
from repro.kernels.flash_prefill.ref import prefill_attention_ref
from repro.kernels.sclad_matmul.sclad_matmul import (
    block_compress, decompress, sclad_matmul)
from repro.kernels.sclad_matmul.ref import sclad_matmul_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,Hk,D,causal", [
    (2, 256, 256, 4, 2, 64, True),
    (1, 128, 384, 8, 8, 128, False),
    (2, 256, 256, 4, 1, 64, True),   # MQA
    (1, 512, 512, 2, 2, 128, True),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Sk, H, Hk, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hk, D)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hk,D,S", [
    (2, 8, 2, 64, 512), (1, 4, 4, 128, 256), (3, 8, 1, 64, 384)])
@pytest.mark.parametrize("length", [1, 129, None])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(B, H, Hk, D, S, length, dtype):
    length = S if length is None else min(length, S)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    kc = jax.random.normal(ks[1], (B, S, Hk, D)).astype(dtype)
    vc = jax.random.normal(ks[2], (B, S, Hk, D)).astype(dtype)
    out = flash_decode(q, kc, vc, jnp.int32(length), interpret=True)
    ref = decode_ref(q, kc, vc, jnp.int32(length))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


def test_flash_decode_per_row_lengths():
    """Rows of a continuous batch sit at different offsets: a (B,) lengths
    vector must reproduce per-row scalar-length runs exactly."""
    B, H, Hk, D, S = 4, 8, 2, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, S, Hk, D))
    vc = jax.random.normal(ks[2], (B, S, Hk, D))
    lengths = jnp.asarray([1, 127, 128, 256], jnp.int32)
    out = flash_decode(q, kc, vc, lengths, interpret=True)
    ref = decode_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # Row b of the batched run == a solo run at that row's scalar length.
    for b in range(B):
        solo = flash_decode(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                            lengths[b], interpret=True)
        np.testing.assert_array_equal(np.asarray(out[b]),
                                      np.asarray(solo[0]))


# ---------------------------------------------------------------------------
# paged flash decode (block-pool layout, tables via scalar prefetch)
# ---------------------------------------------------------------------------

def _stacked(x, layers=1):
    """(N, bs, Hk, D) random blocks -> the lane-dense (L, N, bs, Hk*D)
    layout ``model.init_paged_cache`` stores: the same values in layer 0,
    fresh ones in any further layer."""
    N, bs, Hk, D = x.shape
    more = [jax.random.normal(jax.random.PRNGKey(100 + i), x.shape)
            .astype(x.dtype) for i in range(layers - 1)]
    return jnp.stack([x] + more).reshape(layers, N, bs, Hk * D)


def _build_pool(rng_seed, B, Hk, D, bs, T, lengths, dtype,
                dead_lanes=(), layers=1):
    """A shared stacked pool + per-row tables: unique blocks per live row
    in random pool order, TRASH (0) for unallocated tails and for dead
    lanes."""
    n_blocks = 1 + sum(-(-int(l) // bs) for l in lengths)
    N = n_blocks + 2  # a couple of never-referenced blocks
    ks = jax.random.split(jax.random.PRNGKey(rng_seed), 3)
    k_pool = _stacked(jax.random.normal(ks[0], (N, bs, Hk, D)).astype(dtype),
                      layers)
    v_pool = _stacked(jax.random.normal(ks[1], (N, bs, Hk, D)).astype(dtype),
                      layers)
    rng = np.random.default_rng(rng_seed)
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((B, T), np.int32)
    for b in range(B):
        if b in dead_lanes:
            continue
        for j in range(-(-int(lengths[b]) // bs)):
            tables[b, j] = free.pop()
    return k_pool, v_pool, jnp.asarray(tables)


@pytest.mark.parametrize("B,H,Hk,D,bs,T", [
    (3, 8, 2, 64, 8, 4),    # GQA rep=4
    (2, 4, 4, 32, 4, 6),    # MHA, small blocks
    (4, 8, 1, 64, 16, 2),   # MQA, bigger blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode(B, H, Hk, D, bs, T, dtype):
    """Uneven per-row lengths (full table, single token, mid-block)
    against the dense-gather oracle."""
    rng = np.random.default_rng(5)
    lengths = np.asarray(
        [T * bs, 1] + [int(rng.integers(2, T * bs)) for _ in range(B - 2)],
        np.int32)[:B]
    k_pool, v_pool, tables = _build_pool(7, B, Hk, D, bs, T, lengths, dtype)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, H, D)).astype(dtype)
    out = paged_flash_decode(q, k_pool, v_pool, jnp.asarray(lengths), tables,
                             interpret=True)
    ref = paged_decode_ref(q, k_pool, v_pool, jnp.asarray(lengths), tables)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("block_k", [3, 4, 128])
def test_paged_flash_decode_block_k_mismatch(block_k):
    """The kernel's inner tile need not match the pool block size: any
    requested block_k (even the dense kernel's 128, or a non-divisor) is
    rounded to a divisor of bs without changing results."""
    B, H, Hk, D, bs, T = 2, 4, 2, 32, 8, 3
    lengths = np.asarray([T * bs, 11], np.int32)
    k_pool, v_pool, tables = _build_pool(11, B, Hk, D, bs, T, lengths,
                                         jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(13), (B, H, D))
    ref = paged_decode_ref(q, k_pool, v_pool, jnp.asarray(lengths), tables)
    out = paged_flash_decode(q, k_pool, v_pool, jnp.asarray(lengths), tables,
                             block_k=block_k, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_flash_decode_trash_lanes():
    """Dead lanes (all-trash tables — retired/preempted slots in the
    engine) walk only the trash block; live lanes are unaffected and the
    dead lanes' outputs equal the oracle's on the same masked garbage."""
    B, H, Hk, D, bs, T = 3, 4, 2, 32, 4, 4
    lengths = np.asarray([13, 1, 6], np.int32)  # row 1 is dead
    k_pool, v_pool, tables = _build_pool(17, B, Hk, D, bs, T, lengths,
                                         jnp.float32, dead_lanes=(1,))
    q = jax.random.normal(jax.random.PRNGKey(19), (B, H, D))
    out = paged_flash_decode(q, k_pool, v_pool, jnp.asarray(lengths), tables,
                             interpret=True)
    ref = paged_decode_ref(q, k_pool, v_pool, jnp.asarray(lengths), tables)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_paged_flash_decode_shared_blocks():
    """Two lanes whose tables name the SAME pool blocks (prefix sharing)
    read them concurrently without interference."""
    B, H, Hk, D, bs, T = 2, 4, 2, 32, 4, 3
    lengths = np.asarray([9, 6], np.int32)
    k_pool, v_pool, tables = _build_pool(23, B, Hk, D, bs, T, lengths,
                                         jnp.float32)
    tables = np.asarray(tables).copy()
    tables[1, 0] = tables[0, 0]  # shared prefix block
    tables = jnp.asarray(tables)
    q = jax.random.normal(jax.random.PRNGKey(29), (B, H, D))
    out = paged_flash_decode(q, k_pool, v_pool, jnp.asarray(lengths), tables,
                             interpret=True)
    ref = paged_decode_ref(q, k_pool, v_pool, jnp.asarray(lengths), tables)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged flash prefill (table-walked context + fused K/V scatter)
# ---------------------------------------------------------------------------

def _build_prefill_case(seed, B, H, Hk, D, bs, T, prefix, P, starts, lengths,
                        dtype, share_ctx_block=False, layers=1):
    """Chunk tensors + a shared stacked pool + per-row tables covering each
    row's cached context and write span (unique blocks in random pool
    order)."""
    S = prefix + P
    sv = np.zeros(B, np.int64) if starts is None else np.asarray(starts)
    first_extra = prefix if starts is None else 0
    need = [-(-(int(sv[b]) + first_extra + int(lengths[b])) // bs)
            for b in range(B)]
    N = 1 + sum(need) + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    kn = jax.random.normal(ks[1], (B, S, Hk, D)).astype(dtype)
    vn = jax.random.normal(ks[2], (B, S, Hk, D)).astype(dtype)
    kp = _stacked(jax.random.normal(ks[3], (N, bs, Hk, D)).astype(dtype),
                  layers)
    vp = _stacked(jax.random.normal(ks[4], (N, bs, Hk, D)).astype(dtype),
                  layers)
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((B, T), np.int32)
    for b in range(B):
        for j in range(need[b]):
            tables[b, j] = free.pop()
    if share_ctx_block and B >= 2:
        tables[1, 0] = tables[0, 0]  # read-only shared prefix block
    st = None if starts is None else jnp.asarray(starts, jnp.int32)
    return (q, kn, vn, kp, vp, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(tables), st)


def _check_prefill_parity(case, prefix, dtype):
    q, kn, vn, kp, vp, lengths, tables, st = case
    B = q.shape[0]
    ro, rk, rv = prefill_attention_ref(q, kn, vn, kp, vp, lengths, tables,
                                       start=st, prefix=prefix)
    sv = jnp.zeros((B,), jnp.int32) if st is None else st
    ko, kk, kv = paged_flash_prefill(q, kn, vn, kp, vp, lengths, tables, sv,
                                     prefix=prefix, has_ctx=st is not None,
                                     interpret=True)
    np.testing.assert_allclose(
        np.asarray(ko, np.float32), np.asarray(ro, np.float32),
        atol=tol(dtype), rtol=tol(dtype))
    # The fused scatter is EXACT (one-hot fp32 placement + the same cast
    # chain as the host path): pools must match the reference bitwise —
    # including untouched blocks, which the aliasing must leave alone.
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))


@pytest.mark.parametrize("B,H,Hk,D,bs,T", [
    (3, 8, 2, 64, 8, 4),    # GQA rep=4
    (2, 4, 4, 32, 4, 6),    # MHA, small blocks
    (4, 8, 1, 64, 16, 2),   # MQA, bigger blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_prefill_continuation(B, H, Hk, D, bs, T, dtype):
    """Continuation chunks (the prefix-cache-hit / chunked / preemption-
    recompute path): uneven starts (mid-block and on-boundary) and uneven
    left-padded lengths vs the dense gather+scatter oracle."""
    P = 8
    rng = np.random.default_rng(3)
    cap = (T - 1) * bs  # leave room for the chunk's writes in the table
    starts = [1 + int(rng.integers(0, max(cap - P, 1))) for _ in range(B)]
    starts[0] = bs  # exactly on a block boundary
    lengths = [P] + [int(rng.integers(1, P + 1)) for _ in range(B - 1)]
    case = _build_prefill_case(11, B, H, Hk, D, bs, T, 0, P, starts,
                               lengths, dtype)
    _check_prefill_parity(case, 0, dtype)


@pytest.mark.parametrize("prefix", [0, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_prefill_first_chunk(prefix, dtype):
    """First chunks (start=None): no context phase; a vlm patch prefix is
    written along with the left-compacted prompt tokens."""
    B, H, Hk, D, bs, T, P = 3, 4, 2, 32, 4, 6, 8
    lengths = [8, 3, 5]
    case = _build_prefill_case(13, B, H, Hk, D, bs, T, prefix, P, None,
                               lengths, dtype)
    _check_prefill_parity(case, prefix, dtype)


def test_paged_flash_prefill_shared_context_block():
    """Two lanes whose tables name the SAME cached context block (prefix
    sharing) read it concurrently; neither lane's (exclusive) write span
    disturbs it."""
    B, H, Hk, D, bs, T, P = 2, 4, 2, 32, 4, 6, 4
    case = _build_prefill_case(17, B, H, Hk, D, bs, T, 0, P, [4, 4], [4, 2],
                               jnp.float32, share_ctx_block=True)
    _check_prefill_parity(case, 0, jnp.float32)


def test_paged_flash_prefill_single_token_continuation():
    """The smallest continuation (one uncached token — a maximal prefix
    hit) still walks the whole cached context correctly."""
    B, H, Hk, D, bs, T, P = 2, 4, 1, 16, 4, 5, 4
    case = _build_prefill_case(19, B, H, Hk, D, bs, T, 0, P, [13, 7], [1, 1],
                               jnp.float32)
    _check_prefill_parity(case, 0, jnp.float32)


@pytest.mark.slow
@pytest.mark.parametrize("bs", [2, 4, 8, 16])
@pytest.mark.parametrize("P", [2, 4, 8, 16, 32])
def test_paged_flash_prefill_chunk_sweep(bs, P):
    """Heavyweight (bs, chunk) sweep across start offsets — every
    block-boundary alignment of the write span (nightly tier)."""
    B, H, Hk, D = 3, 4, 2, 32
    rng = np.random.default_rng(bs * 100 + P)
    for trial, start0 in enumerate([1, bs - 1, bs, bs + 1, 2 * bs]):
        T = -(-(start0 + 2 * bs + P) // bs) + 2
        starts = [start0] + [1 + int(rng.integers(0, start0 + bs))
                             for _ in range(B - 1)]
        lengths = [P] + [int(rng.integers(1, P + 1)) for _ in range(B - 1)]
        case = _build_prefill_case(23 + trial, B, H, Hk, D, bs, T, 0, P,
                                   starts, lengths, jnp.float32)
        _check_prefill_parity(case, 0, jnp.float32)


# ---------------------------------------------------------------------------
# SCLAD quantized KV pools (int8/fp8 payload + per-position fp32 scales)
# ---------------------------------------------------------------------------

def _quantize_pool(kp, vp, kv_dtype, D):
    """Compress a dense (L, N, bs, Hk*D) pool the way the engine stores
    it: per-position-per-head payload + fp32 scales (``models.kv_quant``),
    payload lane-dense, scales (L, N, bs, Hk)."""
    from repro.models import kv_quant

    def one(x):
        q, sc = kv_quant.quantize(x.reshape(x.shape[:-1] + (-1, D)),
                                  kv_dtype)
        return q.reshape(x.shape), sc

    (kq, ks), (vq, vs) = one(kp), one(vp)
    return kq, vq, ks, vs


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode_quantized(kv_dtype, dtype):
    """The fused dequant (payload * scale streamed through the table walk)
    against the gather-then-dequantize oracle."""
    B, H, Hk, D, bs, T = 3, 8, 2, 64, 8, 4
    lengths = np.asarray([T * bs, 1, 13], np.int32)
    k_pool, v_pool, tables = _build_pool(31, B, Hk, D, bs, T, lengths,
                                         jnp.float32)
    kq, vq, ks, vs = _quantize_pool(k_pool, v_pool, kv_dtype, D)
    q = jax.random.normal(jax.random.PRNGKey(37), (B, H, D)).astype(dtype)
    out = paged_flash_decode(q, kq, vq, jnp.asarray(lengths), tables,
                             kv_scales=(ks, vs), interpret=True)
    ref = paged_decode_ref(q, kq, vq, jnp.asarray(lengths), tables,
                           kv_scales=(ks, vs))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


def _check_prefill_parity_quantized(case, prefix, dtype, kv_dtype):
    """Kernel-vs-reference on a SCLAD pool: attention within tolerance,
    payload POOLS AND SCALES bitwise equal (the in-kernel quantize must
    reproduce ``kv_quant.quantize`` operation-for-operation, and aliasing
    must leave unwritten blocks' payload/scales untouched)."""
    q, kn, vn, kp, vp, lengths, tables, st = case
    B = q.shape[0]
    kq, vq, ks, vs = _quantize_pool(kp.astype(jnp.float32),
                                    vp.astype(jnp.float32), kv_dtype,
                                    q.shape[-1])
    ro, rk, rv, rks, rvs = prefill_attention_ref(
        q, kn, vn, kq, vq, lengths, tables, start=st, prefix=prefix,
        kv_scales=(ks, vs), kv_dtype=kv_dtype)
    sv = jnp.zeros((B,), jnp.int32) if st is None else st
    ko, kk, kv, kks, kvs = paged_flash_prefill(
        q, kn, vn, kq, vq, lengths, tables, sv, prefix=prefix,
        has_ctx=st is not None, interpret=True, kv_scales=(ks, vs),
        kv_dtype=kv_dtype)
    np.testing.assert_allclose(
        np.asarray(ko, np.float32), np.asarray(ro, np.float32),
        atol=tol(dtype), rtol=tol(dtype))
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(kks), np.asarray(rks))
    np.testing.assert_array_equal(np.asarray(kvs), np.asarray(rvs))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_prefill_quantized_continuation(kv_dtype, dtype):
    """Continuation chunks on a quantized pool: fused context dequant +
    in-kernel quantized scatter vs the host-side reference."""
    B, H, Hk, D, bs, T, P = 3, 8, 2, 64, 8, 4, 8
    rng = np.random.default_rng(41)
    cap = (T - 1) * bs
    starts = [bs] + [1 + int(rng.integers(0, max(cap - P, 1)))
                     for _ in range(B - 1)]
    lengths = [P] + [int(rng.integers(1, P + 1)) for _ in range(B - 1)]
    case = _build_prefill_case(43, B, H, Hk, D, bs, T, 0, P, starts,
                               lengths, dtype)
    _check_prefill_parity_quantized(case, 0, dtype, kv_dtype)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("prefix", [0, 4])
def test_paged_flash_prefill_quantized_first_chunk(kv_dtype, prefix):
    """First chunks (vlm patch prefix included) quantize every written
    position; untouched blocks keep their (garbage) payload and scales."""
    B, H, Hk, D, bs, T, P = 3, 4, 2, 32, 4, 6, 8
    case = _build_prefill_case(47, B, H, Hk, D, bs, T, prefix, P, None,
                               [8, 3, 5], jnp.bfloat16)
    _check_prefill_parity_quantized(case, prefix, jnp.bfloat16, kv_dtype)


def test_quantized_scatter_path_independent():
    """The SAME tokens written as one 8-token chunk or as two 4-token
    chunks leave BITWISE identical payload and scales in the pool — the
    property that makes the hash chain a sound content address for
    compressed blocks (and preemption recompute safe)."""
    B, H, Hk, D, bs, T, P = 1, 4, 2, 32, 4, 4, 8
    case = _build_prefill_case(53, B, H, Hk, D, bs, T, 0, P, [4],
                               [P], jnp.bfloat16)
    q, kn, vn, kp, vp, lengths, tables, st = case
    kq, vq, ks, vs = _quantize_pool(kp.astype(jnp.float32),
                                    vp.astype(jnp.float32), "int8", D)
    _, k1, v1, ks1, vs1 = prefill_attention_ref(
        q, kn, vn, kq, vq, lengths, tables, start=st,
        kv_scales=(ks, vs), kv_dtype="int8")
    # Same tokens, two half chunks (left-padded to the same width P).
    half = P // 2
    pools = (kq, vq, ks, vs)
    for c in range(2):
        pad = jnp.zeros((B, half) + kn.shape[2:], kn.dtype)
        sl = slice(c * half, (c + 1) * half)
        qc = jnp.concatenate(
            [jnp.zeros((B, half) + q.shape[2:], q.dtype), q[:, sl]], axis=1)
        knc = jnp.concatenate([pad, kn[:, sl]], axis=1)
        vnc = jnp.concatenate([pad, vn[:, sl]], axis=1)
        _, *pools = prefill_attention_ref(
            qc, knc, vnc, pools[0], pools[1],
            jnp.full((B,), half, jnp.int32), tables,
            start=st + c * half, kv_scales=(pools[2], pools[3]),
            kv_dtype="int8")
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(pools[0]))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(pools[1]))
    np.testing.assert_array_equal(np.asarray(ks1), np.asarray(pools[2]))
    np.testing.assert_array_equal(np.asarray(vs1), np.asarray(pools[3]))


# ---------------------------------------------------------------------------
# one layer of a stacked pool: the layer index rides the scalar prefetch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_decode_at_layer_of_stacked_pool(kv_dtype):
    """The kernel reads the slab of the layer it is given (here 2 of a
    3-layer pool, through the scalar-prefetched index), matching the
    reference at that layer and differing from the other layers'."""
    B, H, Hk, D, bs, T, layer = 3, 8, 2, 64, 8, 4, 2
    lengths = np.asarray([T * bs, 1, 13], np.int32)
    k_pool, v_pool, tables = _build_pool(59, B, Hk, D, bs, T, lengths,
                                         jnp.float32, layers=3)
    scales = None
    if kv_dtype == "int8":
        k_pool, v_pool, *scales = _quantize_pool(k_pool, v_pool, "int8", D)
        scales = tuple(scales)
    q = jax.random.normal(jax.random.PRNGKey(61), (B, H, D))
    out = paged_flash_decode(q, k_pool, v_pool, jnp.asarray(lengths), tables,
                             jnp.int32(layer), kv_scales=scales,
                             interpret=True)
    for other in range(3):
        ref = paged_decode_ref(q, k_pool, v_pool, jnp.asarray(lengths),
                               tables, kv_scales=scales, layer=other)
        close = np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                            rtol=2e-5)
        assert close == (other == layer), other


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_prefill_at_layer_of_stacked_pool(kv_dtype):
    """The kernel reads context from, and writes the chunk's K/V in place
    into, the layer it is given (1 of a 3-layer pool): output and every
    pool leaf match the reference at that layer, and the other layers'
    bytes are left bit-identical."""
    B, H, Hk, D, bs, T, P, layer = 3, 8, 2, 64, 8, 4, 8, 1
    rng = np.random.default_rng(67)
    starts = [bs] + [1 + int(rng.integers(0, (T - 1) * bs - P))
                     for _ in range(B - 1)]
    lengths = [P] + [int(rng.integers(1, P + 1)) for _ in range(B - 1)]
    q, kn, vn, kp, vp, lengths, tables, st = _build_prefill_case(
        71, B, H, Hk, D, bs, T, 0, P, starts, lengths, jnp.float32,
        layers=3)
    pools, quant = (kp, vp), None
    if kv_dtype == "int8":
        pools, quant = _quantize_pool(kp, vp, "int8", D), "int8"
    scales = tuple(pools[2:]) or None
    ref = prefill_attention_ref(q, kn, vn, pools[0], pools[1], lengths,
                                tables, start=st, kv_scales=scales,
                                kv_dtype=quant, layer=layer)
    got = paged_flash_prefill(q, kn, vn, pools[0], pools[1], lengths, tables,
                              st, jnp.int32(layer), interpret=True,
                              kv_scales=scales, kv_dtype=quant)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                               atol=2e-5, rtol=2e-5)
    for before, r, g in zip(pools, ref[1:], got[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        changed = [not np.array_equal(np.asarray(g[i]), np.asarray(before[i]))
                   for i in range(3)]
        assert changed == [i == layer for i in range(3)], changed


# ---------------------------------------------------------------------------
# SCLD matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,C", [
    (128, 256, 128, 6), (256, 128, 256, 16), (128, 384, 256, 4),
    (384, 128, 128, 1)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_sclad_matmul(M, K, N, C, dtype):
    rng = np.random.default_rng(0)
    w = rng.standard_normal((K, N)).astype(np.float32)
    vals, rows = block_compress(w, C)
    x = jnp.asarray(rng.standard_normal((M, K))).astype(dtype)
    y = sclad_matmul(x, jnp.asarray(vals).astype(dtype),
                     jnp.asarray(rows), interpret=True)
    yr = sclad_matmul_ref(x, np.asarray(vals, np.float32), rows)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        atol=1e-1 if dtype == jnp.bfloat16 else 1e-4,
        rtol=5e-2 if dtype == jnp.bfloat16 else 2e-2)


def test_block_compress_roundtrip_full_capacity():
    rng = np.random.default_rng(1)
    w = rng.standard_normal((256, 256)).astype(np.float32)
    vals, rows = block_compress(w, 16)
    assert np.allclose(decompress(vals, rows), w)


def test_block_compress_keeps_largest_units():
    w = np.zeros((128, 128), np.float32)
    w[0:8] = 100.0  # unit 0 is the largest
    w[64:72] = 50.0  # unit 8 second
    vals, rows = block_compress(w, 2)
    assert set(rows[0, 0].tolist()) == {0, 8}
    assert np.allclose(decompress(vals, rows), w)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BH,S,P,N,chunk", [
    (4, 256, 64, 32, 64), (2, 128, 32, 16, 128), (1, 512, 64, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(BH, S, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    xdt = (jax.random.normal(ks[0], (BH, S, P)) * 0.1).astype(dtype)
    a = (-jnp.abs(jax.random.normal(ks[1], (BH, S))) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (BH, S, N)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[3], (BH, S, N)) * 0.3).astype(dtype)
    y, st = ssd_scan(xdt, a, b, c, chunk=chunk, interpret=True)
    yr, str_ = ssd_scan_ref(xdt, a, b, c)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        atol=tol(dtype) * 5, rtol=tol(dtype) * 5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                               atol=tol(dtype) * 5, rtol=tol(dtype) * 5)
