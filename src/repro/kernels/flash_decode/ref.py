"""Pure-jnp oracles for flash decode (dense and paged layouts).

``decode_ref`` reproduces ``models.layers._sdpa`` arithmetic EXACTLY
(compute-dtype score einsum, fp32 masked softmax, compute-dtype probs @ V):
it is both the kernel parity oracle and the engine's CPU fallback, so the
serving bit-identity matrix (tests/test_continuous_batching.py) holds
bitwise against the pre-kernel gather path.  Masked lanes score ``-1e30``,
which underflows to an exact 0 after the softmax's max-subtraction —
results are therefore independent of how much dead padding the cache
carries, which is what makes dense (S_max) and paged (table_width * bs)
layouts bit-comparable.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_ref(q, k_cache, v_cache, lengths):
    """q: (B, H, D); caches: (B, S, Hk, D); lengths: scalar int32 or (B,)
    valid positions per row -> (B, H, D)."""
    B, H, D = q.shape
    S, Hk = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hk
    lens = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))
    qg = q.reshape(B, 1, Hk, rep, D)
    k = k_cache.astype(q.dtype)
    v = v_cache.astype(q.dtype)
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    mask = jnp.arange(S)[None] < lens[:, None]  # (B, S)
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(B, H, D)


def paged_decode_ref(q, k_pool, v_pool, lengths, block_tables,
                     kv_scales=None, layer=0):
    """Gather oracle for the paged kernel: resolve each lane's block table
    into a dense per-lane cache copy, then run ``decode_ref``.

    q: (B, H, D); pools: (L, N, bs, Hk*D) lane-dense, every layer;
    lengths: (B,) int32; block_tables: (B, T) int32; layer: int32 scalar,
    the layer read.  This MATERIALIZES the (B, T*bs, Hk, D) copy the kernel
    exists to avoid — it is the correctness oracle (and the
    ``attn_kernel="off"`` fallback), not the hot path.

    kv_scales: (k_scale, v_scale) (L, N, bs, Hk) fp32 for a SCLAD quantized
    pool — the gathered payload is dequantized (fp32 multiply, one cast to
    q.dtype: ``models.kv_quant.dequantize``) before attention, the
    load-as-dense half of the compressed layout.
    """
    B, _, D = q.shape
    Hk = k_pool.shape[-1] // D
    kc = k_pool[layer, block_tables].reshape(B, -1, Hk, D)
    vc = v_pool[layer, block_tables].reshape(B, -1, Hk, D)
    if kv_scales is not None:
        from repro.models import kv_quant
        k_scale, v_scale = kv_scales
        ks = k_scale[layer, block_tables].reshape(B, -1, Hk)
        vs = v_scale[layer, block_tables].reshape(B, -1, Hk)
        kc = kv_quant.dequantize(kc, ks, q.dtype)
        vc = kv_quant.dequantize(vc, vs, q.dtype)
    return decode_ref(q, kc, vc, lengths)
