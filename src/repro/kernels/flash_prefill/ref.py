"""Pure-jnp oracle for paged chunked-prefill attention (+ K/V scatter).

``prefill_attention_ref`` reproduces the pre-kernel ``model.prefill_slots``
per-layer arithmetic EXACTLY:

  * the cached-context gather ``k_pool[layer, block_tables]``
    materializing the dense (B, T*bs, Hk, D) per-lane copy the kernel
    exists to avoid,
  * the dense (B, S, S) causal/left-pad mask and its (B, S, T*bs) context
    extension,
  * ``models.layers._sdpa`` arithmetic (compute-dtype score einsum, fp32
    masked softmax, compute-dtype probs @ V),
  * the host-side left-compact roll + block-table scatter of the chunk's
    new-token K/V (``.at[blk, off].set(..., mode="drop")``).

It is both the kernel parity oracle and the engine's CPU fallback
(``attn_kernel="off"`` / "auto" off-TPU), so the serving bit-identity
matrix in tests/test_continuous_batching.py holds bitwise against the
pre-refactor gather path.  Masked lanes score ``-1e30`` (exact 0 after the
softmax max-subtraction), so results are independent of how much dead
padding the gathered context carries.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def prefill_attention_ref(q, k_new, v_new, k_pool, v_pool, lengths,
                          block_tables,
                          start: Optional[jnp.ndarray] = None,
                          prefix: int = 0,
                          kv_scales=None, kv_dtype: Optional[str] = None,
                          layer=0):
    """One layer of chunked-prefill attention against a paged KV pool.

    q:             (B, S, H, D)  rotated queries of this chunk (S = prefix
                   + P: an optional vlm patch prefix plus P LEFT-padded
                   prompt tokens);
    k_new/v_new:   (B, S, Hk, D) this chunk's rotated K/V (compute dtype);
    k_pool/v_pool: (L, N, bs, Hk*D) the shared lane-dense block pool of
                   every layer (pool storage dtype; trash block included);
    lengths:       (B,) int32 true token count of the chunk (<= P);
    block_tables:  (B, T) int32 per-lane tables;
    start:         None => first chunk (rows start at cache position 0, no
                   cached context); else (B,) int32 cache positions already
                   filled per row — the chunk attends to positions
                   [0, start) gathered through the table;
    prefix:        static vlm patch-prefix length (first chunk only);
    kv_scales:     optional (k_scale, v_scale) (L, N, bs, Hk) fp32 scales
                   of a SCLAD quantized pool, with ``kv_dtype`` ("int8"/"fp8")
                   naming the payload encoding.  Quantized semantics: the
                   gathered context payload is dequantized on load, the
                   chunk's OWN in-flight K/V is fake-quantized before
                   attention (every reader observes each token through
                   ``dequantize(quantize(x))`` — in-chunk and from-pool
                   scoring agree, so greedy bit-identity across chunk
                   sizes / prefix hits / preemption recomputes survives
                   quantization), and the scatter writes payload + scales;
    layer:         int32 scalar, the pool layer read and written.

    Returns (attn_out (B, S, H*D) in q.dtype, k_pool', v_pool') with the
    chunk's new K/V left-compacted and scattered through the table at
    positions ``start + i`` (junk-tail writes dropped); quantized calls
    append (k_scale', v_scale').
    """
    B, S, H, D = q.shape
    Hk = k_new.shape[2]
    rep = H // Hk
    P = S - prefix
    lengths = jnp.asarray(lengths, jnp.int32)
    pad = (P - lengths).astype(jnp.int32)  # (B,)
    start_v = jnp.zeros((B,), jnp.int32) if start is None \
        else jnp.asarray(start, jnp.int32)
    quantized = kv_scales is not None
    if quantized:
        from repro.models import kv_quant

    # Key j is visible to query i iff causal AND j is not a pad slot.
    sidx = jnp.arange(S)
    real_key = (sidx[None] < prefix) | (sidx[None] >= prefix + pad[:, None])
    mask = (sidx[None, None, :] <= sidx[None, :, None]) \
        & real_key[:, None, :]  # (B, S, S)

    kk, vv = k_new, v_new
    if quantized:
        # Store-as-compressed consistency: attend to the chunk's K/V as a
        # pool reader will see it once written.
        kk = kv_quant.fake_quant(k_new, kv_dtype)
        vv = kv_quant.fake_quant(v_new, kv_dtype)
    if start is not None:
        # Dense per-lane context gather — the O(B*T*bs*Hk*D) copy this
        # oracle pins and the kernel path provably never materializes.
        bs = k_pool.shape[2]
        kg = k_pool[layer, block_tables].reshape(B, -1, Hk, D)
        vg = v_pool[layer, block_tables].reshape(B, -1, Hk, D)
        if quantized:
            k_scale, v_scale = kv_scales
            ksg = k_scale[layer, block_tables].reshape(B, -1, Hk)
            vsg = v_scale[layer, block_tables].reshape(B, -1, Hk)
            kg = kv_quant.dequantize(kg, ksg, q.dtype)
            vg = kv_quant.dequantize(vg, vsg, q.dtype)
        ctx_len = block_tables.shape[1] * bs
        ctx_mask = jnp.arange(ctx_len)[None] < start_v[:, None]  # (B, T*bs)
        kk = jnp.concatenate([kg.astype(q.dtype), kk], axis=1)
        vv = jnp.concatenate([vg.astype(q.dtype), vv], axis=1)
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_mask[:, None, :], (B, S, ctx_len)),
             jnp.broadcast_to(mask, (B, S, S))], axis=-1)

    # models.layers._sdpa arithmetic, reproduced exactly.
    qg = q.reshape(B, S, Hk, rep, D)
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kk).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, vv).reshape(B, S, H * D)

    if quantized:
        k_pool, v_pool, k_scale, v_scale = scatter_new_kv_ref(
            k_new, v_new, k_pool, v_pool, lengths, block_tables,
            start=start, prefix=prefix, kv_scales=kv_scales,
            kv_dtype=kv_dtype, layer=layer)
        return out, k_pool, v_pool, k_scale, v_scale
    k_pool, v_pool = scatter_new_kv_ref(k_new, v_new, k_pool, v_pool,
                                        lengths, block_tables,
                                        start=start, prefix=prefix,
                                        layer=layer)
    return out, k_pool, v_pool


def scatter_new_kv_ref(k_new, v_new, k_pool, v_pool, lengths, block_tables,
                       start: Optional[jnp.ndarray] = None, prefix: int = 0,
                       kv_scales=None, kv_dtype: Optional[str] = None,
                       layer=0):
    """Host-side new-token K/V scatter (the ``attn_kernel="off"`` write
    path, bit-exact with the pre-fusion ``prefill_slots`` epilogue).

    Left-compacts each row's token K/V — real tokens to offsets 0..len-1
    after the prefix — then scatters through the block table at cache
    positions ``start + i`` of pool layer ``layer``, as lane-dense
    (Hk*D) rows.  Junk-tail entries are redirected out of
    bounds and dropped so they cannot touch another row's blocks.

    With ``kv_scales`` + ``kv_dtype`` (SCLAD pool) the compacted rows are
    quantized (``models.kv_quant.quantize`` — per-row, path-independent,
    so compaction and quantization commute) and both payload and scales
    scatter through the same indices; returns the 4-tuple
    (k_pool, v_pool, k_scale, v_scale).
    """
    B, S = k_new.shape[0], k_new.shape[1]
    N, bs = k_pool.shape[1], k_pool.shape[2]
    T = block_tables.shape[1]
    P = S - prefix
    lengths = jnp.asarray(lengths, jnp.int32)
    pad = (P - lengths).astype(jnp.int32)
    start_v = jnp.zeros((B,), jnp.int32) if start is None \
        else jnp.asarray(start, jnp.int32)
    kvd = k_pool.dtype

    roll_idx = (jnp.arange(P)[None] + pad[:, None]) % P  # (B, P)

    def compact(kv):  # (B, S, Hk, D), token part rolled left
        head, tail = kv[:, :prefix], kv[:, prefix:]
        tail = jnp.take_along_axis(tail, roll_idx[:, :, None, None], axis=1)
        return jnp.concatenate([head, tail], axis=1) if prefix else tail

    dest = start_v[:, None] + jnp.arange(S)[None]  # (B, S) cache positions
    blk_idx = jnp.minimum(dest // bs, T - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # (B, S)
    writable = jnp.arange(S)[None] < prefix + lengths[:, None]
    blk = jnp.where(writable, blk, N)  # junk -> out of bounds -> dropped
    off = dest % bs
    if kv_scales is not None:
        from repro.models import kv_quant
        k_scale, v_scale = kv_scales
        kq, ks1 = kv_quant.quantize(compact(k_new), kv_dtype)
        vq, vs1 = kv_quant.quantize(compact(v_new), kv_dtype)
        k_pool = k_pool.at[layer, blk, off].set(kq.reshape(B, S, -1),
                                                mode="drop")
        v_pool = v_pool.at[layer, blk, off].set(vq.reshape(B, S, -1),
                                                mode="drop")
        k_scale = k_scale.at[layer, blk, off].set(ks1, mode="drop")
        v_scale = v_scale.at[layer, blk, off].set(vs1, mode="drop")
        return k_pool, v_pool, k_scale, v_scale
    k_pool = k_pool.at[layer, blk, off].set(
        compact(k_new).reshape(B, S, -1).astype(kvd), mode="drop")
    v_pool = v_pool.at[layer, blk, off].set(
        compact(v_new).reshape(B, S, -1).astype(kvd), mode="drop")
    return k_pool, v_pool
