"""Flash attention (prefill/train) Pallas TPU kernel.

Blockwise causal attention with online softmax.  The grid is
(batch * kv_heads * q_rep, num_q_blocks); each program streams the KV
sequence in VMEM-resident blocks, keeping the working set at
O(block_q * head_dim + block_q * block_k) — this is the CC-MEM insight
mapped to the TPU memory hierarchy: the hot operand (the KV block) lives in
fast memory and is never spilled.

Block shapes are MXU-aligned (multiples of 128 on the lane dim, 8+ on the
sublane dim).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                 sm_scale: float, seq_k: int):
    """One (batch-head, q-block) program: stream KV blocks, online softmax.

    q_ref: (block_q, d); k_ref/v_ref: (seq_k, d); o_ref: (block_q, d).
    """
    block_q, d = q_ref.shape
    q_blk = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale
    q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    num_kv = seq_k // block_k

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T  # (block_q, block_k)
        if causal:
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    if causal:
        # Skip fully-masked KV blocks: only blocks with start <= q_end run.
        upper = jax.lax.div((q_blk + 1) * block_q + block_k - 1, block_k)
        upper = jnp.minimum(upper, num_kv)
    else:
        upper = num_kv
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hk, D), H % Hk == 0 -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    rep = H // Hk
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk)
    sm_scale = 1.0 / math.sqrt(D)

    # Layout: programs over (B * H) with q/k/v transposed to head-major.
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hk, Sk, D)

    grid = (B * H, Sq // block_q)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_k=block_k, causal=causal,
                          sm_scale=sm_scale, seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, Sk, D), lambda h, i: (h // rep, 0, 0)),
            pl.BlockSpec((None, Sk, D), lambda h, i: (h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
