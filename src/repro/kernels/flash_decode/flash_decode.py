"""Flash GQA decode Pallas kernels: one new token against a long KV cache.

Decode is the workload the paper prices (TCO per *generated* token) and is
purely memory-bound: per token, the kernel streams the KV cache once — the
CC-MEM contract (PAPER.md §CC-MEM).  Two cache layouts share the math:

  * ``flash_decode``       — contiguous (B, S, Hk, D) caches.  The grid is
    (batch, kv_heads); each program holds the ``rep`` query heads that share
    one KV head in VMEM and streams that head's K/V in ``block_k`` tiles
    with online softmax.  ``lengths`` is per-row: rows of a continuous
    batch sit at different sequence offsets.
  * ``paged_flash_decode`` — the serving engine's block pool: one
    lane-dense (L, N, bs, Hk*D) buffer for every layer, addressed through
    per-lane block tables (``serving.paged.BlockStore``) and a layer
    index.  The grid is (batch, table_width), one program per pool block
    with all its kv heads (the block's last two dims, (bs, Hk*D), are
    whole; head h is the lane range [h*D, (h+1)*D)), and the block table
    and the layer index ride the scalar-prefetch channel
    (``PrefetchScalarGridSpec``): the index map resolves
    ``(layer, tables[b, i])`` BEFORE the program body runs, so each
    program's K/V block is DMA'd straight from the shared stacked pool —
    no per-lane gather and no per-layer slice of the pool is ever
    materialized.  The online-softmax accumulator lives in VMEM scratch
    and persists across the (sequential, innermost) block dimension of
    the grid; blocks at or beyond a row's length are skipped, and the
    trash blocks dead lanes' tables point at are naturally masked by
    ``lengths``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pv(p, v):
    """probs @ V on the MXU with an fp32 accumulator (the TPU lowering
    requires 32-bit accumulation).  Operands are cast to the cache dtype,
    except f8 (too coarse for probabilities), which is computed in bf16."""
    dt = jnp.bfloat16 if v.dtype == jnp.float8_e4m3fn else v.dtype
    return jnp.dot(p.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                   sm_scale: float):
    """q_ref: (rep, D); k_ref/v_ref: (S, D); len_ref: (B,) in SMEM."""
    rep, D = q_ref.shape
    S = k_ref.shape[0]
    length = len_ref[pl.program_id(0)]
    q = q_ref[...].astype(jnp.float32) * sm_scale

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T  # (rep, block_k)
        pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + _pv(p, v)
        return acc, m_new, l

    # Only blocks below `length` contribute.
    upper = jnp.minimum(jax.lax.div(length + block_k - 1, block_k),
                        S // block_k)
    acc0 = jnp.zeros((rep, D), jnp.float32)
    m0 = jnp.full((rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, *, block_k: int = 128,
                 interpret: bool = False):
    """q: (B, H, D); k_cache/v_cache: (B, S, Hk, D); lengths: scalar int32
    or a per-row (B,) int32 vector (number of valid cache positions per
    row).  Returns (B, H, D)."""
    B, H, D = q.shape
    S, Hk = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hk
    assert S % block_k == 0
    sm_scale = 1.0 / math.sqrt(D)

    qt = q.reshape(B, Hk, rep, D)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, Hk, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    lens = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))

    grid = (B, Hk)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, rep, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, D), q.dtype),
        interpret=interpret,
    )(lens, qt, kt, vt)
    return out.reshape(B, H, D)


def _head_col(x, h: int):
    """Column ``h`` of a (rows, Hk) tile as (rows, 1), by a lane select and
    reduce (exact: one non-zero term) rather than a lane slice."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == h, x, 0.0), axis=1, keepdims=True)


def _paged_decode_kernel(lens_ref, tbl_ref, lyr_ref, q_ref, k_ref, v_ref,
                         *rest, bs: int, block_k: int, sm_scale: float,
                         quantized: bool):
    """One program = one pool block of one row, all of its KV heads.

    lens_ref (B,) / tbl_ref (B, T) / lyr_ref (1,): scalar-prefetch SMEM
    (the table and the layer index also drive the K/V index maps); q_ref
    (Hk, rep, D); k_ref/v_ref (bs, Hk*D): THIS grid step's pool block,
    already resolved through (layer, table); o_ref (Hk, rep, D).
    acc/m/l: VMEM scratch carrying each head's online softmax across the
    T (innermost, sequential) grid dimension.

    The block is lane-dense: head h is the lane range [h*D, (h+1)*D) of
    the block's (bs, Hk*D) tile, whose two dims are whole (the TPU
    lowering needs a block's last two dims (8, 128)-aligned or whole).
    Heads are a static loop in VMEM.

    ``quantized`` (SCLAD pool): two extra (bs, Hk) fp32 refs ks/vs carry the
    block's per-position-per-head scales (resolved through the SAME table
    walk), and the load path expands payload * scale in fp32 before the
    usual math — compressed bytes are all that crosses HBM; compute sees
    dense values.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b, i = pl.program_id(0), pl.program_id(1)
    T = pl.num_programs(1)
    Hk, D = q_ref.shape[0], q_ref.shape[2]
    length = lens_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Blocks wholly at/beyond the row's length are dead: skip their compute
    # (their table entries point at the trash block for unallocated tails).
    @pl.when(i * bs < length)
    def _block():
        for h in range(Hk):
            q = q_ref[h].astype(jnp.float32) * sm_scale  # (rep, D)
            for s0 in range(0, bs, block_k):  # static sub-tiling
                k = k_ref[s0:s0 + block_k, h * D:(h + 1) * D]
                v = v_ref[s0:s0 + block_k, h * D:(h + 1) * D]
                if quantized:
                    # Load-as-Dense: (bs', D) payload * (bs', 1) scale in
                    # fp32, then ROUNDED to the compute dtype — the exact
                    # cast chain of ``kv_quant.dequantize(..., q.dtype)``
                    # in the jnp reference, so both implementations score
                    # bitwise-equal dense values.
                    k = (k.astype(jnp.float32) * _head_col(
                        ks_ref[s0:s0 + block_k, :], h)).astype(q_ref.dtype)
                    v = (v.astype(jnp.float32) * _head_col(
                        vs_ref[s0:s0 + block_k, :], h)).astype(q_ref.dtype)
                s = q @ k.astype(jnp.float32).T  # (rep, block_k)
                pos = i * bs + s0 + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1)
                s = jnp.where(pos < length, s, NEG_INF)
                m = m_ref[h]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_ref[h] = l_ref[h] * corr \
                    + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + _pv(p, v)
                m_ref[h] = m_new

    @pl.when(i == T - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_flash_decode(q, k_pool, v_pool, lengths, block_tables, layer=0, *,
                       block_k: int = 0, interpret: bool = False,
                       kv_scales=None):
    """Decode attention straight out of the paged KV block pool.

    q:            (B, H, D) — one new token per row;
    k_pool/v_pool:(L, N, bs, Hk*D) — the SHARED lane-dense block pool of
                  every layer (``model.init_paged_cache`` layout, trash
                  block included);
    lengths:      (B,) int32 — valid cache positions per row (dead lanes'
                  lengths only cover trash blocks, so their output is
                  garbage that the caller's active mask discards);
    block_tables: (B, T) int32 — per-lane table mapping block index
                  ``j`` to the pool block holding positions
                  [j*bs, (j+1)*bs); unallocated entries point at the trash
                  block and are masked by ``lengths``.
    layer:        int32 scalar — the layer whose slab of the pool is read
                  (scalar-prefetched into the index maps: the pool is
                  never sliced per layer).
    block_k:      inner tile over a block's token dim (<= bs; 0 => whole
                  block per step).  Rounded down to a divisor of ``bs`` so
                  a caller tuned for the dense kernel's 128 can pass the
                  same value against any pool block size.
    kv_scales:    optional (k_scale, v_scale) (L, N, bs, Hk) fp32 — the
                  SCLAD quantized pool's per-position-per-head scales.
                  They ride the same table-walk BlockSpecs as the payload
                  (one (bs, Hk) scale tile per program) and the dequant
                  multiply is fused into the block-streaming loop in VMEM.

    Returns (B, H, D).  KV bytes are read exactly once per token, block by
    block through the table — never gathered into a per-lane dense copy.
    """
    B, H, D = q.shape
    bs = k_pool.shape[2]
    Hk = k_pool.shape[3] // D
    T = block_tables.shape[1]
    rep = H // Hk
    bk = bs if block_k <= 0 else min(block_k, bs)
    while bs % bk:
        bk -= 1
    sm_scale = 1.0 / math.sqrt(D)
    qt = q.reshape(B, Hk, rep, D)
    quantized = kv_scales is not None

    # The pool is indexed THROUGH the prefetched layer and table: each
    # grid step DMAs exactly one shared block (all kv heads) of one row.
    def pool_idx(b, i, lens, tbl, lyr):
        return lyr[0], tbl[b, i], 0, 0

    pool_blk = pl.BlockSpec((None, None, bs, Hk * D), pool_idx)
    row_blk = pl.BlockSpec((None, Hk, rep, D),
                           lambda b, i, lens, tbl, lyr: (b, 0, 0, 0))
    in_specs = [row_blk, pool_blk, pool_blk]
    inputs = [jnp.asarray(lengths, jnp.int32),
              jnp.asarray(block_tables, jnp.int32),
              jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
              qt, k_pool, v_pool]
    if quantized:
        scale_blk = pl.BlockSpec((None, None, bs, Hk), pool_idx)
        in_specs += [scale_blk, scale_blk]
        inputs += [s.astype(jnp.float32) for s in kv_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # lengths, block_tables, layer
        grid=(B, T),
        in_specs=in_specs,
        out_specs=row_blk,
        scratch_shapes=[
            pltpu.VMEM((Hk, rep, D), jnp.float32),  # acc
            pltpu.VMEM((Hk, rep, 1), jnp.float32),  # running max
            pltpu.VMEM((Hk, rep, 1), jnp.float32),  # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, block_k=bk,
                          sm_scale=sm_scale, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, D), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(*inputs)
    return out.reshape(B, H, D)
