"""Paged flash-prefill Pallas kernel: chunked-prefill attention straight
out of the block-pool KV cache, with the new-token K/V scatter fused in.

This is the prefill-side twin of ``kernels.flash_decode.paged_flash_decode``
and removes the last dense gather from the serving engine's hot path.  The
chunked-prefill continuation step — the path every prefix-cache hit,
long-prompt chunk and preemption recompute takes — previously materialized,
PER LAYER, a dense per-lane copy of the shared KV pool
(``k_pool[block_tables]``: O(B*T*bs*Hk*D) bytes) plus a host-built dense
(B, S, S+T*bs) mask, then round-tripped the chunk's compacted K/V through
HBM again as a separate ``.at[].set`` scatter.  Here instead:

  * the grid is (batch, T_read + W), one program per step taking every
    kv head of its block: the first ``T_read`` steps
    walk the lane's block table on the scalar-prefetch channel
    (``PrefetchScalarGridSpec`` — the index map resolves
    ``(layer, tbl[b, i])`` BEFORE the body runs), streaming cached context
    K/V block by block straight out of the shared lane-dense
    (L, N, bs, Hk*D) pool of every layer with online softmax in VMEM
    scratch (CC-MEM: each cached KV byte crosses HBM exactly once; the
    pool is never sliced per layer);
  * the causal/left-pad mask is derived INSIDE the kernel from the
    ``start``/``lengths`` scalars and the static ``prefix`` — no dense
    (B, S, S) mask is ever built;
  * step ``T_read`` adds the in-chunk self-attention (keys = this chunk's
    K, masked causally with pad keys dropped), fusing what used to be the
    concatenated tail of the dense mask;
  * the last ``W`` steps SCATTER the chunk's new-token K/V into the pool
    through the table (``input_output_aliases`` pins the whole stacked
    pool in place; only the layer's written blocks change):
    each step merges one destination block — old rows kept, new rows
    one contiguous slice of the chunk that folds the left-pad compaction
    (dest ``start + j`` reads padded row ``j + pad``) — so compacted K/V
    never round-trips through HBM as a separate scatter.

Write-target blocks are exclusive to their lane (the engine's grow +
copy-on-write barrier runs before prefill), so the in-place pool update
can never be observed by a concurrently-read shared block; steps whose
block index clamps past the row's real write span re-merge identical
content (idempotent) or copy the old block through unchanged.

The tests run the kernel in Pallas interpret mode on the CPU and compile
it for a v5e (``tests/test_tpu_compile.py``); ``chip_smoke.py`` checks
its output and its pool writes against the jnp reference on the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode.flash_decode import _head_col, _pv

NEG_INF = -1e30


def _prefill_kernel(len_ref, start_ref, tbl_ref, lyr_ref, q_ref, kn_ref,
                    vn_ref, kp_ref, vp_ref, *rest, bs: int, prefix: int,
                    t_read: int, sm_scale: float, kv_dtype=None):
    """One program = one grid step of one row, all of its KV heads.

    len/start (B,), tbl (B, T) and lyr (1,): scalar-prefetch SMEM (the
    table and the layer index also drive the pool index maps); q_ref
    (Hk, S*rep, D); kn/vn_ref (bs + S + bs, Hk, D): the chunk's rotated
    K/V between ``bs`` zero rows on each side (rows on a leading dim, so a
    block's new rows are a dynamic slice at any offset); kp/vp_ref
    (bs, Hk*D): this step's pool block resolved through (layer, table) —
    cached context on read steps, the scatter destination's old content
    on write steps; o_ref (Hk, S*rep, D); ko/vo_ref (bs, Hk*D): the
    (aliased) pool block being written back.  acc/m/l: VMEM scratch
    carrying each head's online softmax across the (innermost,
    sequential) grid dimension.  Heads are a static loop over lane ranges
    [h*D, (h+1)*D): a pool block carries all of them because the TPU
    lowering needs a block's last two dims (bs, Hk*D) whole.

    ``kv_dtype`` ("int8"/"fp8"; None = fp pool) switches on the SCLAD
    layout: ksp/vsp_ref and kso/vso_ref carry the (bs, Hk) per-position
    scale tiles riding the same table walk.  Context loads expand
    payload * scale in fp32; the chunk phase fake-quantizes its own K/V
    (matching what the scatter will store, so in-chunk and from-pool
    scoring agree); the scatter phase reproduces
    ``models.kv_quant.quantize`` operation-for-operation so pool bytes are
    bitwise identical to the host-side reference scatter.
    """
    if kv_dtype is not None:
        (ksp_ref, vsp_ref, o_ref, ko_ref, vo_ref, kso_ref, vso_ref,
         acc_ref, m_ref, l_ref) = rest
        qm = 127.0 if kv_dtype == "int8" else 448.0
    else:
        ksp_ref = vsp_ref = kso_ref = vso_ref = None
        o_ref, ko_ref, vo_ref, acc_ref, m_ref, l_ref = rest
        qm = None
    b, i = pl.program_id(0), pl.program_id(1)
    n_i = pl.num_programs(1)
    T = tbl_ref.shape[1]
    Hk, D = kn_ref.shape[1:]
    S = kn_ref.shape[0] - 2 * bs
    rows = q_ref.shape[1]
    rep = rows // S
    P = S - prefix
    length = len_ref[b]
    start = start_ref[b]
    pad = P - length

    def scale_of(x):  # kv_quant.quantize's scale, op-for-op: (rows, 1)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        # Constant multiply, not division — matches kv_quant.quantize
        # bitwise in every tracing context (XLA rewrites /const under jit).
        return jnp.where(amax > 0, amax * (1.0 / qm), 1.0)

    def quantize(x):
        """fp32 (rows, D) -> ``kv_quant.quantize``'s payload values, in
        fp32 (exact for int8 — round() already yields the representable
        integral grid — and an actual f8 cast for fp8), and scales."""
        scale = scale_of(x)
        qv = x / scale
        if kv_dtype == "int8":
            qv = jnp.round(qv)
        else:
            qv = qv.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return qv, scale

    def fake_quant(x):
        """fp32 (rows, D) -> the value a pool reader will observe: the
        round-trip of ``kv_quant.quantize``/``dequantize`` without the
        payload-dtype container."""
        qv, scale = quantize(x)
        return qv * scale

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def online_update(h, s, v):
        """Fold head h's scores s (rows, K) and values v (K, D) into
        acc/m/l."""
        m = m_ref[h]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + _pv(p, v)
        m_ref[h] = m_new

    # Context phase: blocks wholly at/beyond the row's cached length are
    # dead (their table entries point at the trash block); context is
    # query-independent — every cached position < start is visible to the
    # whole chunk (all chunk positions are causally after it).
    @pl.when((i < t_read) & (i * bs < start))
    def _ctx():
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        for h in range(Hk):
            q = q_ref[h].astype(jnp.float32) * sm_scale
            k = kp_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            v = vp_ref[:, h * D:(h + 1) * D]
            if kv_dtype is not None:
                # Load-as-Dense: (bs, D) payload * (bs, 1) scale in fp32,
                # then ROUNDED to the compute dtype — the reference's
                # ``kv_quant.dequantize(..., q.dtype)`` cast chain, so both
                # implementations attend to bitwise-equal dense values.
                k = (k * _head_col(ksp_ref[...], h)).astype(q_ref.dtype) \
                    .astype(jnp.float32)
                v = (v.astype(jnp.float32) * _head_col(vsp_ref[...], h)
                     ).astype(q_ref.dtype)
            s = q @ k.T  # (rows, bs)
            online_update(h, jnp.where(pos < start, s, NEG_INF), v)

    # In-chunk self-attention: causal over this call's tokens with pad
    # keys dropped — the mask the pre-kernel path materialized densely,
    # rebuilt here from iota against the start/length scalars.
    @pl.when(i == t_read)
    def _chunk():
        qpos = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // rep
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        real = (kpos < prefix) | (kpos >= prefix + pad)
        visible = (kpos <= qpos) & real
        for h in range(Hk):
            q = q_ref[h].astype(jnp.float32) * sm_scale
            k = kn_ref[bs:bs + S, h, :].astype(jnp.float32)
            v = vn_ref[bs:bs + S, h, :]
            if kv_dtype is not None:
                # Attend to the chunk's K/V as quantized — identical to how
                # a later chunk / decode step reads it back from the pool.
                # The compute-dtype round-trip matches
                # ``kv_quant.fake_quant`` (which returns x.dtype) bitwise.
                k = fake_quant(k).astype(kn_ref.dtype).astype(jnp.float32)
                v = fake_quant(v.astype(jnp.float32)).astype(vn_ref.dtype)
            s = q @ k.T  # (rows, S)
            online_update(h, jnp.where(visible, s, NEG_INF), v)

    # Scatter phase: merge one destination block.  Offset o holds cache
    # position w*bs + o = start + j; compacted index j maps back to padded
    # source row j (vlm prefix) or j + pad (prompt tokens) — for each of
    # the two pieces a CONTIGUOUS run of chunk rows, so a head's new rows
    # are one dynamic leading-dim slice of the chunk's K/V (an exact copy)
    # written to the head's lane range of the block.  The chunk carries
    # ``bs`` zero rows on each side, so every slice start clamps into
    # bounds without moving a valid row.
    @pl.when(i >= t_read)
    def _scatter():
        w = jnp.minimum(start // bs + (i - t_read), T - 1)
        base = w * bs - start  # chunk row of offset 0, before the pad
        o = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        j = w * bs + o - start
        valid = (j >= 0) & (j < prefix + length)

        def new_rows(ref, h):
            # Head h's new rows of the block: (bs, D).
            def take(off):
                return ref[pl.ds(jnp.clip(off + bs, 0, S + bs), bs), h, :]
            new = take(base + pad)
            if prefix:
                new = jnp.where(j < prefix, take(base), new)
            return new.astype(jnp.float32)

        def merge(ref, out_ref, old_ref, sc_out, sc_old):
            if kv_dtype is not None:
                head = jax.lax.broadcasted_iota(jnp.int32, (bs, Hk), 1)
                sc = sc_old[...]
            for h in range(Hk):
                lanes = slice(h * D, (h + 1) * D)
                new = new_rows(ref, h)
                old = old_ref[:, lanes]
                if kv_dtype is None:
                    out_ref[:, lanes] = jnp.where(
                        valid, new.astype(out_ref.dtype), old)
                    continue
                # kv_quant.quantize, op-for-op, from the exact fp32 view
                # of the chunk's rows: payload and scales match the
                # reference scatter bitwise.  Invalid rows quantize
                # garbage, but the merge passes the OLD payload and scale
                # through.  The merge runs in fp32 and the payload is cast
                # once after it: with the select on the int8 cast's output
                # the chip let an invalid row's +127 through into a kept
                # row.
                new, scale = quantize(new)
                sc = jnp.where(valid & (head == h), scale, sc)
                out_ref[:, lanes] = jnp.where(
                    valid, new, old.astype(jnp.float32)).astype(out_ref.dtype)
            if kv_dtype is not None:
                sc_out[...] = sc

        merge(kn_ref, ko_ref, kp_ref, kso_ref, ksp_ref)
        merge(vn_ref, vo_ref, vp_ref, vso_ref, vsp_ref)

    @pl.when(i == n_i - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("prefix", "has_ctx", "interpret",
                                    "kv_dtype"))
def paged_flash_prefill(q, k_new, v_new, k_pool, v_pool, lengths,
                        block_tables, start, layer=0, *, prefix: int = 0,
                        has_ctx: bool = True, interpret: bool = False,
                        kv_scales=None, kv_dtype=None):
    """Chunked-prefill attention + fused K/V scatter on the paged pool.

    q:             (B, S, H, D) rotated chunk queries (S = prefix + P,
                   prompt tokens LEFT-padded to P);
    k_new/v_new:   (B, S, Hk, D) the chunk's rotated K/V (compute dtype);
    k_pool/v_pool: (L, N, bs, Hk*D) the SHARED lane-dense block pool of
                   every layer (``model.init_paged_cache`` layout, trash
                   block included) — updated in place via
                   ``input_output_aliases``;
    lengths:       (B,) int32 true chunk token count per row (<= P);
    block_tables:  (B, T) int32 per-lane tables (unallocated entries point
                   at the trash block);
    start:         (B,) int32 cache positions already filled per row;
    layer:         int32 scalar — the layer whose slab is read and
                   written (scalar-prefetched into the index maps; every
                   other layer's bytes pass through untouched);
    prefix:        static vlm patch-prefix length (first chunk only);
    has_ctx:       static — False for first chunks (start == 0 rows): the
                   table-walk read phase is dropped from the grid;
    kv_scales:     optional (k_scale, v_scale) (L, N, bs, Hk) fp32 scales
                   of a SCLAD quantized pool, with static ``kv_dtype``
                   ("int8"/"fp8") naming the payload encoding.  The scales
                   ride the same table-walked BlockSpecs as the payload
                   (one (bs, Hk) tile per step) and are
                   aliased in place alongside it; the chunk's new K/V is
                   QUANTIZED IN-KERNEL before the write-back, so compressed
                   bytes are the only thing that round-trips HBM.

    Returns (attn_out (B, S, H*D), k_pool', v_pool') — plus
    (k_scale', v_scale') for quantized pools.  Cached KV bytes are read
    exactly once per chunk, block by block through the table — never
    gathered into a per-lane dense copy — and the new K/V lands in the
    pool inside the same kernel invocation.
    """
    B, S, H, D = q.shape
    Hk = k_new.shape[2]
    rep = H // Hk
    bs = k_pool.shape[2]
    T = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(D)
    quantized = kv_scales is not None
    assert quantized == (kv_dtype is not None)

    qt = q.reshape(B, S, Hk, rep, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hk, S * rep, D)
    # (B, bs + S + bs, Hk, D): zero rows either side of the chunk (see
    # the scatter phase).
    edge = ((0, 0), (bs, bs), (0, 0), (0, 0))
    knt = jnp.pad(k_new, edge)
    vnt = jnp.pad(v_new, edge)

    # Writes span <= ceil(S/bs)+1 blocks (the +1 absorbs a start%bs
    # straddle); steps clamped past the table end re-merge idempotently.
    t_read = T if has_ctx else 0
    w_steps = min(T, -(-S // bs) + 1)
    grid = (B, t_read + w_steps)

    def pool_read_blk(b, i, lens, starts, tbl, lyr):
        wr = jnp.minimum(starts[b] // bs + (i - t_read), T - 1)
        idx = jnp.where(i < t_read, jnp.minimum(i, T - 1), wr)
        return lyr[0], tbl[b, idx], 0, 0

    def pool_write_blk(b, i, lens, starts, tbl, lyr):
        # Parked on the FIRST write block during the read phase so the
        # (unwritten) output buffer is never flushed over a context block.
        j = jnp.maximum(i - t_read, 0)
        return lyr[0], tbl[b, jnp.minimum(starts[b] // bs + j, T - 1)], 0, 0

    def row(b, i, lens, starts, tbl, lyr):
        return b, 0, 0, 0

    # A pool block carries every KV head and a scale block its (bs, Hk)
    # tile: the TPU lowering accepts a block whose last two dims are whole.
    q_blk = pl.BlockSpec((None, Hk, S * rep, D), row)
    seq_blk = pl.BlockSpec((None, S + 2 * bs, Hk, D), row)
    pool_rd = pl.BlockSpec((None, None, bs, Hk * D), pool_read_blk)
    pool_wr = pl.BlockSpec((None, None, bs, Hk * D), pool_write_blk)
    scale_rd = pl.BlockSpec((None, None, bs, Hk), pool_read_blk)
    scale_wr = pl.BlockSpec((None, None, bs, Hk), pool_write_blk)

    in_specs = [q_blk, seq_blk, seq_blk, pool_rd, pool_rd]
    out_specs = [q_blk, pool_wr, pool_wr]
    out_shape = [
        jax.ShapeDtypeStruct((B, Hk, S * rep, D), q.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    inputs = [jnp.asarray(lengths, jnp.int32), jnp.asarray(start, jnp.int32),
              jnp.asarray(block_tables, jnp.int32),
              jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qt, knt, vnt,
              k_pool, v_pool]
    # Flat input indices (scalar-prefetch leaves included): pools are
    # inputs 7/8 -> outputs 1/2 (and scales 9/10 -> 3/4 when quantized),
    # so every pool update happens in place.
    aliases = {7: 1, 8: 2}
    if quantized:
        k_scale, v_scale = (x.astype(jnp.float32) for x in kv_scales)
        in_specs += [scale_rd, scale_rd]
        out_specs += [scale_wr, scale_wr]
        out_shape += [jax.ShapeDtypeStruct(k_scale.shape, jnp.float32),
                      jax.ShapeDtypeStruct(v_scale.shape, jnp.float32)]
        inputs += [k_scale, v_scale]
        aliases = {7: 1, 8: 2, 9: 3, 10: 4}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # lengths, start, block_tables, layer
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hk, S * rep, D), jnp.float32),  # acc
            pltpu.VMEM((Hk, S * rep, 1), jnp.float32),  # running max
            pltpu.VMEM((Hk, S * rep, 1), jnp.float32),  # running denom
        ],
    )
    results = pl.pallas_call(
        functools.partial(_prefill_kernel, bs=bs, prefix=prefix,
                          t_read=t_read, sm_scale=sm_scale,
                          kv_dtype=kv_dtype),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="paged_flash_prefill",
    )(*inputs)
    out = results[0].reshape(B, Hk, S, rep, D).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, S, H * D)
    if quantized:
        return (out,) + tuple(results[1:])
    _, k_pool, v_pool = results
    return out, k_pool, v_pool
