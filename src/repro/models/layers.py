"""Core transformer layers: norms, rotary embeddings, GQA attention, MLPs.

Pure functions over explicit parameter pytrees (no flax).  All layers take a
``ModelConfig`` and operate in bf16 with fp32 accumulation where it matters
(norm statistics, softmax, loss).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Dict[str, jnp.ndarray]

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=DTYPE):
    """Scaled normal init (fan-in)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=DTYPE):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), DTYPE)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), DTYPE)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_gated(scale: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """Mamba2 gated RMSNorm: norm(x * silu(z))."""
    xf = (x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)).astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + 1e-6) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (partial rotary supported, stablelm2 style)
# ---------------------------------------------------------------------------

def rope_frequencies(cfg: ModelConfig) -> Tuple[int, jnp.ndarray]:
    """Returns (rotary_dim, inv_freq[rotary_dim//2])."""
    rot = int(cfg.head_dim * cfg.rope_fraction)
    rot -= rot % 2
    if cfg.rope_theta <= 0 or rot == 0:
        return 0, jnp.zeros((0,), jnp.float32)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    return rot, jnp.asarray(inv)


def apply_rope(cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    rot, inv = rope_frequencies(cfg)
    if rot == 0:
        return x
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, rot/2)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def sinusoidal_positions(seq_len: int, d: int) -> jnp.ndarray:
    """Whisper-style absolute sinusoidal embeddings (S, d)."""
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    dim = np.arange(d // 2, dtype=np.float32)[None, :]
    ang = pos / np.power(10_000.0, 2 * dim / d)
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), dtype=DTYPE
    )


# ---------------------------------------------------------------------------
# Attention (MHA / GQA / MQA; optional cross attention)
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> Params:
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd)),
        "wk": dense_init(ks[1], (d, hk * hd)),
        "wv": dense_init(ks[2], (d, hk * hd)),
        "wo": dense_init(ks[3], (h * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), DTYPE)
        p["bk"] = jnp.zeros((hk * hd,), DTYPE)
        p["bv"] = jnp.zeros((hk * hd,), DTYPE)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, xq: jnp.ndarray, xkv: jnp.ndarray):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Sq, h, hd)
    k = k.reshape(B, Skv, hk, hd)
    v = v.reshape(B, Skv, hk, hd)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask=None):
    """q: (B,Sq,H,D), k/v: (B,Skv,Hk,D). fp32 softmax."""
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    rep = H // Hk
    qg = q.reshape(B, Sq, Hk, rep, D)
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(B, Sq, H * D)


# Above this sequence length attention runs blockwise (flash-style online
# softmax) so peak memory is O(S * chunk) instead of O(S^2).
CHUNKED_ATTN_THRESHOLD = 2048
Q_CHUNK = 512
K_CHUNK = 1024


def chunked_attention(q, k, v, causal: bool, q_chunk=Q_CHUNK, k_chunk=K_CHUNK):
    """Blockwise attention with online softmax (pure jnp oracle of flash attn).

    q: (B, Sq, H, D); k, v: (B, Skv, Hk, D) with H % Hk == 0.
    Memory-bounded: never materializes the (Sq, Skv) score matrix.
    """
    B, Sq, H, D = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    rep = H // Hk
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Skv)
    while Sq % q_chunk:
        q_chunk //= 2
    while Skv % k_chunk:
        k_chunk //= 2
    assert q_chunk >= 1 and k_chunk >= 1
    nq, nk = Sq // q_chunk, Skv // k_chunk
    scale = 1.0 / math.sqrt(D)

    # (nq, B, qc, Hk, rep, D)
    qc = q.reshape(B, nq, q_chunk, Hk, rep, D).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, k_chunk, Hk, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, Hk, D).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(Sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Skv).reshape(nk, k_chunk)

    @jax.checkpoint
    def kv_step(carry, xs):
        acc, m, denom, qi, qp = carry
        ki, vi, kp = xs
        s = jnp.einsum("bqhrd,bkhd->bqhrk", qi, ki).astype(jnp.float32) * scale
        if causal:
            mask = qp[:, None] >= kp[None, :]  # (qc, kc)
            s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhrk,bkhd->bqhrd", p.astype(qi.dtype), vi
        ).astype(jnp.float32)
        return (acc, m_new, denom, qi, qp), None

    def q_block(args):
        qi, qp = args
        acc0 = jnp.zeros((B, q_chunk, Hk, rep, D), jnp.float32)
        m0 = jnp.full((B, q_chunk, Hk, rep), -jnp.inf, jnp.float32)
        d0 = jnp.zeros((B, q_chunk, Hk, rep), jnp.float32)
        (acc, _, denom, _, _), _ = jax.lax.scan(
            kv_step, (acc0, m0, d0, qi, qp), (kc, vc, k_pos)
        )
        return (acc / jnp.maximum(denom, 1e-30)[..., None]).astype(q.dtype)

    out = jax.lax.map(q_block, (qc, q_pos))  # (nq, B, qc, Hk, rep, D)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H * D)
    return out


def attention(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    causal: bool = True,
    use_rope: bool = True,
) -> jnp.ndarray:
    """Full self-attention over x (B, S, d)."""
    q, k, v = _project_qkv(cfg, p, x, x)
    if use_rope:
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, positions)
    from repro.parallel import sharding as _sh
    q = _sh.constrain_heads(q)
    S = x.shape[1]
    if S >= CHUNKED_ATTN_THRESHOLD and S % Q_CHUNK == 0:
        out = chunked_attention(q, k, v, causal)
    else:
        mask = None
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))[None, None, None]
        out = _sdpa(cfg, q, k, v, mask)
    return out @ p["wo"]


def cross_attention(cfg: ModelConfig, p: Params, x: jnp.ndarray, ctx_k, ctx_v):
    """x: (B,Sq,d); ctx_k/ctx_v: precomputed (B,Skv,Hk,D)."""
    B, Sq, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, Sq, h, hd)
    out = _sdpa(cfg, q, ctx_k, ctx_v, mask=None)
    return out @ p["wo"]


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    position: jnp.ndarray,
    use_rope: bool = True,
    block_tables: Optional[jnp.ndarray] = None,
    kv_scales: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    mesh=None,
    layer=0,
):
    """Single-token decode with in-place cache update.

    x: (B, 1, d); position: scalar int OR a per-row (B,) int vector — rows
    of a batch may sit at different sequence offsets (continuous batching).
    The new K/V is scattered into each row's own cache index, then the
    attention READ dispatches through the single
    ``kernels.flash_decode.ops.decode_attention`` entry point (per-row
    lengths = position + 1), selected by ``cfg.attn_kernel``: the Pallas
    flash-decode kernel on TPU (interpret mode when forced on elsewhere) or
    the jnp reference.

    Two cache layouts:
      * dense (block_tables=None): k_cache/v_cache are (B, S_max, Hk, D)
        slot stripes, row b's position j lives at [b, j];
      * paged: k_cache/v_cache are the lane-dense (L, N, bs, Hk*D) pools
        of fixed-size token blocks of EVERY layer, shared by all rows;
        ``layer`` (int32 scalar) names this layer's slab and
        ``block_tables`` (B, T) int32 maps row b's block index j//bs to a
        pool block (serving.paged hands these out; unallocated entries
        point at the trash block).  The new K/V is written in place as one
        (Hk*D) row at ``[layer, block, pos % bs]`` and the kernel walks
        each row's blocks through (layer, table) directly out of the
        shared pool — neither a per-layer slice nor a dense per-lane copy
        of the pool is materialized on this path (the ``"off"`` fallback
        gathers, as the pre-kernel engine did).  With
        prefix caching, SEVERAL rows' tables may name the same
        (ref-counted) block: concurrent reads are safe because the
        host-side store guarantees the scattered write position always
        lands in a block exclusive to its row (fresh growth or
        copy-on-write — ``BlockStore.ensure_writable``).

    kv_scales: (k_scale, v_scale) (L, N, bs, Hk) fp32 leaves of a SCLAD
    quantized pool (paged layout only, ``cfg.kv_dtype`` in "int8"/"fp8").
    When given, the new token's K/V is quantized (``models.kv_quant``) and
    the payload + per-head scales scattered through the table; readers
    dequantize on load.  The quantized write runs here in jnp for BOTH
    ``attn_kernel`` read paths, so the pool bytes a decode step leaves
    behind are identical whichever kernel serves the read.

    mesh: threaded to ``decode_attention`` on the paged branches — when it
    carries a nontrivial ``model`` axis that divides Hk, the pool read
    runs shard_mapped over the KV heads (the scatter above stays outside:
    a sharded pool's ``.at[].set`` is itself a local per-shard write).

    Returns (out (B,1,d), k_cache, v_cache) — plus (k_scale, v_scale)
    appended when ``kv_scales`` is given.
    """
    from repro.kernels.flash_decode import ops as decode_ops

    B = x.shape[0]
    q, k, v = _project_qkv(cfg, p, x, x)
    pos = jnp.broadcast_to(jnp.asarray(position, jnp.int32), (B,))
    if use_rope:
        q = apply_rope(cfg, q, pos[:, None])
        k = apply_rope(cfg, k, pos[:, None])
    lengths = pos + 1  # row b's valid cache positions, incl. the new token
    if block_tables is None:
        assert kv_scales is None, "kv_scales is a paged-pool layout"
        rows = jnp.arange(B)
        k_cache = k_cache.at[rows, pos].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, pos].set(v[:, 0].astype(v_cache.dtype))
        out = decode_ops.decode_attention(
            q[:, 0], k_cache.astype(x.dtype), v_cache.astype(x.dtype),
            lengths, kernel=cfg.attn_kernel)
    else:
        bs = k_cache.shape[2]
        rows = jnp.arange(B)
        # Dead lanes carry all-trash tables, so their writes land in the
        # trash block and cannot clobber a block re-assigned to a live lane
        # (their stale ``lengths`` only ever cover trash blocks, which the
        # caller's active mask keeps out of every live result).
        at = (layer, block_tables[rows, pos // bs], pos % bs)
        if kv_scales is not None:
            from repro.models import kv_quant
            k_scale, v_scale = kv_scales
            kq, ks1 = kv_quant.quantize(k[:, 0], cfg.kv_dtype)  # (B,Hk,D)/(B,Hk)
            vq, vs1 = kv_quant.quantize(v[:, 0], cfg.kv_dtype)
            k_cache = k_cache.at[at].set(kq.reshape(B, -1))
            v_cache = v_cache.at[at].set(vq.reshape(B, -1))
            k_scale = k_scale.at[at].set(ks1)
            v_scale = v_scale.at[at].set(vs1)
            out = decode_ops.decode_attention(
                q[:, 0], k_cache, v_cache, lengths,
                block_tables=block_tables, layer=layer,
                kernel=cfg.attn_kernel, kv_scales=(k_scale, v_scale),
                mesh=mesh)
            return (out.reshape(B, 1, -1).astype(x.dtype) @ p["wo"],
                    k_cache, v_cache, k_scale, v_scale)
        k_cache = k_cache.at[at].set(k[:, 0].reshape(B, -1)
                                     .astype(k_cache.dtype))
        v_cache = v_cache.at[at].set(v[:, 0].reshape(B, -1)
                                     .astype(v_cache.dtype))
        out = decode_ops.decode_attention(
            q[:, 0], k_cache, v_cache, lengths, block_tables=block_tables,
            layer=layer, kernel=cfg.attn_kernel, mesh=mesh)
    return out.reshape(B, 1, -1).astype(x.dtype) @ p["wo"], k_cache, v_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f)),
            "w_up": dense_init(ks[1], (d, f)),
            "w_down": dense_init(ks[2], (f, d)),
        }
    return {"w_up": dense_init(ks[0], (d, f)), "w_down": dense_init(ks[1], (f, d))}


def apply_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Dense decoder block (pre-norm)
# ---------------------------------------------------------------------------

def init_dense_block(cfg: ModelConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": init_norm(cfg),
        "attn": init_attention(cfg, k1),
        "ln_mlp": init_norm(cfg),
        "mlp": init_mlp(cfg, k2),
    }


def apply_dense_block(cfg: ModelConfig, p: Params, x, positions):
    x = x + attention(cfg, p["attn"], apply_norm(cfg, p["ln_attn"], x), positions)
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln_mlp"], x))
    return x


def apply_dense_block_decode(cfg: ModelConfig, p: Params, x, k_cache, v_cache, position):
    a, k_cache, v_cache = attention_decode(
        cfg, p["attn"], apply_norm(cfg, p["ln_attn"], x), k_cache, v_cache, position
    )
    x = x + a
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln_mlp"], x))
    return x, k_cache, v_cache
