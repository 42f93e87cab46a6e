"""Unified model: init / forward / loss / cache / decode for all families.

Families:
  dense   — GQA decoder-only transformer (tinyllama, stablelm, phi3, granite)
  moe     — dense attention + MoE FFN (qwen3-moe, qwen2-moe)
  ssm     — pure Mamba-2 stack (mamba2-1.3b)
  hybrid  — Mamba-2 backbone + shared attention block every N (zamba2)
  vlm     — dense LM backbone with stub patch-embedding prefix (internvl2)
  audio   — encoder-decoder with stub frame embeddings (whisper)

Layers are scanned (``lax.scan`` over stacked parameters) so the lowered HLO
is O(1) in depth — essential for the 94-layer dry-run compiles.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_prefill import ops as prefill_ops
from repro.models import kv_quant, layers, moe as moe_lib, ssm as ssm_lib
from repro.models.layers import DTYPE, embed_init
from repro.parallel import sharding

Params = Dict[str, Any]

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    per = cfg.hybrid.attn_every
    groups = cfg.num_layers // per
    tail = cfg.num_layers - groups * per
    return groups, per, tail


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model)),
        "final_norm": layers.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(keys[1], (cfg.d_model, cfg.vocab_size))

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["blocks"] = _stack_init(
            lambda k: layers.init_dense_block(cfg, k), keys[2], cfg.num_layers
        )
        if fam == "vlm":
            p["patch_proj"] = layers.dense_init(keys[3], (cfg.d_model, cfg.d_model))
    elif fam == "moe":
        def init_moe_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln_attn": layers.init_norm(cfg),
                "attn": layers.init_attention(cfg, k1),
                "ln_mlp": layers.init_norm(cfg),
                "moe": moe_lib.init_moe(cfg, k2),
            }
        p["blocks"] = _stack_init(init_moe_block, keys[2], cfg.num_layers)
    elif fam == "ssm":
        def init_ssm_block(k):
            return {
                "ln": layers.init_norm(cfg),
                "mamba": ssm_lib.init_mamba_block(cfg, k),
            }
        p["blocks"] = _stack_init(init_ssm_block, keys[2], cfg.num_layers)
    elif fam == "hybrid":
        groups, per, tail = _hybrid_layout(cfg)

        def init_ssm_block(k):
            return {
                "ln": layers.init_norm(cfg),
                "mamba": ssm_lib.init_mamba_block(cfg, k),
            }

        def init_group(k):
            return _stack_init(init_ssm_block, k, per)

        p["groups"] = _stack_init(init_group, keys[2], groups)
        if tail:
            p["tail"] = _stack_init(init_ssm_block, keys[3], tail)
        p["shared_attn"] = layers.init_dense_block(cfg, keys[4])
    elif fam == "audio":
        def init_enc_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln_attn": layers.init_norm(cfg),
                "attn": layers.init_attention(cfg, k1),
                "ln_mlp": layers.init_norm(cfg),
                "mlp": layers.init_mlp(cfg, k2),
            }

        def init_dec_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {
                "ln_self": layers.init_norm(cfg),
                "self_attn": layers.init_attention(cfg, k1),
                "ln_cross": layers.init_norm(cfg),
                "cross_attn": layers.init_attention(cfg, k2),
                "ln_mlp": layers.init_norm(cfg),
                "mlp": layers.init_mlp(cfg, k3),
            }

        p["enc_blocks"] = _stack_init(init_enc_block, keys[2],
                                      cfg.encdec.num_encoder_layers)
        p["enc_norm"] = layers.init_norm(cfg)
        p["dec_blocks"] = _stack_init(init_dec_block, keys[3], cfg.num_layers)
    else:
        raise ValueError(fam)
    return p


def param_specs(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_count(cfg: ModelConfig) -> int:
    import math
    specs = param_specs(cfg)
    return sum(math.prod(x.shape) if x.shape else 1
               for x in jax.tree.leaves(specs))


def param_count_active(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE experts scaled by top-k/E)."""
    import numpy as _np
    specs = param_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    total = 0.0
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "idx", "")) for p in path]
        n = float(_np.prod(leaf.shape)) if leaf.shape else 1.0
        if cfg.moe is not None and "moe" in keys and "shared" not in keys \
                and keys[-1] in ("w_gate", "w_up", "w_down"):
            n *= cfg.moe.num_experts_per_tok / cfg.moe.num_experts
        total += n
    return int(total)


# ---------------------------------------------------------------------------
# Forward (train / prefill) — full-sequence
# ---------------------------------------------------------------------------

def _pad_to_multiple(h: jnp.ndarray, mult: int):
    S = h.shape[1]
    pad = (-S) % mult
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    return h, S


def _maybe_remat(fn, remat: bool):
    return jax.checkpoint(fn) if remat else fn


def _scan_blocks(body, h, blocks, remat: bool):
    def wrapped(c, b):
        out, aux = body(c, b)
        return sharding.constrain_tokens(out), aux

    wrapped = _maybe_remat(wrapped, remat)
    h, aux = jax.lax.scan(wrapped, h, blocks)
    return h, aux


def _attn_qkv(cfg: ModelConfig, blk: Params, x: jnp.ndarray,
              positions: jnp.ndarray):
    """Shared attention-input stage: norm, QKV projection, RoPE, sharding
    anchor.  positions: (S,) or (B, S) rope positions.  Returns (q, k, v)
    in compute dtype — ONE definition, so every caller's K/V matches the
    cache contents bit-for-bit."""
    xn = layers.apply_norm(cfg, blk["ln_attn"], x)
    q, k, v = layers._project_qkv(cfg, blk["attn"], xn, xn)
    q = layers.apply_rope(cfg, q, positions)
    k = layers.apply_rope(cfg, k, positions)
    return sharding.constrain_heads(q), k, v


def _attn_post(cfg: ModelConfig, blk: Params, x: jnp.ndarray,
               a: jnp.ndarray, moe_valid: Optional[jnp.ndarray] = None):
    """Shared attention-output stage: residual + output projection, then
    the MLP/MoE half.  moe_valid: (B, S) bool routing-validity mask
    (pads/dead lanes consume no expert capacity; moe family only).
    Returns (x_out, aux)."""
    x = x + a @ blk["attn"]["wo"]
    if "moe" in blk:
        y, aux = moe_lib.apply_moe(
            cfg, blk["moe"], layers.apply_norm(cfg, blk["ln_mlp"], x),
            valid=moe_valid)
    else:
        y = layers.apply_mlp(cfg, blk["mlp"],
                             layers.apply_norm(cfg, blk["ln_mlp"], x))
        aux = 0.0
    return x + y, aux


def _attn_block_body(cfg: ModelConfig, blk: Params, x: jnp.ndarray,
                     positions: jnp.ndarray):
    """ONE per-layer block body for the attention families (dense/moe/vlm)
    over a plain causal window.

    ``backbone`` (train/full forward) and ``prefill`` (wave cache build)
    run this body; ``prefill_slots`` (paged chunked admission) shares its
    ``_attn_qkv``/``_attn_post`` stages but routes the attention core
    through ``kernels.flash_prefill.ops.prefill_attention`` (cached-context
    table walk + left-pad masking + fused K/V scatter), so the greedy
    bit-identity contract pinned by tests/test_continuous_batching.py holds
    across all three by construction.

    Returns (x_out, k, v, aux) with k/v of this call's tokens (compute
    dtype — callers cast to the cache storage dtype).
    """
    q, k, v = _attn_qkv(cfg, blk, x, positions)
    S = x.shape[1]
    if S >= layers.CHUNKED_ATTN_THRESHOLD and S % layers.Q_CHUNK == 0:
        a = layers.chunked_attention(q, k, v, causal=True)
    else:
        mask = jnp.tril(jnp.ones((S, S), bool))[None]
        a = layers._sdpa(cfg, q, k, v, mask[:, None, None])
    x, aux = _attn_post(cfg, blk, x, a)
    return x, k, v, aux


def backbone(cfg: ModelConfig, params: Params, h: jnp.ndarray,
             positions: jnp.ndarray, remat: bool = False,
             encoder_out: Optional[jnp.ndarray] = None):
    """Runs the layer stack on embedded input h (B, S, d).

    Returns (h, aux_loss).
    """
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        def body(x, blk):
            x, _, _, aux = _attn_block_body(cfg, blk, x, positions)
            return x, aux
        h, aux = _scan_blocks(body, h, params["blocks"], remat)
        return h, jnp.sum(aux)
    if fam == "ssm":
        def body(x, blk):
            y, _ = ssm_lib.apply_mamba_block(
                cfg, blk["mamba"], layers.apply_norm(cfg, blk["ln"], x))
            return x + y, 0.0
        h, aux = _scan_blocks(body, h, params["blocks"], remat)
        return h, jnp.sum(aux)
    if fam == "hybrid":
        shared = params["shared_attn"]

        def ssm_body(x, blk):
            y, _ = ssm_lib.apply_mamba_block(
                cfg, blk["mamba"], layers.apply_norm(cfg, blk["ln"], x))
            return x + y, 0.0

        def group_body(x, grp):
            x, _ = _scan_blocks(ssm_body, x, grp, remat)
            x = layers.apply_dense_block(cfg, shared, x, positions)
            return x, 0.0

        group_body = _maybe_remat(group_body, remat)
        h, _ = jax.lax.scan(group_body, h, params["groups"])
        if "tail" in params:
            h, _ = _scan_blocks(ssm_body, h, params["tail"], remat)
        return h, jnp.zeros(())
    if fam == "audio":
        assert encoder_out is not None

        def body(x, blk):
            x = x + layers.attention(
                cfg, blk["self_attn"],
                layers.apply_norm(cfg, blk["ln_self"], x),
                positions, causal=True, use_rope=False)
            xc = layers.apply_norm(cfg, blk["ln_cross"], x)
            B, F = encoder_out.shape[0], encoder_out.shape[1]
            ck = (encoder_out @ blk["cross_attn"]["wk"]).reshape(
                B, F, cfg.num_kv_heads, cfg.head_dim)
            cv = (encoder_out @ blk["cross_attn"]["wv"]).reshape(
                B, F, cfg.num_kv_heads, cfg.head_dim)
            x = x + layers.cross_attention(cfg, blk["cross_attn"], xc, ck, cv)
            x = x + layers.apply_mlp(cfg, blk["mlp"],
                                     layers.apply_norm(cfg, blk["ln_mlp"], x))
            return x, 0.0
        h, _ = _scan_blocks(body, h, params["dec_blocks"], remat)
        return h, jnp.zeros(())
    raise ValueError(fam)


def encode_audio(cfg: ModelConfig, params: Params, frames: jnp.ndarray,
                 remat: bool = False) -> jnp.ndarray:
    """Whisper encoder over stub frame embeddings (B, F, d)."""
    F = frames.shape[1]
    h = frames + layers.sinusoidal_positions(F, cfg.d_model)[None]
    positions = jnp.arange(F)

    def body(x, blk):
        x = x + layers.attention(cfg, blk["attn"],
                                 layers.apply_norm(cfg, blk["ln_attn"], x),
                                 positions, causal=False, use_rope=False)
        x = x + layers.apply_mlp(cfg, blk["mlp"],
                                 layers.apply_norm(cfg, blk["ln_mlp"], x))
        return x, 0.0

    h, _ = _scan_blocks(body, h, params["enc_blocks"], remat)
    return layers.apply_norm(cfg, params["enc_norm"], h)


def unembed(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    h = layers.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    return sharding.constrain_logits(logits)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. batch: tokens (B,S) [+ patch_embeds | frames].

    Returns (logits (B, S, vocab), aux_loss).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = sharding.constrain_tokens(params["embed"][tokens])
    encoder_out = None
    prefix = 0

    if cfg.family == "vlm":
        patches = batch["patch_embeds"] @ params["patch_proj"]
        h = jnp.concatenate([patches.astype(h.dtype), h], axis=1)
        prefix = patches.shape[1]
    elif cfg.family == "audio":
        encoder_out = encode_audio(cfg, params, batch["frames"], remat)
        h = h + layers.sinusoidal_positions(S, cfg.d_model)[None]

    h, orig_len = _pad_to_multiple(h, layers.Q_CHUNK
                                   if h.shape[1] >= layers.CHUNKED_ATTN_THRESHOLD
                                   else 1)
    positions = jnp.arange(h.shape[1])
    h, aux = backbone(cfg, params, h, positions, remat, encoder_out)
    h = h[:, prefix: prefix + S]
    logits = unembed(cfg, params, h)
    return logits, aux


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> jnp.ndarray:
    """Next-token cross-entropy (labels = batch['labels'])."""
    logits, aux = forward(cfg, params, batch, remat=True)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    # Gather-free gold-logit extraction: elementwise mask + reduce stays local
    # on a vocab-sharded logits tensor (no all-gather of the logits).
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), axis=-1)
    nll = jnp.mean(logz - gold)
    return nll + MOE_AUX_COEF * aux


# ---------------------------------------------------------------------------
# KV / state caches + single-token decode
# ---------------------------------------------------------------------------

def kv_store_dtype(cfg: ModelConfig):
    """DENSE KV-cache storage dtype (bf16 default; f8 halves bytes).

    The SCLAD values ("int8"/"fp8") only change the PAGED pool layout
    (``init_paged_cache`` — compressed payload + scale leaves); dense
    stripes (wave mode, hybrid/audio caches) keep the bf16 default under
    them, so every family stays servable at any ``kv_dtype``.
    """
    return jnp.float8_e4m3fn if cfg.kv_dtype == "f8" else DTYPE


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    fam = cfg.family
    KVD = kv_store_dtype(cfg)
    hk, hd, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    if fam in ("dense", "moe", "vlm"):
        ctx = max_len + (cfg.num_patches if fam == "vlm" else 0)
        return {
            "k": jnp.zeros((L, batch, ctx, hk, hd), KVD),
            "v": jnp.zeros((L, batch, ctx, hk, hd), KVD),
        }
    if fam == "ssm":
        one = ssm_lib.init_mamba_cache(cfg, batch)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), one)
    if fam == "hybrid":
        groups, per, tail = _hybrid_layout(cfg)
        one = ssm_lib.init_mamba_cache(cfg, batch)
        c = {
            "groups": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (groups, per) + x.shape), one),
            "attn_k": jnp.zeros((groups, batch, max_len, hk, hd), KVD),
            "attn_v": jnp.zeros((groups, batch, max_len, hk, hd), KVD),
        }
        if tail:
            c["tail"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (tail,) + x.shape), one)
        return c
    if fam == "audio":
        F = cfg.encdec.encoder_seq_len
        return {
            "k": jnp.zeros((L, batch, max_len, hk, hd), KVD),
            "v": jnp.zeros((L, batch, max_len, hk, hd), KVD),
            "cross_k": jnp.zeros((L, batch, F, hk, hd), KVD),
            "cross_v": jnp.zeros((L, batch, F, hk, hd), KVD),
        }
    raise ValueError(fam)


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int, mesh=None) -> Params:
    """KV cache as a pool of fixed-size token blocks (attention families).

    Layout (L, num_blocks, block_size, Hk*hd), lane-dense: row ``o`` of
    block ``b`` in layer ``l`` holds one token's K (or V) for every KV head,
    head h in lanes [h*hd, (h+1)*hd).  Block ``b`` holds ``block_size``
    consecutive token positions of whichever sequence(s) reference it per
    the host-side ``serving.paged.BlockStore`` — with prefix caching a
    block can appear in SEVERAL lanes' tables at once (ref-counted,
    read-only sharing), and retired blocks keep their payload while they
    sit in the store's LRU pool.  Block 0 is the trash block dead lanes
    write into.  ``layers.attention_decode`` and ``prefill_slots`` address
    the pool through per-row block tables and a layer index, and carry the
    whole stacked pool through the layer scan: no step slices, restacks or
    relays out the pool (the lane-dense rows are what the Pallas kernels
    DMA, so the device layout needs no relayout for them).  Writes must
    target blocks the store reports exclusive (the engine's copy-on-write
    barrier guarantees this — see ``copy_cache_block``).

    With a SCLAD ``cfg.kv_dtype`` ("int8" / "fp8") the pool is stored
    compressed: the k/v leaves hold the quantized payload and two extra
    fp32 leaves ``k_scale`` / ``v_scale`` of shape (L, N, bs, Hk) hold the
    per-position-per-head scales (``models.kv_quant``).  Every pool
    reader/writer — ``layers.attention_decode``, ``prefill_slots``, the
    flash kernels and their jnp references — carries the scale leaves
    alongside the payload; block identity (hashing, sharing, COW, LRU) is
    over the (payload, scale) pair as one unit.

    With a ``mesh`` the pool is placed per ``cache_specs(paged=True)``:
    payload and scale leaves co-sharded on their KV heads over ``model``
    (contiguous head ranges of the lane axis, so the shard_map'd kernels
    dequantize locally), everything else replicated.  The head sharding is
    dropped when Hk does not divide the axis — the same gate
    ``attn_shard_size`` applies at dispatch, so placement and dispatch
    always agree.
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm"):
        raise NotImplementedError(
            f"paged KV caches cover the attention families, not {fam!r}")
    hk, hd, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    rows = (L, num_blocks, block_size, hk * hd)
    if kv_quant.is_quantized(cfg.kv_dtype):
        KVD = kv_quant.payload_dtype(cfg.kv_dtype)
        cache = {
            "k": jnp.zeros(rows, KVD),
            "v": jnp.zeros(rows, KVD),
            # All-zero payload rows carry scale 1.0 by the quantizer's
            # convention; zeros-init matches (0 * 1.0 == 0) but any init
            # works — unwritten positions are masked by lengths.
            "k_scale": jnp.ones((L, num_blocks, block_size, hk),
                                jnp.float32),
            "v_scale": jnp.ones((L, num_blocks, block_size, hk),
                                jnp.float32),
        }
    else:
        KVD = kv_store_dtype(cfg)
        cache = {"k": jnp.zeros(rows, KVD), "v": jnp.zeros(rows, KVD)}
    if mesh is None:
        return cache
    with sharding.use_axes(mesh):
        specs = sharding.cache_specs(cfg, cache, None, 1, paged=True)
        specs = sharding.sanitize_specs(specs, cache)
    return jax.device_put(cache, sharding.to_shardings(mesh, specs))


def _pool_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    """The paged pool's leaves in kernel argument order: payload, then the
    SCLAD scales of a quantized pool."""
    if kv_quant.is_quantized(cfg.kv_dtype):
        return ("k", "v", "k_scale", "v_scale")
    return ("k", "v")


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


@functools.lru_cache(maxsize=1)
def _copy_cache_block_fn():
    # Jitted with the cache DONATED so XLA aliases the pool and the copy is
    # an in-place one-block scatter — un-jitted `.at[].set` would
    # materialize a full copy of the whole pool per COW event.  (CPU has no
    # donation; skip it there to avoid warnings.)
    donate = (0,) if jax.default_backend() != "cpu" else ()

    def body(cache, src, dst):
        # tree.map so the quantized layout's scale leaves ride along with
        # the payload — a COW'd block is the (payload, scale) pair.
        return jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]), cache)

    return jax.jit(body, donate_argnums=donate)


def copy_cache_block(cache: Params, src: int, dst: int) -> Params:
    """Copy one paged-KV block's payload across all layers (``src -> dst``).

    The copy-on-write half of block sharing: when the host-side
    ``serving.paged.BlockStore`` swaps a shared block for a fresh exclusive
    one (``ensure_writable``), the device payload must follow before the
    lane's next scatter.  Rare by construction — full-block-only sharing
    puts writes past the shared prefix — but each event must still cost
    O(block), not O(pool): the copy runs jitted with the pool donated, and
    src/dst passed as traced scalars (one compile covers every block pair).
    """
    return _copy_cache_block_fn()(cache, jnp.int32(src), jnp.int32(dst))


def prefill_slots(cfg: ModelConfig, params: Params, cache: Params,
                  tokens: jnp.ndarray, lengths: jnp.ndarray,
                  block_tables: jnp.ndarray,
                  start: Optional[jnp.ndarray] = None,
                  patch_embeds: Optional[jnp.ndarray] = None,
                  all_logits: bool = False,
                  mesh=None) -> Tuple[jnp.ndarray, Params]:
    """Prefill one left-padded prompt CHUNK per row into a paged KV cache.

    The continuous-batching admission path: a group of requests with
    *different* prompt lengths is left-padded to a common bucket length and
    prefilled in one call, each row writing its K/V into its own cache
    blocks at its own offset.  Long prompts are processed in fixed-size
    chunks across several calls (interleaved with decode iterations by the
    engine, so admission never stalls in-flight decodes): the first call
    passes ``start=None``, later calls pass each row's already-cached token
    count and the chunk attends to the cached context through its block
    table.

    Prefix caching rides the same ``start`` mechanism: a request admitted
    with ``cached_len`` prefix tokens already resident (shared blocks
    matched by ``serving.paged.BlockStore``) enters here as a continuation
    with ``start = cached_len`` — only the uncached tail is embedded and
    written, while the shared context (including a cached vlm patch prefix)
    is read through the block table.  The writes land strictly at
    positions >= ``start``, i.e. past every shared block.

    The whole stacked pool rides the layer scan's carry.  Per layer, the
    attention core AND the new-token K/V scatter dispatch through
    ``kernels.flash_prefill.ops.prefill_attention`` at that layer's index,
    selected by ``cfg.attn_kernel``: on the kernel path the cached context
    is streamed block-by-block straight out of the shared pool
    (scalar-prefetched table walk — no dense per-lane
    ``k_pool[layer, block_tables]`` copy, no dense (Bn, S, S) mask) and the
    compacted chunk K/V is scattered into the pool inside the same kernel
    invocation; the reference path gathers and scatters host-side,
    bit-exact with the pre-kernel engine.

    tokens:  (Bn, P) int32, each row's chunk LEFT-padded to P;
    lengths: (Bn,) true token count of this chunk (<= P);
    block_tables: (Bn, T) int32 rows of the paged block table
        (``serving.paged.BlockStore.block_table()``), grown by the
        caller to cover this chunk's writes;
    start:   None => every row starts at cache position 0 (first chunk; the
        vlm patch prefix is embedded and written here); else (Bn,) int32
        cache positions already filled per row (INCLUDING any vlm prefix);
    patch_embeds: (Bn, num_patches, d) for the vlm family (zeros if None;
        ignored on continuation chunks).

    Pad positions are masked out of the attention (so dense/vlm results are
    bit-identical to unpadded single-request prefill; for moe, co-admitted
    requests share expert-capacity buffers, so under *tight* capacity
    factors drops — and therefore logits — can differ from the solo run)
    and pad RoPE phases are clipped to each row's first real position.
    Per layer each row's K/V is left-compacted ([patches | chunk | junk])
    and scattered through its block table at positions ``start + i``;
    junk-tail writes are dropped, so nothing lands outside the row's own
    blocks.

    Families: dense / moe / vlm (attention KV caches).  MoE blocks receive
    the real-token mask as routing validity, so pad tokens consume no
    expert capacity and cannot displace live tokens.
    Returns (last-real-token logits (Bn, vocab), updated cache).  The
    logits are only meaningful on a row's FINAL chunk.

    ``all_logits=True`` instead returns per-position logits (Bn, P, vocab)
    over the CHUNK's token columns (the vlm patch prefix is excluded) —
    the speculative-decode verify entry point: the engine feeds
    [last-accepted | drafts] as a continuation chunk and needs the logits
    AT every drafted position to check each draft against what plain
    decode would have sampled.  Left padding means row positions < pad are
    junk; callers mask by ``lengths``.  The K/V write-through is identical
    either way (drafted K/V lands in the pool optimistically).
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm"):
        raise NotImplementedError(
            f"prefill_slots supports attention KV caches, not family {fam!r}")
    Bn, P = tokens.shape
    first = start is None
    pad = (P - lengths).astype(jnp.int32)  # (Bn,)
    h = params["embed"][tokens]
    prefix = 0
    if fam == "vlm" and first:
        if patch_embeds is None:
            patch_embeds = jnp.zeros((Bn, cfg.num_patches, cfg.d_model),
                                     DTYPE)
        patches = patch_embeds @ params["patch_proj"]
        h = jnp.concatenate([patches.astype(h.dtype), h], axis=1)
        prefix = cfg.num_patches
    S = prefix + P
    start_v = jnp.zeros((Bn,), jnp.int32) if first \
        else start.astype(jnp.int32)

    tok_pos = start_v[:, None] + prefix \
        + jnp.maximum(jnp.arange(P)[None] - pad[:, None], 0)
    if prefix:
        positions = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(prefix)[None], (Bn, prefix)),
             tok_pos], axis=1)
    else:
        positions = tok_pos  # (Bn, S)

    # Real-token mask for MoE routing validity: pads/dead slots consume
    # no expert capacity.  (The attention-side causal/left-pad masking now
    # lives in kernels.flash_prefill, derived from the same scalars — on
    # the kernel path no dense (Bn, S, S) mask is ever materialized.)
    sidx = jnp.arange(S)
    real_key = (sidx[None] < prefix) | (sidx[None] >= prefix + pad[:, None])
    lengths = jnp.asarray(lengths, jnp.int32)

    quantized = kv_quant.is_quantized(cfg.kv_dtype)
    names = _pool_leaves(cfg)

    # The whole stacked pool rides the scan carry: each layer's kernel (or
    # reference) reads and writes its slab in place through the layer
    # index, so no per-layer slice or restack of the pool is made.
    def body(carry, blk_layer):
        x, pool = carry
        blk, layer = blk_layer
        q, k, v = _attn_qkv(cfg, blk, x, positions)
        a, *pool = prefill_ops.prefill_attention(
            q, k, v, pool[0], pool[1], lengths, block_tables, layer=layer,
            start=None if first else start_v, prefix=prefix,
            kernel=cfg.attn_kernel, kv_scales=tuple(pool[2:]) or None,
            kv_dtype=cfg.kv_dtype if quantized else None, mesh=mesh)
        x, _ = _attn_post(cfg, blk, x, a, moe_valid=real_key)
        return (x, tuple(pool)), None

    (h, pool), _ = jax.lax.scan(
        body, (h, tuple(cache[n] for n in names)),
        (params["blocks"], jnp.arange(cfg.num_layers)))
    cache = dict(cache, **dict(zip(names, pool)))
    # Left padding aligns every row's last REAL token at index S-1.
    if all_logits:
        logits = unembed(cfg, params, h[:, prefix:])  # (Bn, P, vocab)
    else:
        logits = unembed(cfg, params, h[:, -1])
    return logits, cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: jnp.ndarray, position: jnp.ndarray,
                active: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None,
                mesh=None) -> Tuple[jnp.ndarray, Params]:
    """One autoregressive step. tokens: (B, 1); position: scalar int32 OR a
    per-row (B,) int32 vector (index of each row's new token within the
    cache context — continuous batching runs rows at different offsets).
    Vector positions are supported for the dense/moe/vlm/ssm/hybrid
    families; audio requires a scalar.

    active: optional (B,) bool — rows marked False are dead lanes (retired
    serving slots).  For the moe family they are excluded from expert
    capacity so they cannot displace live rows' tokens; other families
    ignore the mask (dead lanes are already masked out by position).

    block_tables: optional (B, T) int32 — the cache is a paged block pool
    (``init_paged_cache``) addressed per row through this table instead of
    a dense (L, B, ctx) stripe (dense/moe/vlm only).  The whole stacked
    pool rides the layer scan's carry; per layer, attention reads dispatch
    through ``kernels.flash_decode.ops.decode_attention`` with the
    (pool, block_tables, layer, lengths = position + 1) calling
    convention: on the kernel path (``cfg.attn_kernel``) each row's
    blocks are walked through (layer, table) straight out of the shared
    pool — neither a per-layer slice nor a dense per-lane copy of the
    pool is materialized.

    Returns (logits (B, 1, vocab), updated cache).
    """
    fam = cfg.family
    h = params["embed"][tokens]
    B = tokens.shape[0]
    if block_tables is not None and fam not in ("dense", "moe", "vlm"):
        raise NotImplementedError(
            f"paged decode covers the attention families, not {fam!r}")

    if fam in ("dense", "moe", "vlm"):
        pos = position + (cfg.num_patches if fam == "vlm" else 0)

        def ffn(x, blk):
            if fam == "moe":
                y, _ = moe_lib.apply_moe(
                    cfg, blk["moe"], layers.apply_norm(cfg, blk["ln_mlp"], x),
                    valid=None if active is None else active[:, None])
                return x + y
            return x + layers.apply_mlp(
                cfg, blk["mlp"], layers.apply_norm(cfg, blk["ln_mlp"], x))

        if block_tables is None:
            def body(x, blk_kv):
                blk, kc, vc = blk_kv
                a, kc, vc = layers.attention_decode(
                    cfg, blk["attn"],
                    layers.apply_norm(cfg, blk["ln_attn"], x), kc, vc, pos)
                x = ffn(x + a, blk)
                return x, (kc, vc)

            h, (k_new, v_new) = jax.lax.scan(
                body, h, (params["blocks"], cache["k"], cache["v"]))
            new_cache = {"k": k_new, "v": v_new}
        else:
            names = _pool_leaves(cfg)

            # The whole stacked pool rides the scan carry (see
            # prefill_slots): the new token's K/V is written in place at
            # [layer, block, offset] and the read walks (layer, table).
            def body(carry, blk_layer):
                x, pool = carry
                blk, layer = blk_layer
                a, *pool = layers.attention_decode(
                    cfg, blk["attn"],
                    layers.apply_norm(cfg, blk["ln_attn"], x), pool[0],
                    pool[1], pos, block_tables=block_tables,
                    kv_scales=tuple(pool[2:]) or None, mesh=mesh,
                    layer=layer)
                return (ffn(x + a, blk), tuple(pool)), None

            (h, pool), _ = jax.lax.scan(
                body, (h, tuple(cache[n] for n in names)),
                (params["blocks"], jnp.arange(cfg.num_layers)))
            new_cache = dict(zip(names, pool))
    elif fam == "ssm":
        def body(x, blk_c):
            blk, c = blk_c
            y, c = ssm_lib.apply_mamba_decode(
                cfg, blk["mamba"], c, layers.apply_norm(cfg, blk["ln"], x))
            return x + y, c

        h, new_c = jax.lax.scan(body, h, (params["blocks"], cache))
        new_cache = new_c
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def ssm_body(x, blk_c):
            blk, c = blk_c
            y, c = ssm_lib.apply_mamba_decode(
                cfg, blk["mamba"], c, layers.apply_norm(cfg, blk["ln"], x))
            return x + y, c

        def group_body(x, xs):
            grp, gc, kc, vc = xs
            x, gc = jax.lax.scan(ssm_body, x, (grp, gc))
            a, kc, vc = layers.attention_decode(
                cfg, shared["attn"],
                layers.apply_norm(cfg, shared["ln_attn"], x), kc, vc, position)
            x = x + a
            x = x + layers.apply_mlp(
                cfg, shared["mlp"], layers.apply_norm(cfg, shared["ln_mlp"], x))
            return x, (gc, kc, vc)

        h, (gc, kc, vc) = jax.lax.scan(
            group_body, h,
            (params["groups"], cache["groups"], cache["attn_k"], cache["attn_v"]))
        new_cache = {"groups": gc, "attn_k": kc, "attn_v": vc}
        if "tail" in cache:
            h, tc = jax.lax.scan(ssm_body, h, (params["tail"], cache["tail"]))
            new_cache["tail"] = tc
    elif fam == "audio":
        h = h + layers.sinusoidal_positions(
            int(cache["k"].shape[2]), cfg.d_model)[position][None, None]

        def body(x, xs):
            blk, kc, vc, ck, cv = xs
            a, kc, vc = layers.attention_decode(
                cfg, blk["self_attn"],
                layers.apply_norm(cfg, blk["ln_self"], x), kc, vc, position,
                use_rope=False)
            x = x + a
            x = x + layers.cross_attention(
                cfg, blk["cross_attn"],
                layers.apply_norm(cfg, blk["ln_cross"], x),
                ck.astype(x.dtype), cv.astype(x.dtype))
            x = x + layers.apply_mlp(
                cfg, blk["mlp"], layers.apply_norm(cfg, blk["ln_mlp"], x))
            return x, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(
            body, h, (params["dec_blocks"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=k_new, v=v_new)
    else:
        raise ValueError(fam)

    logits = unembed(cfg, params, h)
    return logits, new_cache


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray],
            max_len: int) -> Tuple[jnp.ndarray, Params]:
    """Process the prompt, returning (last-position logits (B, vocab), cache).

    Implemented as forward + recompute of K/V into the cache for attention
    families; SSM caches carry the final state from the chunked scan.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    fam = cfg.family
    cache = init_cache(cfg, B, max_len)
    h = params["embed"][tokens]
    prefix = 0
    encoder_out = None
    if fam == "vlm":
        patches = batch["patch_embeds"] @ params["patch_proj"]
        h = jnp.concatenate([patches.astype(h.dtype), h], axis=1)
        prefix = patches.shape[1]
    elif fam == "audio":
        encoder_out = encode_audio(cfg, params, batch["frames"])
        h = h + layers.sinusoidal_positions(S, cfg.d_model)[None]

    positions = jnp.arange(h.shape[1])
    S_ctx = h.shape[1]

    if fam in ("dense", "moe", "vlm", "audio"):
        blocks = params["blocks"] if fam != "audio" else params["dec_blocks"]
        kvd = kv_store_dtype(cfg)

        if fam == "audio":
            def body(x, blk):
                xn = layers.apply_norm(cfg, blk["ln_self"], x)
                q, k, v = layers._project_qkv(cfg, blk["self_attn"], xn, xn)
                if S_ctx >= layers.CHUNKED_ATTN_THRESHOLD and \
                        S_ctx % layers.Q_CHUNK == 0:
                    a = layers.chunked_attention(q, k, v, causal=True)
                else:
                    mask = jnp.tril(
                        jnp.ones((S_ctx, S_ctx), bool))[None, None, None]
                    a = layers._sdpa(cfg, q, k, v, mask)
                x = x + a @ blk["self_attn"]["wo"]
                F = encoder_out.shape[1]
                ck = (encoder_out @ blk["cross_attn"]["wk"]).reshape(
                    B, F, cfg.num_kv_heads, cfg.head_dim)
                cv = (encoder_out @ blk["cross_attn"]["wv"]).reshape(
                    B, F, cfg.num_kv_heads, cfg.head_dim)
                xc = layers.apply_norm(cfg, blk["ln_cross"], x)
                x = x + layers.cross_attention(cfg, blk["cross_attn"], xc,
                                               ck, cv)
                x = x + layers.apply_mlp(
                    cfg, blk["mlp"], layers.apply_norm(cfg, blk["ln_mlp"], x))
                return x, dict(k=k.astype(kvd), v=v.astype(kvd),
                               cross_k=ck.astype(kvd), cross_v=cv.astype(kvd))
        else:
            # K/V for the cache is recomputed by the shared block body
            # (weights are cheap to re-apply and this keeps the layer math
            # single-sourced with backbone/prefill_slots).
            def body(x, blk):
                x, k, v, _ = _attn_block_body(cfg, blk, x, positions)
                return x, dict(k=k.astype(kvd), v=v.astype(kvd))

        h, kv = jax.lax.scan(body, h, blocks)
        pad = cache["k"].shape[2] - S_ctx
        k_full = jnp.pad(kv["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v_full = jnp.pad(kv["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache = dict(cache, k=k_full, v=v_full)
        if fam == "audio":
            cache["cross_k"] = kv["cross_k"]
            cache["cross_v"] = kv["cross_v"]
    elif fam == "ssm":
        def body(x, blk):
            y, st = ssm_lib.apply_mamba_block(
                cfg, blk["mamba"], layers.apply_norm(cfg, blk["ln"], x))
            return x + y, st

        h, states = jax.lax.scan(body, h, params["blocks"])
        cache = states  # stacked {"state", "conv"} matches init_cache layout
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def ssm_body(x, blk):
            y, st = ssm_lib.apply_mamba_block(
                cfg, blk["mamba"], layers.apply_norm(cfg, blk["ln"], x))
            return x + y, st

        def group_body(x, grp):
            x, st = jax.lax.scan(ssm_body, x, grp)
            xn = layers.apply_norm(cfg, shared["ln_attn"], x)
            q, k, v = layers._project_qkv(cfg, shared["attn"], xn, xn)
            q = layers.apply_rope(cfg, q, positions)
            k = layers.apply_rope(cfg, k, positions)
            if S_ctx >= layers.CHUNKED_ATTN_THRESHOLD and \
                    S_ctx % layers.Q_CHUNK == 0:
                a = layers.chunked_attention(q, k, v, causal=True)
            else:
                mask = jnp.tril(jnp.ones((S_ctx, S_ctx), bool))[None, None, None]
                a = layers._sdpa(cfg, q, k, v, mask)
            x = x + a @ shared["attn"]["wo"]
            x = x + layers.apply_mlp(
                cfg, shared["mlp"], layers.apply_norm(cfg, shared["ln_mlp"], x))
            return x, (st, k.astype(kv_store_dtype(cfg)),
                       v.astype(kv_store_dtype(cfg)))

        h, (gst, gk, gv) = jax.lax.scan(group_body, h, params["groups"])
        pad = cache["attn_k"].shape[2] - S_ctx
        cache["attn_k"] = jnp.pad(gk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["attn_v"] = jnp.pad(gv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["groups"] = gst
        if "tail" in params:
            h, tst = jax.lax.scan(ssm_body, h, params["tail"])
            cache["tail"] = tst
    else:
        raise ValueError(fam)

    logits = unembed(cfg, params, h[:, -1])
    return logits, cache
