"""SCLAD KV quantization: Store-as-Compressed, Load-as-Dense block payloads.

CC-MEM's signature mechanism (PAPER.md §CC-MEM) keeps payloads compressed
in the memory system and expands them on the load path, so compute units
only ever see dense values.  The repo already models SCLAD for *weights*
(``kernels.sclad_matmul`` / ``core.sparsity``); this module is the KV-cache
side: the paged serving pool (``model.init_paged_cache``) stores an int8 /
fp8 payload plus per-position-per-head fp32 scales, and every reader —
the jnp references AND the Pallas kernels — dequantizes on load.

ONE quantization definition, shared by every writer:

  * ``layers.attention_decode``   — the decode-step single-token scatter;
  * ``kernels.flash_prefill.ref.scatter_new_kv_ref`` — the host-side
    chunk scatter (``attn_kernel="off"`` / "auto" off-TPU);
  * ``kernels.flash_prefill.flash_prefill`` — the fused in-kernel scatter
    (quantizes the chunk's new K/V in VMEM before the
    ``input_output_aliases`` write-back).

The arithmetic is deliberately PATH-INDEPENDENT: each token's payload and
scale are a pure function of that token's dense K/V row (fp32 view of the
compute-dtype value, amax over the head dim, symmetric round-to-nearest).
No running block amax, no requantization — so the compressed bytes a token
leaves in the pool are bitwise identical whether it arrived via a first
chunk, a continuation chunk, a decode step or a preemption recompute.
That bit-determinism is what makes the ``BlockStore`` hash chain (token
ids + chain root) a sound content address FOR the compressed payload, and
what lets kernel-vs-reference tests compare pools bitwise.

Consequently the compute side can be made path-independent too: readers
always observe a token through ``dequantize(quantize(x))``.  The prefill
paths "fake-quantize" the chunk's own in-flight K/V before attending to it
(see ``fake_quant``), so a key scores identically whether it is read from
the quantized pool or seen in-chunk — preserving the serving engine's
greedy bit-identity matrix (prefix cache on/off, chunk sizes, preemption
recompute) under quantization.

Scales are per (token position, kv head): ``quantize`` of a (..., Hk, D)
view returns (..., Hk) — for the lane-dense (L, N, bs, Hk*D) pool that is
(L, N, bs, Hk) fp32, and the payload is stored back lane-dense.  Per-head
granularity matches the "per-block-per-head scale metadata" the CC-MEM
decompressor would hold; per-position granularity is what keeps writes
path-independent (a per-block amax would depend on write history and stale
recycled content).  fp8 payloads reuse the float8_e4m3fn dtype the dense-cache
``kv_dtype="f8"`` path already ships.
"""
from __future__ import annotations

import jax.numpy as jnp

#: Every accepted ``ModelConfig.kv_dtype`` spelling.
#:   "fp"   — fp-exact pool (storage dtype via ``model.kv_store_dtype``);
#:   "bf16" — legacy alias of "fp" (the pre-SCLAD default spelling);
#:   "f8"   — legacy DENSE-cache storage override (float8 stripes, no
#:            scales; paged pools treat it as fp-exact f8 storage);
#:   "int8" — SCLAD paged pool: int8 payload + fp32 scales;
#:   "fp8"  — SCLAD paged pool: float8_e4m3fn payload + fp32 scales.
KV_DTYPES = ("fp", "bf16", "f8", "int8", "fp8")

#: The subset that stores the paged pool as compressed payload + scales.
QUANTIZED_KV_DTYPES = ("int8", "fp8")


def is_quantized(kv_dtype: str) -> bool:
    """True iff the paged pool stores compressed payload + scale leaves."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
    return kv_dtype in QUANTIZED_KV_DTYPES


def payload_dtype(kv_dtype: str):
    """On-device dtype of the compressed pool payload."""
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"{kv_dtype!r} is not a quantized kv_dtype")


def qmax(kv_dtype: str) -> float:
    """Largest representable payload magnitude the scale normalizes to."""
    if kv_dtype == "int8":
        return 127.0
    if kv_dtype == "fp8":
        return 448.0  # float8_e4m3fn max normal
    raise ValueError(f"{kv_dtype!r} is not a quantized kv_dtype")


def quantize(x: jnp.ndarray, kv_dtype: str):
    """Compress ``x`` (..., D) -> (payload (..., D), scales (...,) fp32).

    Symmetric per-row (last axis) quantization: ``scale = amax / qmax``
    (1.0 for all-zero rows so dequantization is exact), payload
    ``round(x / scale)`` for int8 (|q| <= 127 by construction — no clip
    needed) or a saturating fp8 cast.  All arithmetic runs in fp32 from
    the compute-dtype input, and is reproduced operation-for-operation by
    the fused in-kernel scatter — the two writers are BITWISE identical.
    """
    qm = qmax(kv_dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    # amax * (1/qm), NOT amax / qm: XLA rewrites division by a constant
    # into reciprocal multiplication under jit but not in eager mode, so
    # a division here would make the scale depend on the tracing context
    # (1-ulp drift between the engine's jitted writers and eagerly-built
    # test pools).  An explicit constant multiply is bitwise identical
    # everywhere.  round(xf/scale) still can't exceed qmax + 0.5, so the
    # int8 cast below stays clip-free.
    scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / qm),
                      1.0).astype(jnp.float32)
    q = xf / scale[..., None]
    if kv_dtype == "int8":
        payload = jnp.round(q).astype(jnp.int8)
    else:
        payload = q.astype(jnp.float8_e4m3fn)
    return payload, scale


def dequantize(payload: jnp.ndarray, scale: jnp.ndarray,
               dtype=jnp.float32) -> jnp.ndarray:
    """Expand payload (..., D) with scales (...,) back to dense ``dtype``.

    The load-path half of SCLAD: ``payload * scale`` in fp32, then one
    cast to the requested compute dtype — the SAME cast chain the kernels
    use, so a value dequantized host-side and in-kernel agrees bitwise in
    fp32 (and to the cast's rounding in bf16).
    """
    out = payload.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    return out.astype(dtype)


def fake_quant(x: jnp.ndarray, kv_dtype: str) -> jnp.ndarray:
    """``dequantize(quantize(x))`` in x's dtype — the quantization a reader
    will observe once ``x`` lands in the pool.

    The prefill attention paths run the chunk's own K/V through this
    before attending, so a token's keys/values score identically in-chunk
    and from-pool: greedy outputs stay bit-identical across chunk sizes,
    prefix-cache hits and preemption recomputes even under quantization.
    """
    payload, scale = quantize(x, kv_dtype)
    return dequantize(payload, scale, x.dtype)
