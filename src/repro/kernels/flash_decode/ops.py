"""Public decode-attention entry point: one call, both cache layouts.

``decode_attention`` is what ``models.layers.attention_decode`` (and
therefore ``model.decode_step`` and the serving engine's jitted decode
window) dispatches through.  Layout is selected by ``block_tables``
(None = contiguous (B, S, Hk, D) caches; else the lane-dense
(L, N, bs, Hk*D) block pool of every layer, read at one layer index),
and the implementation by the ``kernel`` knob:

  * ``"auto"`` (default) — the Pallas kernel on TPU, the jnp reference
    elsewhere.  The probe is ``jax.default_backend()`` (respects
    JAX_PLATFORMS, no eager device enumeration) combined with this
    explicit knob — NOT ``jax.devices()[0].platform``, which forces
    device initialization and ignores how the caller placed its arrays.
  * ``"on"``   — always the kernel; off-TPU it runs in Pallas interpret
    mode (the CI/CPU parity path — bit-for-bit the kernel's math, executed
    by the interpreter).
  * ``"off"``  — always the jnp reference (the pre-kernel gather path).

The knob threads down from ``ModelConfig.attn_kernel`` /
``ServingEngine(attn_kernel=...)`` / ``launch.serve --attn-kernel``; the
same knob selects the prefill-side ``kernels.flash_prefill`` twin.
Deprecated spellings: ``ServingEngine(decode_kernel=...)`` and
``--decode-kernel`` still map onto ``attn_kernel`` (DeprecationWarning),
and ``cfg.decode_kernel`` remains readable as a property — but
``ModelConfig(decode_kernel=...)`` construction is gone with the field.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.flash_decode import (flash_decode,
                                                     paged_flash_decode)
from repro.kernels.flash_decode.ref import decode_ref, paged_decode_ref
from repro.parallel import sharding

DECODE_KERNEL_MODES = ("auto", "on", "off")


def resolve_kernel(kernel: str = "auto"):
    """-> (use_kernel, interpret) for the current backend."""
    if kernel not in DECODE_KERNEL_MODES:
        raise ValueError(
            f"decode kernel mode {kernel!r} not in {DECODE_KERNEL_MODES}")
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = on_tpu if kernel == "auto" else kernel == "on"
    return use_kernel, use_kernel and not on_tpu


def decode_attention(q, k_cache, v_cache, lengths, *, block_tables=None,
                     layer=0, kernel: str = "auto", block_k: int = 128,
                     kv_scales=None, mesh=None):
    """One decode-attention step.

    q: (B, H, D) — the new token's (rotated) queries;
    k_cache/v_cache: (B, S, Hk, D) contiguous caches, OR — when
        ``block_tables`` (B, T) int32 is given — the shared lane-dense
        (L, N, bs, Hk*D) block pool of every layer they index;
    lengths: scalar or (B,) int32 valid positions per row;
    layer: int32 scalar, the pool layer read (paged layout only);
    kv_scales: optional (k_scale, v_scale) (L, N, bs, Hk) fp32 scales of a
        SCLAD quantized pool (paged layout only) — both implementations
        dequantize the compressed payload on the load path.
    mesh: optional mesh with a ``model`` axis — the paged path then runs
        under ``shard_map`` with the pool's head lanes (contiguous Hk/m
        head ranges of the merged axis, payload and scale leaves) and
        the query head groups sharded over it; tables, lengths and the
        layer index broadcast; per-shard body unchanged.  Ignored (plain
        single-device dispatch) when the axis can't split Hk evenly.

    Returns (B, H, D).  The caller owns the cache scatter of the new K/V;
    this is the read side only.
    """
    use_kernel, interpret = resolve_kernel(kernel)
    if block_tables is not None:
        num_kv_heads = k_cache.shape[-1] // q.shape[-1]
        if sharding.attn_shard_size(mesh, num_kv_heads) > 1:
            return _sharded_paged_decode(q, k_cache, v_cache, lengths,
                                         block_tables, layer, kernel,
                                         block_k, kv_scales, mesh)
        if not use_kernel:
            return paged_decode_ref(q, k_cache, v_cache, lengths,
                                    block_tables, kv_scales=kv_scales,
                                    layer=layer)
        return paged_flash_decode(q, k_cache, v_cache, lengths, block_tables,
                                  layer, block_k=block_k,
                                  interpret=interpret, kv_scales=kv_scales)
    assert kv_scales is None, "kv_scales is a paged-pool layout"
    if not use_kernel:
        return decode_ref(q, k_cache, v_cache, lengths)
    S = k_cache.shape[1]
    bk = min(block_k, S)
    while S % bk:  # largest divisor of S at most block_k
        bk -= 1
    if bk < 8 and bk < S:
        # Degenerate tiling (e.g. prime S): refuse rather than quietly
        # serve the reference where the kernel was asked for.
        raise ValueError(
            f"contiguous cache length {S} has no tile in [8, {block_k}] "
            f"that divides it; size the cache to a multiple of 8")
    return flash_decode(q, k_cache, v_cache, lengths, block_k=bk,
                        interpret=interpret)


def _sharded_paged_decode(q, k_cache, v_cache, lengths, block_tables,
                          layer, kernel, block_k, kv_scales, mesh):
    """shard_map the paged decode read over the mesh's ``model`` axis.

    Attention is independent per KV head, so splitting the pool's heads
    changes no arithmetic: shard i reads its own contiguous range of Hk/m
    heads — a contiguous lane range of the lane-dense pool, head-major —
    with the matching contiguous H/m query-head group (head h attends
    kv-head h // rep, and contiguous chunks keep rep per shard), and
    outputs concat back on the head axis — no collective at all on this
    read path (the downstream ``@ wo`` psum lives in the layer).  Block
    tables, lengths and the layer index — the kernel's scalar-prefetch
    operands — are broadcast so every shard walks the identical table.
    """
    sp = sharding.paged_attn_specs()
    args = [q, k_cache, v_cache, lengths, block_tables,
            jnp.asarray(layer, jnp.int32)]
    in_specs = [sp["q_decode"], sp["pool"], sp["pool"], sp["host"],
                sp["host"], sp["host"]]
    if kv_scales is not None:
        args += list(kv_scales)
        in_specs += [sp["scale"], sp["scale"]]

    def body(q, k, v, lengths, tables, layer, *scales):
        return decode_attention(q, k, v, lengths, block_tables=tables,
                                layer=layer, kernel=kernel, block_k=block_k,
                                kv_scales=tuple(scales) or None)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=sp["out_decode"], check_vma=False)(*args)
