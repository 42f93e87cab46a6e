"""Public chunked-prefill attention entry point: one call, both paths.

``prefill_attention`` is what ``models.model.prefill_slots`` (and therefore
the serving engine's jitted prefill chunks) dispatches through.  The
implementation is selected by the ``attn_kernel`` knob — the generalization
of PR 4's ``decode_kernel`` to BOTH attention hot paths:

  * ``"auto"`` (default) — the Pallas kernel on TPU, the jnp reference
    elsewhere (probe: ``jax.default_backend()``, same as flash_decode);
  * ``"on"``   — always the kernel; off-TPU it runs in Pallas interpret
    mode (the CI/CPU parity path — bit-for-bit the kernel's math, executed
    by the interpreter);
  * ``"off"``  — always the jnp reference: the pre-kernel dense context
    gather + host-side K/V scatter.

The knob threads down from ``ModelConfig.attn_kernel`` /
``ServingEngine(attn_kernel=...)`` / ``launch.serve --attn-kernel``.
Deprecated spellings: ``ServingEngine(decode_kernel=...)`` and
``--decode-kernel`` map onto ``attn_kernel`` with a DeprecationWarning,
and ``cfg.decode_kernel`` remains readable as a property.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Same probe + mode set as the decode-side kernel: "attn_kernel" selects
# both, so resolve_kernel is single-sourced there.
from repro.kernels.flash_decode.ops import (DECODE_KERNEL_MODES,
                                            resolve_kernel)
from repro.kernels.flash_prefill.flash_prefill import paged_flash_prefill
from repro.kernels.flash_prefill.ref import prefill_attention_ref
from repro.parallel import sharding

ATTN_KERNEL_MODES = DECODE_KERNEL_MODES  # ("auto", "on", "off")


def prefill_attention(q, k_new, v_new, k_pool, v_pool, lengths,
                      block_tables, *, layer=0,
                      start: Optional[jnp.ndarray] = None,
                      prefix: int = 0, kernel: str = "auto",
                      kv_scales=None, kv_dtype: Optional[str] = None,
                      mesh=None):
    """One layer of paged chunked-prefill attention + new-token K/V scatter.

    q: (B, S, H, D) rotated chunk queries (S = prefix + P, prompt tokens
    LEFT-padded to P); k_new/v_new: (B, S, Hk, D) the chunk's rotated K/V;
    k_pool/v_pool: (L, N, bs, Hk*D) shared lane-dense block pool of every
    layer; lengths: (B,) int32 true chunk token counts; block_tables:
    (B, T) int32; layer: int32 scalar, the pool layer read and written;
    start: None for first chunks, else (B,) int32 cached positions per
    row; prefix: static vlm patch-prefix length (first chunk only);
    kv_scales + kv_dtype: (k_scale, v_scale) (L, N, bs, Hk) fp32 scale
    leaves and the payload
    encoding ("int8"/"fp8") of a SCLAD quantized pool — both paths
    dequantize context on load, fake-quantize the chunk's own K/V before
    attending, and write quantized payload + scales (returning the
    5-tuple with k_scale'/v_scale' appended).

    Returns (attn_out (B, S, H*D), k_pool', v_pool').  On the kernel path
    the cached context is streamed through the block table (no dense
    per-lane gather, no dense (B, S, S) mask) and the scatter happens
    inside the kernel; the reference path gathers and scatters host-side,
    bit-exact with the pre-kernel engine.

    mesh: optional mesh with a ``model`` axis — the call then runs under
    ``shard_map`` with the pools (payload AND scale leaves, which are both
    inputs and outputs here: the scatter is fused in; contiguous head
    ranges of the merged head lanes), the chunk's new K/V, and the query
    heads sharded over it; tables, lengths, start and the layer index
    broadcast.  Ignored when the axis can't split Hk evenly.
    """
    if sharding.attn_shard_size(mesh, k_new.shape[2]) > 1:
        return _sharded_paged_prefill(q, k_new, v_new, k_pool, v_pool,
                                      lengths, block_tables, layer, start,
                                      prefix, kernel, kv_scales, kv_dtype,
                                      mesh)
    use_kernel, interpret = resolve_kernel(kernel)
    if not use_kernel:
        return prefill_attention_ref(q, k_new, v_new, k_pool, v_pool,
                                     lengths, block_tables, start=start,
                                     prefix=prefix, kv_scales=kv_scales,
                                     kv_dtype=kv_dtype, layer=layer)
    B = q.shape[0]
    start_v = jnp.zeros((B,), jnp.int32) if start is None \
        else jnp.asarray(start, jnp.int32)
    return paged_flash_prefill(q, k_new, v_new, k_pool, v_pool, lengths,
                               block_tables, start_v, layer, prefix=prefix,
                               has_ctx=start is not None,
                               interpret=interpret, kv_scales=kv_scales,
                               kv_dtype=kv_dtype)


def _sharded_paged_prefill(q, k_new, v_new, k_pool, v_pool, lengths,
                           block_tables, layer, start, prefix, kernel,
                           kv_scales, kv_dtype, mesh):
    """shard_map chunked prefill over the mesh's ``model`` axis.

    Unlike the decode read, the pools are inputs AND outputs (the new-token
    scatter is fused into the call), so the pool/scale out_specs mirror the
    in_specs — each shard scatters its own Hk/m slice in place and the
    stitched result is exactly the single-device write-back.  The attn
    output is (B, S, H*D) head-major, so concatenating shards on the last
    axis restores full head order.  Tables, lengths, start and the layer
    index (scalar-prefetch operands) broadcast.
    """
    sp = sharding.paged_attn_specs()
    args = [q, k_new, v_new, k_pool, v_pool, lengths, block_tables,
            jnp.asarray(layer, jnp.int32)]
    in_specs = [sp["q_chunk"], sp["new_kv"], sp["new_kv"], sp["pool"],
                sp["pool"], sp["host"], sp["host"], sp["host"]]
    out_specs = [sp["out_chunk"], sp["pool"], sp["pool"]]
    has_start = start is not None
    if has_start:
        args.append(jnp.asarray(start, jnp.int32))
        in_specs.append(sp["host"])
    if kv_scales is not None:
        args += list(kv_scales)
        in_specs += [sp["scale"], sp["scale"]]
        out_specs += [sp["scale"], sp["scale"]]

    def body(q, k_new, v_new, k_pool, v_pool, lengths, tables, layer,
             *rest):
        rest = list(rest)
        start_s = rest.pop(0) if has_start else None
        return prefill_attention(q, k_new, v_new, k_pool, v_pool, lengths,
                                 tables, layer=layer, start=start_s,
                                 prefix=prefix, kernel=kernel,
                                 kv_scales=tuple(rest) or None,
                                 kv_dtype=kv_dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=tuple(out_specs), check_vma=False)(*args)
