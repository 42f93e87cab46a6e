"""SCLD matmul: Store-as-Compressed, Load-as-Dense weights (paper §3.2).

TPU adaptation of the CC-MEM compression decoder.  The paper's ASIC decodes
an element-wise tile-CSR format in dedicated hardware next to each SRAM bank
group; a TPU has no such decoder and VMEM wants >= (8, 128) granularity, so
the format here is *block* SCLD:

  * W (K, N) is partitioned into MXU tiles of (128, 128); each tile is
    16 row-units of (8, 128).
  * Store-as-compressed: each tile keeps only its C nonzero row-units
    (values (C, 8, 128) + unit row indices), N:M-style uniform so shapes are
    static.  HBM traffic per tile is C/16 of dense.
  * Load-as-dense: the kernel decodes the units into a dense (128, 128) VMEM
    scratch tile, then issues a dense MXU matmul — compute stays entirely
    sparsity-agnostic, exactly the paper's contract.

Grid: (M/bm, N/bn, K/128), K innermost; accumulation in an f32 VMEM scratch
that is flushed to the output on the last K step.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

UNIT_R = 8  # row-unit height (TPU sublane granularity)
TILE = 128  # MXU tile edge
UNITS_PER_TILE = TILE // UNIT_R  # 16


def _sclad_kernel(x_ref, vals_ref, rows_ref, o_ref, w_scratch, acc_scratch,
                  *, n_k: int):
    """x_ref: (bm, 128); vals_ref: (C, 8, 128); rows_ref: (C,) int32;
    o_ref: (bm, bn=128); scratch: w (128,128), acc (bm, 128) f32."""
    C = vals_ref.shape[0]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    # Load-as-dense: decode the C stored row-units into a dense VMEM tile.
    w_scratch[...] = jnp.zeros_like(w_scratch)
    for c in range(C):  # C is static (uniform N:M block compression)
        r = rows_ref[c]
        w_scratch[pl.ds(r * UNIT_R, UNIT_R), :] = \
            vals_ref[c].astype(w_scratch.dtype)

    # Dense MXU matmul on the decoded tile — compute is sparsity-agnostic.
    x = x_ref[...]
    acc_scratch[...] += jax.lax.dot(
        x, w_scratch[...].astype(x.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[...] = acc_scratch[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def sclad_matmul(x, vals, rows, *, block_m: int = 128,
                 interpret: bool = False):
    """y = x @ decode(vals, rows).

    x:    (M, K)
    vals: (K//128, N//128, C, 8, 128) — stored nonzero row-units
    rows: (K//128, N//128, C) int32  — unit row index within the tile
    Returns (M, N).
    """
    M, K = x.shape
    nk, nn, C = vals.shape[:3]
    N = nn * TILE
    assert K == nk * TILE and M % block_m == 0

    grid = (M // block_m, nn, nk)

    return pl.pallas_call(
        functools.partial(_sclad_kernel, n_k=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, TILE), lambda i, j, k: (i, k)),
            pl.BlockSpec((None, None, C, UNIT_R, TILE),
                         lambda i, j, k: (k, j, 0, 0, 0)),
            pl.BlockSpec((None, None, C), lambda i, j, k: (k, j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, TILE), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            # dense decode tile + accumulator, VMEM-resident
            pltpu.VMEM((TILE, TILE), jnp.float32),
            pltpu.VMEM((block_m, TILE), jnp.float32),
        ],
        interpret=interpret,
    )(x, vals, rows)


# ---------------------------------------------------------------------------
# Block compression (encode side of SCLD)
# ---------------------------------------------------------------------------

def block_compress(w: np.ndarray, units_kept: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Uniform N:M block pruning + compression.

    Keeps the `units_kept` largest-magnitude (8, 128) row-units per (128,128)
    tile.  Returns (vals (nk, nn, C, 8, 128), rows (nk, nn, C) int32).
    """
    K, N = w.shape
    assert K % TILE == 0 and N % TILE == 0
    nk, nn = K // TILE, N // TILE
    C = units_kept
    tiles = w.reshape(nk, TILE, nn, TILE).transpose(0, 2, 1, 3)
    units = tiles.reshape(nk, nn, UNITS_PER_TILE, UNIT_R, TILE)
    mag = np.abs(units).sum(axis=(-1, -2))  # (nk, nn, 16)
    order = np.argsort(-mag, axis=-1)[..., :C]  # top-C units
    rows = np.sort(order, axis=-1).astype(np.int32)
    vals = np.take_along_axis(units, rows[..., None, None], axis=2)
    return vals.astype(w.dtype), rows


def decompress(vals: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Inverse of block_compress (zero-filled)."""
    nk, nn, C = vals.shape[:3]
    units = np.zeros((nk, nn, UNITS_PER_TILE, UNIT_R, TILE), vals.dtype)
    np.put_along_axis(units, rows[..., None, None], vals, axis=2)
    tiles = units.reshape(nk, nn, TILE, TILE).transpose(0, 2, 1, 3)
    return tiles.reshape(nk * TILE, nn * TILE)
