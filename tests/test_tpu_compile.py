"""The paged kernels compile for a TPU v5e at tinyllama-1.1b widths.

Interpret mode (every other kernel test) runs the kernels' math but not the
TPU lowering, so it cannot see a block shape or a layout the chip's
compiler refuses.  These tests compile ahead of time against a described
``v5e:2x2`` topology — no chip attached, nothing runs — at H=32 query
heads, Hk=4 kv heads, head_dim 64, 16-token blocks, for the bf16 and the
int8 SCLAD pool, plus the ``shard_map`` decode over four chips.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import ops as decode_ops
from repro.kernels.flash_decode.flash_decode import paged_flash_decode
from repro.kernels.flash_prefill.flash_prefill import paged_flash_prefill

H, HK, D, BS, N, T = 32, 4, 64, 16, 1025, 32
ROWS, CHUNK = 4, 128
PAYLOAD = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]


def _pool(sharding, kv_dtype):
    """(k_pool, v_pool, kv_scales) shapes of one layer's pool."""
    k, v = _shapes(sharding, *[((N, BS, HK, D), PAYLOAD[kv_dtype])] * 2)
    if kv_dtype == "bf16":
        return k, v, None
    return k, v, tuple(_shapes(sharding, *[((N, BS, HK), jnp.float32)] * 2))


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_decode_compiles_for_v5e(one_chip, kv_dtype):
    k, v, scales = _pool(one_chip, kv_dtype)
    q, lens, tables = _shapes(one_chip, ((16, H, D), jnp.bfloat16),
                              ((16,), jnp.int32), ((16, T), jnp.int32))
    _compiled_text(
        lambda q, k, v, n, bt, sc: paged_flash_decode(q, k, v, n, bt,
                                                      kv_scales=sc),
        q, k, v, lens, tables, scales)


@pytest.mark.parametrize("has_ctx", [False, True],
                         ids=["first_chunk", "continuation"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_prefill_compiles_for_v5e(one_chip, kv_dtype, has_ctx):
    k, v, scales = _pool(one_chip, kv_dtype)
    q, kn, vn, lens, tables, start = _shapes(
        one_chip, ((ROWS, CHUNK, H, D), jnp.bfloat16),
        ((ROWS, CHUNK, HK, D), jnp.bfloat16),
        ((ROWS, CHUNK, HK, D), jnp.bfloat16), ((ROWS,), jnp.int32),
        ((ROWS, T), jnp.int32), ((ROWS,), jnp.int32))
    quant = None if kv_dtype == "bf16" else kv_dtype
    _compiled_text(
        lambda *a: paged_flash_prefill(*a[:8], has_ctx=has_ctx,
                                       kv_scales=a[8], kv_dtype=quant),
        q, kn, vn, k, v, lens, tables, start, scales)


def test_sharded_paged_decode_compiles_for_4_chips(topo, monkeypatch):
    # The dispatch picks interpret mode off-TPU; this process is on the
    # CPU, so steer it to the Mosaic kernel the chip would run.
    monkeypatch.setattr(decode_ops, "resolve_kernel",
                        lambda kernel="auto": (True, False))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))

    def placed(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    q = placed((16, H, D), jnp.bfloat16, P(None, "model", None))
    k, v = [placed((N, BS, HK, D), jnp.bfloat16,
                   P(None, None, "model", None))] * 2
    lens = placed((16,), jnp.int32, P())
    tables = placed((16, T), jnp.int32, P())
    text = _compiled_text(
        lambda q, k, v, n, bt: decode_ops.decode_attention(
            q, k, v, n, block_tables=bt, kernel="on", mesh=mesh),
        q, k, v, lens, tables)
    # Each chip's kernel reads its own one-kv-head slice of the pool.
    assert f"bf16[{N},{BS},{HK // 4},{D}]" in text
