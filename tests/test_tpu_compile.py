"""The paged kernels and the engine's steps compile for a TPU v5e.

Interpret mode (every other kernel test) runs the kernels' math but not the
TPU lowering, so it cannot see a block shape or a layout the chip's
compiler refuses.  These tests compile ahead of time against a described
``v5e:2x2`` topology — no chip attached, nothing runs:

  * the kernels at tinyllama-1.1b widths (H=32 query heads, Hk=4 kv heads,
    head_dim 64, 16-token blocks) on the stacked lane-dense
    (L, N, bs, Hk*D) pool, for the bf16 and the int8 SCLAD pool, plus the
    ``shard_map`` decode over four chips;
  * the engine's decode window and prefill continuation step for a
    few-layer model at stablelm-1.6b widths (D=64) and granite-3-8b widths
    (D=128): the pool stays in place through the layer scan — no
    pool-sized copy, slice or update in the optimized HLO, and step
    temporaries under 5% of the pool;
  * the int8 pool's prefill kernel, which quantizes in VMEM, at those two
    configurations' widths and lane counts.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.flash_decode import ops as decode_ops
from repro.kernels.flash_decode.flash_decode import paged_flash_decode
from repro.kernels.flash_prefill import ops as prefill_ops
from repro.kernels.flash_prefill.flash_prefill import paged_flash_prefill
from repro.models import model as M
from repro.serving import engine as engine_mod

H, HK, D, BS, N, T, L = 32, 4, 64, 16, 1025, 32, 2
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
    """(k_pool, v_pool, kv_scales) shapes of an L-layer stacked pool."""
    k, v = _shapes(sharding, *[((L, N, BS, HK * D), PAYLOAD[kv_dtype])] * 2)
    if kv_dtype == "bf16":
        return k, v, None
    return k, v, tuple(_shapes(sharding,
                               *[((L, N, BS, HK), jnp.float32)] * 2))


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_decode_compiles_for_v5e(one_chip, kv_dtype):
    k, v, scales = _pool(one_chip, kv_dtype)
    q, lens, tables, layer = _shapes(
        one_chip, ((16, H, D), jnp.bfloat16), ((16,), jnp.int32),
        ((16, T), jnp.int32), ((), jnp.int32))
    _compiled_text(
        lambda q, k, v, n, bt, lyr, sc: paged_flash_decode(
            q, k, v, n, bt, lyr, kv_scales=sc),
        q, k, v, lens, tables, layer, scales)


@pytest.mark.parametrize("has_ctx", [False, True],
                         ids=["first_chunk", "continuation"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_flash_prefill_compiles_for_v5e(one_chip, kv_dtype, has_ctx):
    k, v, scales = _pool(one_chip, kv_dtype)
    q, kn, vn, lens, tables, start, layer = _shapes(
        one_chip, ((ROWS, CHUNK, H, D), jnp.bfloat16),
        ((ROWS, CHUNK, HK, D), jnp.bfloat16),
        ((ROWS, CHUNK, HK, D), jnp.bfloat16), ((ROWS,), jnp.int32),
        ((ROWS, T), jnp.int32), ((ROWS,), jnp.int32), ((), jnp.int32))
    quant = None if kv_dtype == "bf16" else kv_dtype
    _compiled_text(
        lambda *a: paged_flash_prefill(*a[:9], has_ctx=has_ctx,
                                       kv_scales=a[9], kv_dtype=quant),
        q, kn, vn, k, v, lens, tables, start, layer, scales)


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
    k, v = [placed((L, N, BS, HK * D), jnp.bfloat16,
                   P(None, None, None, "model"))] * 2
    lens = placed((16,), jnp.int32, P())
    tables = placed((16, T), jnp.int32, P())
    layer = placed((), jnp.int32, P())
    text = _compiled_text(
        lambda q, k, v, n, bt, lyr: decode_ops.decode_attention(
            q, k, v, n, block_tables=bt, layer=lyr, kernel="on", mesh=mesh),
        q, k, v, lens, tables, layer)
    # Each chip's kernel reads its own one-kv-head lane range of the pool.
    assert f"bf16[{L},{N},{BS},{HK * D // 4}]" in text


# ---------------------------------------------------------------------------
# The engine's steps keep the stacked pool in place
# ---------------------------------------------------------------------------

#: (config, layers, pool blocks, lanes): few-layer models at the benchmark
#: configurations' widths and pool sizes (stablelm: 32 kv heads of 64;
#: granite: 8 kv heads of 128).
STEP_CASES = {
    "stablelm-1.6b.d64": ("stablelm-1.6b", 4, 1537, 8),
    "granite-3-8b.d128": ("granite-3-8b", 2, 2305, 16),
}
POOL_MOVES = re.compile(
    r"=\s*\S+\s+(copy|dynamic-slice|dynamic-update-slice)\(")


def _pool_sized_moves(text, num_blocks, slab):
    """HLO lines that copy, slice or update a pool-sized array: a shape on
    the line has the pool's block count as a dim and at least one layer's
    slab of elements (the weights' per-layer slices have other dims)."""
    hits = []
    for line in text.splitlines():
        if not POOL_MOVES.search(line):
            continue
        for dims in re.findall(r"\b[a-z0-9]+\[([0-9,]+)\]", line):
            shape = [int(d) for d in dims.split(",")]
            if num_blocks in shape and np.prod(shape) >= slab:
                hits.append(line.strip()[:160])
                break
    return hits


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_int8_paged_flash_prefill_compiles_at_config_widths(one_chip, case):
    """The int8 pool's prefill kernel quantizes in VMEM: a 128-token
    continuation chunk of every lane fits the 16 MB of scoped VMEM at the
    configurations' widths."""
    arch, layers, num_blocks, lanes = STEP_CASES[case]
    cfg = get_config(arch)
    hk, d, chunk, width = cfg.num_kv_heads, cfg.head_dim, CHUNK, 4096 // BS
    k, v = _shapes(one_chip, *[((layers, num_blocks, BS, hk * d),
                                jnp.int8)] * 2)
    scales = tuple(_shapes(one_chip, *[((layers, num_blocks, BS, hk),
                                        jnp.float32)] * 2))
    q, kn, vn, lens, tables, start, layer = _shapes(
        one_chip, ((lanes, chunk, cfg.num_heads, d), jnp.bfloat16),
        ((lanes, chunk, hk, d), jnp.bfloat16),
        ((lanes, chunk, hk, d), jnp.bfloat16), ((lanes,), jnp.int32),
        ((lanes, width), jnp.int32), ((lanes,), jnp.int32), ((), jnp.int32))
    _compiled_text(
        lambda *a: paged_flash_prefill(*a[:9], kv_scales=a[9],
                                       kv_dtype="int8"),
        q, kn, vn, k, v, lens, tables, start, layer, scales)


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_engine_steps_keep_pool_in_place(one_chip, case, monkeypatch):
    arch, layers, num_blocks, lanes = STEP_CASES[case]
    cfg = dataclasses.replace(get_config(arch), num_layers=layers,
                              attn_kernel="on", kv_dtype="bf16")
    # The Mosaic kernels the chip runs, not interpret mode; and the
    # engine's jits donate the pool, as they do on the chip.
    kernel = lambda kernel="auto": (True, False)  # noqa: E731
    monkeypatch.setattr(decode_ops, "resolve_kernel", kernel)
    monkeypatch.setattr(prefill_ops, "resolve_kernel", kernel)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    with monkeypatch.context() as m:
        m.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
        eng = engine_mod.ServingEngine(
            cfg, params, mode="continuous", max_batch=lanes, max_len=4096,
            block_size=BS, num_blocks=4, prefill_chunk=CHUNK, eos_id=-1)
    pool = jax.eval_shape(lambda: M.init_paged_cache(cfg, num_blocks, BS))
    assert pool["k"].shape == (layers, num_blocks, BS,
                               cfg.num_kv_heads * cfg.head_dim)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in pool.values())
    slab = pool["k"].size // layers

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def ints(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    width = 4096 // BS
    steps = {
        "decode": eng._decode_fn.lower(
            placed(params), placed(pool),
            ints(lanes, cfg.vocab_size, dtype=jnp.bfloat16), ints(lanes),
            ints(lanes, dtype=bool), ints(lanes), ints(lanes, 2,
                                                       dtype=jnp.uint32),
            ints(lanes, width)),
        "prefill_cont": eng._prefill_cont.lower(
            placed(params), placed(pool), ints(2, CHUNK), ints(2),
            ints(2, width), ints(2)),
    }
    for name, lowered in steps.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text, name
        moves = _pool_sized_moves(text, num_blocks, slab)
        assert not moves, (name, moves)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.05 * pool_bytes, (name, temp, pool_bytes)
