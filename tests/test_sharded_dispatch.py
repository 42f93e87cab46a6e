"""shard_map'd paged attention over the ``model`` mesh axis (PR 9 rung 1).

Parity contract: sharding the paged KV pool's kv-head axis (payload AND
SCLAD scale leaves) changes NO arithmetic — attention is independent per
KV head, so each shard reads its contiguous Hk/m pool slice with its
matching query head group and outputs concat back on the head axis (the
pool is lane-dense, so a shard's heads are a contiguous lane range).
Pinned here at three levels:

  * kernel level — ``decode_attention`` / ``prefill_attention`` with a
    (1, m) mesh vs meshless, fp and int8-SCLAD pools, kernel on and off:
    bitwise-equal outputs in float32, the shared ``tol(dtype)`` envelope
    for bf16, and bitwise-equal pool/scale write-back for prefill;
  * engine level — the full serving matrix (dense/moe/vlm x prefix
    on/off x chunked prefill x int8 SCLAD) under 2- and 4-way meshes:
    float32 params (bf16 TP psum reduction order flips greedy near-ties,
    see the probe docstrings), greedy tokens EXACT and scheduler
    invariants (preemptions, admissions, cached tokens) bitwise equal;
  * lowering level — the compiled sharded decode step never all-gathers
    the pool (``roofline.parse_collectives`` HLO regression), and
    ``cache_specs(paged=True)`` co-shards payload and scale leaves on
    the same head axis for EVERY kv_dtype (pure-spec, no devices), with
    ``copy_cache_block`` COW preserving placement.

Multi-device cases force host devices; under a stock single-device
session they SKIP (CI runs this file with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import get_config
from repro.core import roofline
from repro.kernels.flash_decode import ops as decode_ops
from repro.kernels.flash_prefill import ops as prefill_ops
from repro.models import kv_quant
from repro.models import model as M
from repro.parallel import sharding
from repro.serving.engine import ServingEngine

needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")
needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=4)")


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _mesh(m):
    devs = np.array(jax.devices()[:m]).reshape(1, m)
    return Mesh(devs, ("data", "model"))


#: Pool layers, and the layer the kernel-level cases read and write.
LAYERS, LAYER = 2, 1


def _paged_inputs(rng_seed, B, H, Hk, D, N, bs, dtype=jnp.float32,
                  quantized=False):
    """A stacked lane-dense (LAYERS, N, bs, Hk*D) pool and its inputs."""
    ks = jax.random.split(jax.random.PRNGKey(rng_seed), 5)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    rows = (LAYERS, N, bs, Hk * D)
    if quantized:
        kc = jax.random.randint(ks[1], rows, -127, 128, jnp.int8)
        vc = jax.random.randint(ks[2], rows, -127, 128, jnp.int8)
        scales = (jax.random.uniform(ks[3], rows[:3] + (Hk,), jnp.float32,
                                     0.01, 0.1),
                  jax.random.uniform(ks[4], rows[:3] + (Hk,), jnp.float32,
                                     0.01, 0.1))
    else:
        kc = jax.random.normal(ks[1], rows).astype(dtype)
        vc = jax.random.normal(ks[2], rows).astype(dtype)
        scales = None
    # Non-overlapping per-row tables walking the whole pool.
    T = N // B
    tables = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    lengths = jnp.arange(1, B + 1, dtype=jnp.int32) * bs - 1
    return q, kc, vc, lengths, tables, scales


# ---------------------------------------------------------------------------
# Placement/dispatch gate
# ---------------------------------------------------------------------------

@needs2
def test_attn_shard_size_matches_sanitize_gate():
    """The dispatch gate and the pool's placement must agree: shard
    exactly when the mesh has a model axis >1 that divides Hk — also where
    the merged Hk*D lane axis would divide it."""
    mesh = _mesh(2)
    assert sharding.attn_shard_size(None, 4) == 1
    assert sharding.attn_shard_size(mesh, 4) == 2
    assert sharding.attn_shard_size(mesh, 3) == 1  # 3 % 2 != 0 -> solo
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              num_heads=3, num_kv_heads=3)
    cache = M.init_paged_cache(cfg, 2, 8)
    assert cache["k"].shape[-1] % 2 == 0  # 3 heads x 16 lanes
    with sharding.use_axes(mesh):
        spec = sharding.sanitize_specs(
            sharding.cache_specs(cfg, cache, None, 1, paged=True), cache)
    assert spec["k"][3] is None  # placement drops it for the same Hk


def test_paged_attn_specs_shapes():
    sp = sharding.paged_attn_specs()
    # Kernels take the whole stacked (L, N, bs, Hk*D) pool: heads are
    # contiguous lane ranges of its last axis, as in cache_specs(paged=True).
    assert sp["pool"] == P(None, None, None, "model")
    assert sp["scale"] == P(None, None, None, "model")
    assert sp["q_decode"] == P(None, "model", None)
    assert sp["host"] == P()


# ---------------------------------------------------------------------------
# Kernel-level parity: decode
# ---------------------------------------------------------------------------

@needs2
@pytest.mark.parametrize("kernel", ["off", "on"])
@pytest.mark.parametrize("quantized", [False, True])
def test_sharded_decode_matches_single(kernel, quantized):
    q, kc, vc, lengths, tables, scales = _paged_inputs(
        0, B=2, H=8, Hk=4, D=16, N=8, bs=8, quantized=quantized)
    solo = decode_ops.decode_attention(
        q, kc, vc, lengths, block_tables=tables, layer=LAYER, kernel=kernel,
        kv_scales=scales, mesh=None)
    shard = decode_ops.decode_attention(
        q, kc, vc, lengths, block_tables=tables, layer=LAYER, kernel=kernel,
        kv_scales=scales, mesh=_mesh(2))
    # float32 per-head math is untouched by the split: bitwise equal.
    np.testing.assert_array_equal(np.asarray(solo), np.asarray(shard))


@needs2
def test_sharded_decode_bf16_within_kernel_tolerance():
    q, kc, vc, lengths, tables, _ = _paged_inputs(
        1, B=2, H=4, Hk=2, D=16, N=8, bs=8, dtype=jnp.bfloat16)
    solo = decode_ops.decode_attention(q, kc, vc, lengths,
                                       block_tables=tables, layer=LAYER,
                                       mesh=None)
    shard = decode_ops.decode_attention(q, kc, vc, lengths,
                                        block_tables=tables, layer=LAYER,
                                        mesh=_mesh(2))
    np.testing.assert_allclose(
        np.asarray(solo, np.float32), np.asarray(shard, np.float32),
        atol=tol(jnp.bfloat16), rtol=tol(jnp.bfloat16))


@needs2
def test_indivisible_heads_fall_back_to_single_path():
    """Hk=3 on a 2-way mesh: the gate must route to the plain path (and
    produce the same numbers), never crash inside shard_map."""
    q, kc, vc, lengths, tables, _ = _paged_inputs(
        2, B=1, H=3, Hk=3, D=8, N=4, bs=8)
    solo = decode_ops.decode_attention(q, kc, vc, lengths,
                                       block_tables=tables, layer=LAYER,
                                       mesh=None)
    shard = decode_ops.decode_attention(q, kc, vc, lengths,
                                        block_tables=tables, layer=LAYER,
                                        mesh=_mesh(2))
    np.testing.assert_array_equal(np.asarray(solo), np.asarray(shard))


# ---------------------------------------------------------------------------
# Kernel-level parity: chunked prefill (pools are inputs AND outputs)
# ---------------------------------------------------------------------------

@needs2
@pytest.mark.parametrize("kernel", ["off", "on"])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("continuation", [False, True])
def test_sharded_prefill_matches_single(kernel, quantized, continuation):
    B, S, H, Hk, D, N, bs = 2, 8, 4, 2, 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k_new = jax.random.normal(ks[1], (B, S, Hk, D))
    v_new = jax.random.normal(ks[2], (B, S, Hk, D))
    _, kp, vp, _, tables, scales = _paged_inputs(
        4, B=B, H=H, Hk=Hk, D=D, N=N, bs=bs, quantized=quantized)
    lengths = jnp.array([S, S - 3], jnp.int32)
    start = jnp.array([bs, bs], jnp.int32) if continuation else None
    kv_dtype = "int8" if quantized else None
    kw = dict(layer=LAYER, start=start, kernel=kernel, kv_scales=scales,
              kv_dtype=kv_dtype)
    solo = prefill_ops.prefill_attention(
        q, k_new, v_new, kp, vp, lengths, tables, mesh=None, **kw)
    shard = prefill_ops.prefill_attention(
        q, k_new, v_new, kp, vp, lengths, tables, mesh=_mesh(2), **kw)
    assert len(solo) == len(shard) == (5 if quantized else 3)
    # Output AND every written-back pool/scale leaf: bitwise equal — each
    # shard scatters its own Hk/m slice and the stitch is the solo write.
    for a, b in zip(solo, shard):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Engine-level parity: the serving matrix under 2- and 4-way meshes
# ---------------------------------------------------------------------------

def _f32_params(cfg, seed=0):
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        M.init_params(cfg, jax.random.PRNGKey(seed)))


def _engine_run(cfg, params, mesh, reqs, prefix_cache):
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32,
                        mode="continuous", mesh=mesh, block_size=8,
                        prefill_chunk=8, prefix_cache=prefix_cache,
                        eos_id=-1, seed=5)
    for p, m, pe in reqs:
        eng.submit(p, max_new_tokens=m, patch_embeds=pe)
    out = eng.run()
    s = eng.stats
    return out, (s.preemptions, s.admissions, s.cached_prompt_tokens,
                 s.prefill_tokens, s.generated_tokens,
                 s.prefix_hit_rate)


def _matrix_reqs(cfg, arch, n=4):
    rng = np.random.default_rng(17)
    system = rng.integers(1, cfg.vocab_size, size=9)
    pe = None
    if arch == "internvl2-26b":
        pe = rng.normal(size=(cfg.num_patches, cfg.d_model)) \
                .astype(np.float32)
    reqs = []
    for i in range(n):
        tail = rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(3, 8)))
        p = np.concatenate([system, tail]) if i % 2 == 0 else tail
        reqs.append((p, 3, pe))
    return reqs


@needs2
@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b",
                                  "internvl2-26b"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_engine_sharded_matrix_2way(arch, kv_dtype, prefix_cache):
    cfg = get_config(arch).reduced()
    if kv_dtype != "fp":
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    params = _f32_params(cfg)
    reqs = _matrix_reqs(cfg, arch)
    solo_out, solo_sched = _engine_run(cfg, params, None, reqs,
                                       prefix_cache)
    shard_out, shard_sched = _engine_run(cfg, params, _mesh(2), reqs,
                                         prefix_cache)
    assert shard_out == solo_out, "sharded dispatch changed greedy tokens"
    assert shard_sched == solo_sched, (
        "sharded dispatch changed scheduling invariants")


@needs4
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_engine_sharded_4way(kv_dtype):
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              num_heads=4, num_kv_heads=4)
    if kv_dtype != "fp":
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    params = _f32_params(cfg)
    reqs = _matrix_reqs(cfg, "tinyllama-1.1b")
    solo_out, solo_sched = _engine_run(cfg, params, None, reqs, True)
    shard_out, shard_sched = _engine_run(cfg, params, _mesh(4), reqs, True)
    assert shard_out == solo_out
    assert shard_sched == solo_sched


# ---------------------------------------------------------------------------
# Lowering regression: the pool is never all-gathered on the hot path
# ---------------------------------------------------------------------------

@needs2
def test_sharded_decode_never_allgathers_pool():
    mesh = _mesh(2)
    q, kc, vc, lengths, tables, _ = _paged_inputs(
        6, B=2, H=8, Hk=4, D=32, N=16, bs=8)

    def step(q, kc, vc, lengths, tables):
        return decode_ops.decode_attention(q, kc, vc, lengths,
                                           block_tables=tables, layer=LAYER,
                                           mesh=mesh)

    hlo = jax.jit(step).lower(q, kc, vc, lengths, tables) \
        .compile().as_text()
    stats = roofline.parse_collectives(hlo, total_devices=2)
    pool_bytes = int(np.prod(kc.shape)) * kc.dtype.itemsize
    ag = stats.by_op.get("all-gather", [0, 0, 0])
    # The read path needs NO pool-sized collective: each shard owns its
    # head slice.  Anything all-gather-shaped must be far below one pool
    # leaf (e.g. the (B, H, D) output stitch, if XLA emits one at all).
    assert ag[1] < pool_bytes / 2, (
        f"sharded decode all-gathered ~pool bytes ({ag[1]} vs pool "
        f"{pool_bytes})")


# ---------------------------------------------------------------------------
# cache_specs(paged=True) co-sharding + COW placement (pure-spec + 2-dev)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", list(kv_quant.KV_DTYPES))
def test_cache_specs_cosharded_payload_and_scales(kv_dtype):
    """For every pool encoding, payload leaves shard the KV-head axis
    over ``model`` and (when present) scale leaves shard the SAME axis —
    so a shard always dequantizes locally.  No devices needed."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              kv_dtype=kv_dtype)
    cache = M.init_paged_cache(cfg, 2, 8)
    specs = sharding.cache_specs(cfg, cache, None, 1, paged=True)
    assert specs["k"] == P(None, None, None, "model")
    assert specs["v"] == specs["k"]
    if kv_quant.is_quantized(kv_dtype):
        assert set(cache) == {"k", "v", "k_scale", "v_scale"}
        assert specs["k_scale"] == P(None, None, None, "model")
        assert specs["v_scale"] == specs["k_scale"]
        # Head axis position: payload axis 3 == scale axis 3.
        assert specs["k"][3] == specs["k_scale"][3] == "model"
    else:
        assert set(cache) == {"k", "v"}


@needs2
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_copy_cache_block_preserves_sharding(kv_dtype):
    """COW (ensure_writable's device half) must keep every leaf — payload
    and scales — on its original sharding: a COW event that silently
    replicated the pool would wreck the next sharded step's placement."""
    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              kv_dtype=kv_dtype)
    mesh = _mesh(2)
    cache = M.init_paged_cache(cfg, 4, 8, mesh=mesh)
    # Distinct payload per block so the copy is observable.
    cache = jax.tree.map(
        lambda x: (jnp.arange(x.size, dtype=jnp.float32)
                   .reshape(x.shape).astype(x.dtype)), cache)
    cache = jax.device_put(cache, jax.tree.map(
        lambda x: x.sharding, M.init_paged_cache(cfg, 4, 8, mesh=mesh)))
    before = jax.tree.map(lambda x: x.sharding, cache)
    out = M.copy_cache_block(cache, 2, 1)
    for key in cache:
        assert out[key].sharding.is_equivalent_to(
            before[key], out[key].ndim), f"{key} lost its sharding"
        np.testing.assert_array_equal(np.asarray(out[key][:, 1]),
                                      np.asarray(cache[key][:, 2]))
        np.testing.assert_array_equal(np.asarray(out[key][:, 3]),
                                      np.asarray(cache[key][:, 3]))
