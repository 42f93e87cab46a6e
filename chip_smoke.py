"""Chip smoke: serve full-width tinyllama-1.1b on a TPU through the paged
Pallas kernels, and check what comes out.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # tensor parallelism and replicas

Run from the repository root on a machine whose JAX sees a TPU.  Weights
are random, made from ``--seed``; nothing is read from outside the
checkout.  The persistent compile cache goes to ``JAX_COMPILATION_CACHE_DIR``
when set, else to ``<checkout>/.jax_cache``.

One chip: a continuous-batching ``ServingEngine`` (kernels forced on,
16 lanes, 16-token blocks, 128-token prefill chunks, prefix cache on, a
4096-block pool) serves 8 prompts of 136-384 tokens, half of them sharing
a 128-token prefix, 32 greedy tokens each — closed-loop, then open-loop
through ``run_open_loop``, then closed-loop again on the int8 SCLAD pool.
Then the model steps (first chunk, cached-context continuation chunk,
decode steps) run on the kernel path and on the jnp reference path
(``attn_kernel="off"``) for the bf16 and the int8 pool: logits must agree
within ``LOGIT_TOL`` and the pool the prefill kernel wrote must equal the
reference scatter bit for bit.

``--chips 4`` runs only (a) the engine on a (1, 4) ("data", "model") mesh,
one kv head per chip, against one chip, and (b) four one-chip replicas
behind the prefix-affinity router against solo runs of the same cohorts.

Earlier lines are informational.  The last line is the JSON verdict
``{"ok": true, "device": {...}}``; any failed phase raises instead, so a
run that does not end in it failed.  There is no CPU fallback: without a
TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.base import get_config  # noqa: E402
from repro.kernels.flash_decode.ops import resolve_kernel  # noqa: E402
from repro.kernels.flash_prefill.ops import prefill_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import engine_mesh, replica_meshes  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.parallel import sharding  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402
from repro.serving.openloop import TraceItem, run_open_loop  # noqa: E402
from repro.serving.router import run_open_loop_router  # noqa: E402
from repro.serving.warmup import warmup_prefill  # noqa: E402

ARCH = "tinyllama-1.1b"
LANES, BLOCK, CHUNK, MAX_LEN, POOL_BLOCKS = 16, 16, 128, 512, 4096
PREFIX = 128
#: Group A: fresh prompts, three 128-token chunks each (the first one
#: starts with the shared prefix and donates it); group B: the shared
#: prefix plus a tail, served from the prefix cache as one continuation
#: chunk.  Every cohort pads to the 128 bucket, so one first-chunk and one
#: continuation shape cover the closed loop.
A_LENS = (264, 300, 340, 384)
B_TAILS = (8, 48, 96, 128)
MAX_NEW = 32
#: Kernel vs reference (and tensor-parallel vs one chip) logits: relative
#: RMS error bound.  The two paths round at different points — the
#: reference rounds scores and probabilities to bf16, the kernels keep
#: fp32 scores; tensor parallelism sums partial products in another
#: order.  bf16 keeps 8 significand bits (2^-8 = 0.4%), and a 22-layer
#: bf16 residual stream compounds about two roundings per layer as a
#: random walk: sqrt(44) * 0.4% = 2.6%.  0.1 leaves about 4x headroom over
#: that, while a wrong block, head or stale read moves whole rows by O(1).
LOGIT_TOL = 0.1
#: Model-step probe: 2 rows on a 64-block pool (block 0 is trash).
PROBE_BLOCKS, PROBE_TABLE = 64, 16
PROBE_FIRST, PROBE_CONT, PROBE_DECODE = (100, 128), (96, 70), 4


def log(**kw) -> None:
    print("chip_smoke: " + json.dumps(kw, sort_keys=True), flush=True)


def prompts(vocab: int, seed: int):
    """Group A and group B prompts (see ``A_LENS``/``B_TAILS``)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, PREFIX)
    a = [rng.integers(1, vocab, n) for n in A_LENS]
    a[0][:PREFIX] = prefix
    b = [np.concatenate([prefix, rng.integers(1, vocab, n)])
         for n in B_TAILS]
    return a, b


def make_engine(cfg, params, mesh=None, prefix_cache=True, **kw):
    return ServingEngine(cfg, params, max_batch=LANES, max_len=MAX_LEN,
                         eos_id=-1, mode="continuous", mesh=mesh,
                         block_size=BLOCK, num_blocks=POOL_BLOCKS,
                         prefill_chunk=CHUNK, prefix_cache=prefix_cache,
                         attn_kernel="on", **kw)


def warm(engine, vocab: int, group_sizes, prompt_len: int = 200) -> float:
    """Compile the first-chunk, continuation and decode steps for these
    admission group sizes; returns the seconds it took."""
    t0 = time.perf_counter()
    warmup_prefill(engine, vocab, prompt_lens=(prompt_len,),
                   group_sizes=group_sizes)
    return time.perf_counter() - t0


def serve_closed(engine, a, b):
    """Group A, then (once A's three chunks are in) group B, drained
    closed-loop; returns the outputs in submission order."""
    done = {}
    uids = [engine.submit(p, MAX_NEW) for p in a]
    chunks = engine.stats.prefill_chunks
    while engine.stats.prefill_chunks < chunks + 3:
        done.update(engine.step())
    uids += [engine.submit(p, MAX_NEW) for p in b]
    done.update(engine.run())
    return [done[u] for u in uids]


def agreement(xs, ys) -> float:
    same = sum(int(x == y) for s, t in zip(xs, ys) for x, y in zip(s, t))
    return same / max(sum(len(s) for s in xs), 1)


def serve_one_chip(cfg, params, seed: int) -> None:
    vocab = cfg.vocab_size
    a, b = prompts(vocab, seed)
    eng = make_engine(cfg, params)
    log(phase="fp.warmup", compile_and_run_s=warm(eng, vocab, (4,)))
    out = serve_closed(eng, a, b)
    s = eng.stats
    assert all(len(o) == MAX_NEW for o in out), [len(o) for o in out]
    assert s.cached_prompt_tokens >= len(b) * PREFIX, s.cached_prompt_tokens
    log(phase="fp.closed_loop", requests=len(out),
        generated_tokens=s.generated_tokens, prefill_chunks=s.prefill_chunks,
        cached_prompt_tokens=s.cached_prompt_tokens,
        preemptions=s.preemptions)

    # Open loop over the same prompts, all arriving at once: every prompt
    # is now cached but for its last block, so the 8 tails are one
    # (8, 16) continuation cohort — warmed here first.
    log(phase="fp.warmup_open_loop",
        compile_and_run_s=warm(eng, vocab, (8,), PREFIX + BLOCK))
    trace = [TraceItem(arrival_s=0.0, prompt=p, max_new_tokens=MAX_NEW)
             for p in a + b]
    rep = run_open_loop(eng, trace)
    got = [r.tokens for r in rep.records]
    assert rep.count("completed") == len(trace), rep.summary(1.0)
    assert all(len(t) == MAX_NEW for t in got)
    log(phase="fp.open_loop", completed=rep.count("completed"),
        greedy_agreement_vs_closed=agreement(got, out),
        cached_prompt_tokens=eng.stats.cached_prompt_tokens)
    del eng, rep  # the report's frontend holds the engine and its pool

    eng = make_engine(cfg, params, kv_dtype="int8")
    log(phase="int8.warmup", compile_and_run_s=warm(eng, vocab, (4,)))
    out8 = serve_closed(eng, a, b)
    assert all(len(o) == MAX_NEW for o in out8)
    assert eng.stats.cached_prompt_tokens >= len(b) * PREFIX
    log(phase="int8.closed_loop", requests=len(out8),
        generated_tokens=eng.stats.generated_tokens,
        kv_block_bytes=eng.kv_block_bytes,
        greedy_agreement_vs_fp=agreement(out8, out))


def probe_tables():
    """Two rows of 15 blocks each; the last table entry is the trash
    block (0), as for an engine lane's unallocated tail."""
    tables = np.zeros((2, PROBE_TABLE), np.int32)
    for r in range(2):
        tables[r, :PROBE_TABLE - 1] = 1 + r * (PROBE_TABLE - 1) \
            + np.arange(PROBE_TABLE - 1)
    return tables


def model_steps(cfg, params, mesh=None, seed: int = 0):
    """First chunk, cached-context continuation chunk and decode steps of
    the model on a small paged pool, each step compiled ahead of time.

    Row 0's continuation starts mid-block (100 % 16), so the prefill
    kernel's write-back straddles a block it also read as context; both
    rows' tables end in the trash block.  Returns the logits after each
    step, the compiled step texts and their compile seconds."""
    rng = np.random.default_rng(seed)
    vocab, rows, width = cfg.vocab_size, 2, CHUNK
    tables = probe_tables()

    def chunk(lens):
        t = np.zeros((rows, width), np.int32)
        for r, n in enumerate(lens):
            t[r, width - n:] = rng.integers(1, vocab, n)
        return t, np.asarray(lens, np.int32)

    def scoped(fn):
        def body(*args):
            with sharding.use_axes(mesh):
                return fn(*args)
        return jax.jit(body)

    steps = {
        "prefill_first": scoped(lambda p, c, t, n, bt: M.prefill_slots(
            cfg, p, c, t, n, bt, mesh=mesh)),
        "prefill_cont": scoped(lambda p, c, t, n, bt, st: M.prefill_slots(
            cfg, p, c, t, n, bt, start=st, mesh=mesh)),
        "decode": scoped(lambda p, c, t, pos, bt: M.decode_step(
            cfg, p, c, t, pos, block_tables=bt, mesh=mesh)),
    }
    res = {"logits": [], "hlo": {}, "compile_s": {}}
    compiled = {}

    def call(name, *args):
        if name not in compiled:
            t0 = time.perf_counter()
            compiled[name] = steps[name].lower(*args).compile()
            res["compile_s"][name] = time.perf_counter() - t0
            res["hlo"][name] = compiled[name].as_text()
        return compiled[name](*args)

    cache = M.init_paged_cache(cfg, PROBE_BLOCKS + 1, BLOCK, mesh=mesh)
    tok, n = chunk(PROBE_FIRST)
    logits, cache = call("prefill_first", params, cache, tok, n, tables)
    res["logits"].append(np.asarray(logits, np.float32))
    tok, n = chunk(PROBE_CONT)
    start = np.asarray(PROBE_FIRST, np.int32)
    logits, cache = call("prefill_cont", params, cache, tok, n, tables,
                         start)
    res["logits"].append(np.asarray(logits, np.float32))
    pos = start + n
    for _ in range(PROBE_DECODE):
        tok = rng.integers(1, vocab, (rows, 1)).astype(np.int32)
        logits, cache = call("decode", params, cache, tok, pos, tables)
        res["logits"].append(np.asarray(logits[:, 0], np.float32))
        pos = pos + 1
    return res


def compare_logits(got, want, label: str) -> None:
    stages = ["first_chunk", "continuation"] + [
        f"decode{i}" for i in range(PROBE_DECODE)]
    pairs = list(zip(got["logits"], want["logits"]))
    assert len(pairs) == len(stages)
    for stage, (x, y) in zip(stages, pairs):
        assert np.isfinite(x).all() and x.shape == y.shape, (label, stage)
    errs = [float(np.sqrt(np.mean((x - y) ** 2) / np.mean(y ** 2)))
            for x, y in pairs]
    log(phase=label, rel_rms_err=dict(zip(stages, errs)), tol=LOGIT_TOL,
        max_abs_logit_diff=max(float(np.abs(x - y).max()) for x, y in pairs),
        greedy_agreement=float(np.mean([
            np.argmax(x, -1) == np.argmax(y, -1) for x, y in pairs])))
    assert max(errs) <= LOGIT_TOL, (label, errs)


def pool_writes(cfg, kv_dtype: str, seed: int = 0):
    """Elements of a two-layer stacked pool whose bits differ between the
    prefill kernel and the reference scatter, both writing layer 1, after
    a first chunk and after a cached-context continuation (row 0 starting
    mid-block), both paths given the same materialized inputs and the same
    starting pool.  Layer 0 must come through untouched on both paths.
    Block 0, the trash block, is garbage by contract and left out."""
    quant = None if kv_dtype == "bf16" else kv_dtype
    stacked = M.init_paged_cache(
        replace(cfg, num_layers=2, kv_dtype=kv_dtype), PROBE_BLOCKS + 1,
        BLOCK)
    pool = tuple(stacked[k] for k in ("k", "v", "k_scale", "v_scale")
                 if k in stacked)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads, kv_heads, dim = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    tables = probe_tables()

    def chunk(ks):
        q, k, v = (jax.random.normal(key, (2, CHUNK, h, dim), jnp.bfloat16)
                   for key, h in zip(ks, (heads, kv_heads, kv_heads)))
        return q, k, v

    def write(kernel, pool, qkv, lens, start):
        def fn(pool, q, k, v, lens, start):
            return prefill_attention(
                q, k, v, pool[0], pool[1], lens, tables, layer=1,
                start=start, kernel=kernel, kv_scales=pool[2:] or None,
                kv_dtype=quant)[1:]
        return jax.jit(fn)(pool, *qkv, np.asarray(lens, np.int32), start)

    differ = {}
    first = chunk(keys[:3])
    out = {kernel: write(kernel, pool, first, PROBE_FIRST, None)
           for kernel in ("on", "off")}
    cont = chunk(keys[3:])
    start = np.asarray(PROBE_FIRST, np.int32)
    for after, res in (("first_chunk", out), ("continuation", {
            kernel: write(kernel, out["off"], cont, PROBE_CONT, start)
            for kernel in ("on", "off")})):
        for name, x, y in zip(("k", "v", "k_scale", "v_scale"),
                              res["on"], res["off"]):
            x, y = np.asarray(x)[:, 1:], np.asarray(y)[:, 1:]
            bits = f"u{x.itemsize}"
            differ[f"{after}.{name}"] = int(np.sum(
                x.view(bits) != y.view(bits)))
    return differ


def check_kernels(cfg, params, kv_dtype: str) -> None:
    """Kernel path vs jnp reference path: model-step logits, and the pool
    bytes the prefill kernel writes."""
    kern = model_steps(replace(cfg, attn_kernel="on", kv_dtype=kv_dtype),
                       params)
    ref = model_steps(replace(cfg, attn_kernel="off", kv_dtype=kv_dtype),
                      params)
    for name, text in kern["hlo"].items():
        assert "tpu_custom_call" in text, f"{name}: no Pallas kernel"
    for name, text in ref["hlo"].items():
        assert "tpu_custom_call" not in text, f"{name}: reference has one"
    differ = pool_writes(cfg, kv_dtype)
    log(phase=f"{kv_dtype}.pool_vs_reference_scatter",
        elements_differing=differ, compile_s=kern["compile_s"])
    compare_logits(kern, ref, f"{kv_dtype}.kernel_vs_reference")
    assert not any(differ.values()), differ


def run_one_chip(cfg, params, seed: int) -> None:
    assert resolve_kernel("on") == (True, False), resolve_kernel("on")
    serve_one_chip(cfg, params, seed)
    for kv_dtype in ("bf16", "int8"):
        check_kernels(cfg, params, kv_dtype)


def run_four_chips(cfg, params, seed: int) -> None:
    devices = jax.devices()[:4]
    assert len(devices) == 4, f"--chips 4 needs 4 devices, got {devices}"
    cfg = replace(cfg, attn_kernel="on")
    vocab = cfg.vocab_size
    a, b = prompts(vocab, seed)
    # Cohorts of two whose chunks all pad to the 128 bucket; the router
    # deals a burst to replicas in arrival order (least-loaded ties go to
    # the lowest index), so replica i serves pairs[i].
    arrivals = [a[0], a[1], b[0], b[1], a[2], a[3], b[2], b[3]]
    pairs = [arrivals[i::4] for i in range(4)]

    # Nothing is warmed here: no latency is read, and each engine compiles
    # its few step shapes on first use.
    def serve_pairs(engine):
        t0, out = time.perf_counter(), []
        for pair in pairs:
            uids = [engine.submit(p, MAX_NEW) for p in pair]
            done = engine.run()
            out.append([done[u] for u in uids])
        assert all(len(t) == MAX_NEW for o in out for t in o)
        return out, time.perf_counter() - t0

    # One chip: the solo reference for both comparisons.
    solo_out, secs = serve_pairs(make_engine(
        cfg, params, mesh=engine_mesh(devices[:1]), prefix_cache=False))
    log(phase="solo.serve", compile_and_run_s=secs)

    # (a) Tensor parallelism: one kv head per chip.
    mesh = engine_mesh(devices)
    tp = make_engine(cfg, params, mesh=mesh, prefix_cache=False)
    assert all(len(x.sharding.device_set) == 4
               for x in jax.tree.leaves(tp._cache) + jax.tree.leaves(
                   tp.params)), "pool or weights not on 4 chips"
    tp_out, secs = serve_pairs(tp)
    log(phase="tp.serve", compile_and_run_s=secs,
        generated_tokens=tp.stats.generated_tokens,
        greedy_agreement_vs_one_chip=agreement(sum(tp_out, []),
                                               sum(solo_out, [])))
    sharded = model_steps(cfg, tp.params, mesh=mesh)
    del tp
    shard_pool = f"[{cfg.num_layers},{PROBE_BLOCKS + 1},{BLOCK}," \
        f"{cfg.num_kv_heads * cfg.head_dim // 4}]"
    for name, text in sharded["hlo"].items():
        assert "tpu_custom_call" in text, f"{name}: no Pallas kernel"
        assert shard_pool in text, f"{name}: kernel not on a pool shard"
    compare_logits(sharded, model_steps(cfg, params),
                   "tp.logits_vs_one_chip")

    # (b) Replicas, each on its own chip.
    engines = [make_engine(cfg, params, mesh=m) for m in replica_meshes(4)]
    homes = [{d for x in jax.tree.leaves(e.params) for d in x.devices()}
             for e in engines]
    assert all(len(h) == 1 for h in homes), homes
    assert len(set().union(*homes)) == 4, homes
    trace = [TraceItem(arrival_s=0.0, prompt=p, max_new_tokens=MAX_NEW)
             for p in arrivals]
    t0 = time.perf_counter()
    rep, router = run_open_loop_router(engines, trace, policy="affinity")
    assert rep.count("completed") == len(trace), rep.summary(1.0)
    routed = [r.tokens for r in rep.records]
    same = [routed[i::4] == solo_out[i] for i in range(4)]
    log(phase="replicas.routed_vs_solo", identical=same,
        compile_and_run_s=time.perf_counter() - t0,
        per_replica_requests=router.stats.per_replica,
        devices=[str(d) for h in homes for d in h])
    assert router.stats.per_replica == [2, 2, 2, 2], router.stats
    assert all(same), "routed outputs differ from the solo runs"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 1
    log(phase="device", kind=dev.device_kind, count=len(jax.devices()),
        compile_cache=enable_compile_cache())
    cfg = get_config(ARCH)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    log(phase="model", arch=ARCH, layers=cfg.num_layers,
        d_model=cfg.d_model, params=int(sum(
            x.size for x in jax.tree.leaves(params))))
    if args.chips == 4:
        run_four_chips(cfg, params, args.seed)
    else:
        run_one_chip(cfg, params, args.seed)
    log(phase="memory",
        peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
