#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json``, one seed, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

Run from the checkout's root on a machine whose JAX sees a TPU; without
one (or with fewer chips than the cell asks for) it exits non-zero and
prints no result.  The system under test is the program under ``src/``:
the async front door (``serving/frontend.py``) over one continuous-
batching ``ServingEngine`` with the paged Pallas kernels.

A run: make the configuration's weights on the device from the seed, build
the engine and front door from the configuration file, warm every prefill
and decode shape the cell's traffic can reach (set-up ends here), send the
mix's lead-in so that the window opens on a loaded engine, drive the
traffic for ``--seconds``, let the window's requests finish, read the
metrics over the window's requests, free the engine, and compare a sample
of them with the plain reference.  ``--trace 1`` records a profiler trace of the
traffic and prints the per-layer metrics instead of the end-to-end ones.

The last line of standard output is the JSON result; the numbers compared
for ``correct`` are the last lines of standard error and the result's last
key.  JAX's compile cache lives in ``<checkout>/.bench_cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import fields, replace  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from lib import check, client, trace, traffic, work  # noqa: E402
from lib.spec import load_cell, metric_reader, reference_module  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Device, compile cache, compile counting
# ---------------------------------------------------------------------------

def require_chips(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"needs {n} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
        sys.exit(3)
    return devs


def use_compile_cache() -> None:
    """Every program, however quick to compile, goes to the checkout's own
    cache directory, so only a cell's first run in a checkout compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # No size limit: a limit inherited from the environment would evict
    # this cell's programs as others are added.
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileLog:
    """Executables built or loaded from the compile cache, with the host
    time at which each finished."""

    def __init__(self):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, *args, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(duration),
                                str(kw.get("fun_name", ""))))

    def between(self, a: float, b: float):
        return [e for e in self.events if a <= e[0] <= b]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def program_config(conf, m):
    """The program's ``ModelConfig``: the registered architecture with the
    file's overrides, checked against the file's published sizes."""
    from repro.configs.base import get_config

    cfg = replace(get_config(conf["arch"]), **conf["overrides"])
    want = dict(num_layers=m["layers"], d_model=m["d"], num_heads=m["heads"],
                num_kv_heads=m["kv_heads"], head_dim=m["head_dim"],
                d_ff=m["ff"], vocab_size=m["vocab"],
                tie_embeddings=m["tied"], qkv_bias=m["qkv_bias"],
                norm="layernorm" if m["layernorm"] else "rmsnorm")
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise SystemExit(f"program config {got} != configuration {want}")
    return cfg


def bucket(n: int, cap: int) -> int:
    """The engine's prefill bucket of an n-token chunk: the smallest power
    of two >= n, at least 8, at most the chunk cap."""
    p = 8
    while p < n:
        p *= 2
    return min(p, cap)


def warm_lengths(settings, mix) -> list:
    """One prompt length per prefill shape the mix can reach: a one-chunk
    prompt for each first-chunk bucket from the shortest prompt's up, and
    a full first chunk plus a tail for each continuation bucket (chunk
    tails, and recomputes after a preemption, can be any size)."""
    cap = settings["prefill_chunk"]
    lo = mix["prompt"]["min"]
    lens = set()
    b = bucket(min(cap, lo), cap)
    while b < cap:
        lens.add(max(b, lo))
        b *= 2
    b = 8
    while b <= cap:
        lens.add(cap + b)
        b *= 2
    return sorted(lens)


def warm_bookkeeping(max_batch: int, vocab: int, dtype) -> None:
    """The engine's host-side bookkeeping runs small eager operations whose
    shapes the traffic decides: after a prefill chunk it gathers the
    finished rows' logits out of the cohort's (an eager gather for every
    cohort size and finished count), and a preemption clears one lane's
    active flag (an eager scatter for every lane).  Compile each once."""
    import jax.numpy as jnp

    for n in range(1, max_batch + 1):
        x = jnp.zeros((n, vocab), dtype)
        for k in range(1, n + 1):
            x[jnp.asarray(list(range(k)))]
    flags = jnp.zeros((max_batch,), bool)
    for lane in range(max_batch):
        flags.at[lane].set(False)


def build_engine(conf, cfg, weights, seed: int):
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(cfg, weights, mode="continuous",
                        seed=seed % (2**31), **conf["engine"]["settings"])
    step = eng.step

    def traced_step():
        with TraceAnnotation("bench.engine_step"):
            return step()

    eng.step = traced_step
    return eng


def warm_engine(conf, mix, weights, seed: int, break_path=None):
    """The engine of configuration ``conf`` serving ``weights``, with every
    prefill and decode shape that ``mix`` can reach compiled and run once.
    ``break_path(engine)``, when given, breaks the timed path underneath
    before anything compiles (the fault tests)."""
    from repro.serving.warmup import warmup_prefill

    m = reference_module(conf).dims(conf["model"])
    settings = conf["engine"]["settings"]
    eng = build_engine(conf, program_config(conf, m), weights, seed)
    if break_path is not None:
        break_path(eng)
    lens = warm_lengths(settings, mix)
    warmup_prefill(eng, m["vocab"], prompt_lens=lens,
                   group_sizes=range(1, settings["max_batch"] + 1))
    warm_bookkeeping(settings["max_batch"], m["vocab"],
                     weights["embed"].dtype)
    return eng


def build_frontend(conf, eng):
    from repro.serving.frontend import AsyncFrontend, CircuitBreaker

    fd = conf["frontend"]
    return AsyncFrontend(eng, max_queue_depth=fd["max_queue_depth"],
                         breaker=CircuitBreaker(**fd["breaker"]))


def stats_snapshot(eng) -> dict:
    s = eng.stats
    return {f.name: getattr(s, f.name) for f in fields(s)
            if isinstance(getattr(s, f.name), (int, float))}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(cell, seed: int, seconds: float, traced: bool, *,
        break_path=None, control=None, peaks=None, device=None) -> dict:
    """Everything after the look for a chip; ``break_path`` as for
    ``warm_engine``.  With ``control`` (a precision the reference knows,
    such as ``"fp8"``), the reference at that precision takes the
    program's place in the comparison: at each served position its own
    first choice is judged instead of the served token."""
    conf, mix = cell.config, cell.traffic
    compiles = CompileLog()
    ref = reference_module(conf)
    m = ref.dims(conf["model"])
    settings = conf["engine"]["settings"]

    weights = jax.block_until_ready(ref.make_weights(conf["model"], seed))
    log(f"weights ready at {time.perf_counter() - T_START:.1f}s")
    eng = warm_engine(conf, mix, weights, seed, break_path)
    log(f"warm at {time.perf_counter() - T_START:.1f}s, "
        f"{len(compiles.events)} executables")
    fe = build_frontend(conf, eng)

    reqs = traffic.requests(mix, seed, seconds, m["vocab"])
    prompts = {r.index: r.prompt for r in reqs}
    deadline = seconds + mix.get("drain_s", 0.0)
    lead = traffic.lead_s(mix)

    logdir = CACHE / "trace" / cell.workload["name"]
    if traced:
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(str(logdir))
    span = TraceAnnotation(trace.WINDOW_SPAN)
    mark = {}

    def on_start() -> float:
        mark["start"] = time.perf_counter()
        return mark["start"] + lead

    async def open_window(t0):
        await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
        mark["stats0"] = stats_snapshot(eng)
        span.__enter__()

    async def body(t0):
        opener = asyncio.ensure_future(open_window(t0))
        recs = await client.open_loop(fe, reqs, t0, deadline)
        await opener
        mark["t1"] = time.perf_counter()
        span.__exit__(None, None, None)
        mark["stats1"] = stats_snapshot(eng)
        mark["t0"] = t0
        return recs

    served = client.run(fe, body, on_start)
    t0, t1 = mark["t0"], mark["t1"]
    if traced:
        jax.profiler.stop_trace()
    dev = device or jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    breaker = {"opens": fe.breaker.opens, "shed": fe.breaker.shed,
               "rejected_backpressure": fe.stats.rejected_backpressure}

    # The window's requests are measured; the lead-in's only loaded it.
    recs = [r for r in served if r.due_s >= 0]
    red = trace.reduce(str(logdir)) if traced else None
    s0, s1 = mark["stats0"], mark["stats1"]
    ctx = SimpleNamespace(
        workload=cell.workload, config=conf, traffic=mix, m=m,
        settings=settings, records=recs, served=served,
        window_s=float(seconds), phase_s=t1 - t0, deadline_s=deadline,
        setup_s=mark["start"] - T_START,
        stats={k: s1[k] - s0[k] for k in s0}, trace=red,
        compiles=compiles.between(mark["start"], t1), peak=peaks, work=work)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for spec in wanted:
        v = metric_reader(spec["name"])(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}

    failed = sum(r.failed for r in recs)
    attempted = len(recs)
    log(f"setup {ctx.setup_s:.1f}s; lead-in {lead:.1f}s, "
        f"{len(served) - len(recs)} requests; traffic {ctx.phase_s:.1f}s; "
        f"{attempted} attempted, {failed} failed; front door {breaker}; "
        f"stats {ctx.stats}")
    for when, dur, name in ctx.compiles:
        log(f"compiled while the traffic ran: {name} ({dur:.3f}s)")
    compiles.close()

    # Free the engine (its pool and the step's buffers) before the
    # reference runs: the weights alone stay.
    fe.engine = None
    del fe, eng
    gc.collect()
    t = time.perf_counter()
    corr = conf["correctness"]
    sample = check.draw_sample(recs, seed, corr["min_tokens"],
                               corr["max_requests"], corr["min_requests"])
    judge = (lambda *a: check.control_gap(*a, control)) if control \
        else check.widest_gap
    gap = judge(ref, conf["model"], weights, sample, prompts) \
        if sample else math.inf
    who = f"control {control}" if control else "reference"
    log(f"{who} over {len(sample)} requests, "
        f"{sum(len(r.tokens) for r in sample)} served tokens, "
        f"{time.perf_counter() - t:.1f}s: widest gap {gap!r}")
    checks = {"max_logit_gap": {"value": gap,
                                "limit": corr["max_logit_gap"]}}
    correct = bool(sample) and gap <= corr["max_logit_gap"]

    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem}}
    if red is not None:
        out["device"]["busy_s"] = red.busy_s
        out["device"]["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(),
                            "idle_gaps": red.idle_gaps()}
    out["checks"] = checks
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="judge the reference at this precision in the "
                    "program's place (the comparison's control); never "
                    "part of a benchmark run")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    devs = require_chips(cell.workload["chips"])
    use_compile_cache()
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    kind = devs[0].device_kind
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              control=args.control, peaks=table[kind], device=devs[0])
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
