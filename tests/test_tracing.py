"""Spans and counters inside the serving path.

The counters are ``EngineStats`` fields the engine fills on the thread
that drives it: front-door hand-off, engine queue wait, admission to
first token, prefill requests left out of a step's cohort, step wall time
and the caller's time between steps.  Each is pinned here by hand on a
tiny engine (reference attention, CPU).  The spans are
``jax.profiler.TraceAnnotation``s named ``serve.*``; a profiler trace
around a few front-door ticks must show each of them on the host plane,
nested tick > step > decode run.
"""
import asyncio
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.frontend import AsyncFrontend

SPANS = ("serve.tick", "serve.dispatch", "serve.step", "serve.admit",
         "serve.grow", "serve.prefill.inputs", "serve.prefill.run",
         "serve.decode.inputs", "serve.decode.run", "serve.emit")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    cfg, params = tiny
    base = dict(max_batch=2, max_len=64, eos_id=-1, block_size=4,
                prefill_chunk=8, attn_kernel="off")
    base.update(kw)
    return ServingEngine(cfg, params, **base)


def _prompts(n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=lengths[i % len(lengths)])
            for i in range(n)]


def test_queue_wait_plus_admit_to_first_is_ttft(tiny):
    """With no preemption, each request's queue wait and its admission to
    first token are two halves of the engine's own submit-to-first-token
    time, so their sums agree; five requests on two lanes queue."""
    eng = _engine(tiny)
    for p in _prompts(5, (5, 11, 17)):
        eng.submit(p, max_new_tokens=4)
    eng.run()
    s = eng.stats
    assert s.preemptions == 0
    assert s.admissions == s.first_tokens == s.ttft_count == 5
    assert s.queue_wait_s > 0 and s.admit_to_first_s > 0
    assert s.queue_wait_s + s.admit_to_first_s == pytest.approx(
        s.ttft_s_sum, rel=1e-9, abs=1e-12)


def test_preempted_request_waits_again_from_its_requeue(tiny):
    """A pool too small for every lane's growth preempts: each re-queue
    is a further admission whose wait counts from the re-queue, and the
    first token still counts once per request from its first admission."""
    eng = _engine(tiny, max_batch=3, num_blocks=12)
    for p in _prompts(4, (9, 13)):
        eng.submit(p, max_new_tokens=16)
    eng.run()
    s = eng.stats
    assert s.preemptions > 0
    assert s.admissions == 4 + s.preemptions
    assert s.first_tokens == s.ttft_count == 4
    assert s.queue_wait_s + s.admit_to_first_s >= s.ttft_s_sum - 1e-9


def test_new_prompt_left_out_behind_continuation_chunks(tiny):
    """The prefill cohort follows the oldest prompt's first/continuation
    kind: a prompt admitted while the first is mid-prompt waits out the
    first's remaining chunks."""
    eng = _engine(tiny)
    eng.submit(_prompts(1, (24,))[0], max_new_tokens=2)  # 3 chunks of 8
    eng.step()
    s = eng.stats
    assert (s.prefill_left_out, s.prefill_member_ticks) == (0, 1)
    eng.submit(_prompts(1, (5,), seed=1)[0], max_new_tokens=2)
    eng.step()  # the first's chunk 2; the new prompt is left out
    assert (s.prefill_left_out, s.prefill_member_ticks) == (1, 3)
    eng.step()  # the first's chunk 3; left out again
    assert (s.prefill_left_out, s.prefill_member_ticks) == (2, 5)
    eng.step()  # now its own first chunk runs
    assert (s.prefill_left_out, s.prefill_member_ticks) == (2, 6)
    assert s.prefill_chunks == 4


def test_between_steps_counts_only_while_work_is_pending(tiny):
    eng = _engine(tiny)
    prompt = _prompts(1, (6,))[0]
    eng.submit(prompt, max_new_tokens=1)
    eng.step()  # prefill and its one token: the engine is empty again
    assert not eng.has_pending_work()
    time.sleep(0.02)
    eng.submit(prompt, max_new_tokens=3)
    eng.step()
    s = eng.stats
    assert (s.between_steps, s.between_steps_s) == (0, 0.0)
    assert eng.has_pending_work()
    time.sleep(0.02)
    eng.step()
    assert s.between_steps == 1 and s.between_steps_s >= 0.02
    assert s.engine_steps == 3
    assert s.step_s >= s.prefill_s + s.decode_s > 0


def test_handoff_counts_front_door_submits(tiny):
    """Direct submits carry no arrival stamp and are not counted; every
    request the front door accepted is, with a positive hand-off."""
    eng = _engine(tiny)
    eng.submit(_prompts(1, (7,))[0], max_new_tokens=2)
    eng.run()
    assert (eng.stats.submitted, eng.stats.handoff_s) == (0, 0.0)

    async def main():
        async with AsyncFrontend(eng) as fe:
            streams = [await fe.submit(p, max_new_tokens=3)
                       for p in _prompts(3, (5, 9))]
            for st in streams:
                await st.collect()
            return fe.stats.accepted

    accepted = asyncio.run(main())
    assert eng.stats.submitted == accepted == 3
    assert eng.stats.handoff_s > 0


def _host_events(logdir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:CPU"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def test_serve_spans_on_the_profiler_host_plane(tiny, tmp_path):
    eng = _engine(tiny)
    prompts = _prompts(3, (5, 13))
    for p in prompts:  # compile outside the trace
        eng.submit(p, max_new_tokens=3)
    eng.run()

    async def main():
        async with AsyncFrontend(eng) as fe:
            streams = [await fe.submit(p, max_new_tokens=3)
                       for p in prompts]
            for st in streams:
                await st.collect()

    jax.profiler.start_trace(str(tmp_path))
    try:
        asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(tmp_path)
    assert set(SPANS) <= {n for n, *_ in evs}

    def inside(a, b):
        return b[1] <= a[1] and a[2] <= b[2]

    by = {n: [e for e in evs if e[0] == n] for n in SPANS}
    assert any(inside(run, step) and inside(step, tick)
               for run in by["serve.decode.run"]
               for step in by["serve.step"]
               for tick in by["serve.tick"])
    assert {e[3]["uid"] for e in by["serve.admit"]} == {4, 5, 6}
    assert all(e[3]["rows"] >= 1 for e in by["serve.prefill.run"])
