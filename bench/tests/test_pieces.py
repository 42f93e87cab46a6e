"""Self-checks of the benchmark's pieces on the CPU: traffic, percentiles,
window arithmetic, work functions and the trace reduction.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from lib import trace, traffic, work
from lib.client import Record
from lib.spec import metric_reader
from lib.stats import nearest_rank, quartile_spread

CHAT = {"loop": "open", "rate_req_s": 2.5, "drain_s": 60,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.7,
                   "min": 129, "max": 3072},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 8, "max": 1024}}
BIG_SEED = 2**31 + 977
DATA = __import__("pathlib").Path(__file__).parent / "data"


def test_same_seed_same_requests():
    a = traffic.open_loop(CHAT, BIG_SEED, 51, 100352)
    b = traffic.open_loop(CHAT, BIG_SEED, 51, 100352)
    assert len(a) == len(b) == int(2.5 * 51)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_share_the_schedule_not_the_tokens():
    a = traffic.open_loop(CHAT, 1, 51, 100352)
    b = traffic.open_loop(CHAT, BIG_SEED, 51, 100352)
    assert [(r.arrival_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.arrival_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0 <= r.arrival_s < 51 for r in a)


def test_lengths_follow_the_distribution():
    x = traffic.quantile_lengths(CHAT["prompt"], 1001)
    assert x.min() >= 129 and x.max() <= 3072
    assert abs(np.median(x) - 1024) <= 1
    g = traffic.exponential_gaps(2.5, 1000)
    assert abs(g.mean() - 1 / 2.5) < 1e-12
    assert abs(np.median(g) - math.log(2) / 2.5) < 0.01


def test_lead_in_precedes_the_window_and_leaves_it_alone():
    mix = dict(CHAT, lead_s=20)
    a = traffic.open_loop(CHAT, BIG_SEED, 51, 100352)
    b = traffic.open_loop(mix, BIG_SEED, 51, 100352)
    lead = [r for r in b if r.arrival_s < 0]
    assert len(lead) == int(2.5 * 20) and b[len(lead):][0].arrival_s == 0
    assert traffic.lead_s(mix) == pytest.approx(20.0)
    assert lead[0].arrival_s == pytest.approx(-20.0)
    assert all(x.arrival_s < y.arrival_s for x, y in zip(lead, lead[1:]))
    # The window's requests are the same with and without a lead-in.
    for x, y in zip(a, b[len(lead):]):
        assert (x.index, x.arrival_s, x.max_new_tokens) == \
            (y.index, y.arrival_s, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)
    assert sorted(r.index for r in b) == list(range(len(b)))


def test_nearest_rank_on_hand_built_samples():
    xs = list(range(1, 11))
    assert nearest_rank(xs, 90) == 9
    assert nearest_rank(xs, 91) == 10
    assert nearest_rank(xs, 50) == 5
    assert nearest_rank([3.0, math.inf, 1.0], 90) == math.inf
    assert nearest_rank([7.0], 99) == 7.0
    # statistics.quantiles (exclusive method) of 1..9: 2.5, 5, 7.5
    assert quartile_spread(list(range(1, 10))) == pytest.approx(1.0)


def _rec(i, due, tokens_at, status="completed", prompt=100):
    r = Record(i, prompt, len(tokens_at), due, sent_s=due)
    r.token_s = list(tokens_at)
    r.tokens = [1] * len(tokens_at)
    r.status = status
    return r


def test_window_arithmetic():
    recs = [_rec(i, 0.1 * i, [0.1 * i + 0.05 * (i + 1), 0.1 * i + 0.5,
                              0.1 * i + 0.6]) for i in range(9)]
    recs.append(_rec(9, 0.9, [], status="unfinished"))
    ctx = SimpleNamespace(records=recs, window_s=1.0, deadline_s=61.0)
    # Ten requests: the median is the 5th smallest, 0.25 s.
    assert metric_reader("ttft_p50_ms")(ctx) == pytest.approx(250.0)
    for r in recs[3:9]:
        r.status, r.token_s = "unfinished", []
    # Seven failures: the 5th smallest is now a failure -> the deadline.
    assert metric_reader("ttft_p50_ms")(ctx) == pytest.approx(61000.0)
    gaps = sorted(g for r in recs for g in r.gaps_s)
    assert metric_reader("itl_p99_ms")(ctx) == pytest.approx(
        1000 * gaps[math.ceil(0.99 * len(gaps)) - 1])


M = {"layers": 2, "d": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
     "ff": 128, "vocab": 256}


def test_work_functions_by_hand():
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert work.matmul_params_per_layer(M) == per_layer
    assert work.kv_bytes_per_token(M) == 2 * 2 * 16 * 2 * 2
    assert work.decode_token_flops(M, 10) == 2 * (2 * per_layer + 64 * 256) \
        + 4 * 4 * 16 * 10 * 2
    f, b = work.decode_attn_work(M, 10)
    assert f == 4 * 4 * 16 * 10 * 2
    assert b == 256 * 10 + 2 * 4 * 16 * 2 * 2
    assert list(work.chunks(600, 256)) == [(0, 256), (256, 256), (512, 88)]
    # Prefill attention over a whole prompt is the same work however it
    # is chunked.
    whole = work.prefill_attn_work(M, 0, 600)[0]
    parts = sum(work.prefill_attn_work(M, s, n)[0]
                for s, n in work.chunks(600, 256))
    assert parts == pytest.approx(whole)
    peak = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert work.least_time([(100.0, 5.0), (10.0, 50.0)], peak) == 6.0


def _red(intervals, names, window_ns):
    st = np.array([a for a, _ in intervals], np.int64)
    du = np.array([b - a for a, b in intervals], np.int64)
    return trace.Reduction(window_ns / 1e9, [trace.DeviceOps(
        names, st, du)], host=[("bench.engine_step", 0, 400),
                               ("bench.submit", 450, 480)])


def test_busy_union_kernel_time_and_gaps():
    red = _red([(0, 100), (50, 150), (300, 400), (390, 420), (800, 900)],
               ["fusion", "paged_flash_decode", "fusion",
                "paged_flash_decode", "copy"], 1000)
    assert red.busy_s == pytest.approx(370e-9)
    assert red.kernel_s(r"^paged_flash_decode$") == pytest.approx(130e-9)
    assert red.kernel_s(r"nothing") is None
    gaps = red.idle_gaps(2)
    assert gaps[0] == ["host:no host span", pytest.approx(380e-9)]
    assert gaps[1] == ["host:bench.engine_step", pytest.approx(150e-9)]
    assert red.top_ops(1) == [["fusion", pytest.approx(200e-9)]]


def test_roofline_share_by_hand():
    # One decode pass at context 10 takes 1 us of kernel time; the least
    # time is its bytes over bandwidth.  A lead-in request's token served
    # before the window opened is not the window's work.
    red = _red([(0, 1000)], ["paged_flash_decode"], 2000)
    recs = [_rec(0, 0.0, [1e-7], prompt=9), _rec(1, -1.0, [-0.5], prompt=9)]
    peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    ctx = SimpleNamespace(trace=red, served=recs, m=M, peak=peak, work=work)
    got = metric_reader("flash_decode_roofline")(ctx)
    want = 100 * work.decode_attn_work(M, 10)[1] / 819e9 / 1e-6
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    ctx.trace = _red([(0, 1000)], ["fusion"], 2000)
    assert metric_reader("flash_decode_roofline")(ctx) is None


def test_op_names():
    assert trace.op_key("%paged_flash_prefill.7 = (bf16[1,32,256,64]) "
                        "custom-call(s32[1] %get-tuple-element.3)") == \
        "paged_flash_prefill"
    assert trace.op_key("%multiply_reduce_fusion.46 = f32[4] fusion()") == \
        "multiply_reduce_fusion"
    assert trace.op_key("%copy-start.3 = (bf16[2]) copy-start()") == \
        "copy-start"


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite chip: a two-layer engine at
    stablelm-1.6b's widths serves one 300-token prompt (chunks of 256 and
    44) for 8 greedy tokens, inside the window span."""
    red = trace.reduce(str(DATA))
    assert 0 < red.busy_s < red.window_s < 0.2
    c = red.chips[0]
    assert c.names.count("paged_flash_decode") == 2 * 8
    assert c.names.count("paged_flash_prefill") == 2 * 2
    assert "while" not in c.names
    m = dict(M, layers=2, d=2048, heads=32, kv_heads=32, head_dim=64,
             ff=5632, vocab=100352)
    peak = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    rec = _rec(0, 0.0, [red.window_s / 2] * 8, prompt=300)
    ctx = SimpleNamespace(trace=red, served=[rec], m=m, peak=peak,
                          work=work, settings={"prefill_chunk": 256})
    dec = metric_reader("flash_decode_roofline")(ctx)
    pre = metric_reader("flash_prefill_roofline")(ctx)
    least = sum(work.decode_attn_work(m, 301 + j)[1] for j in range(8)) \
        / 819e9
    assert dec == pytest.approx(100 * least / red.kernel_s(
        "^paged_flash_decode$"))
    assert 0 < dec <= 100 and 0 < pre <= 100
    assert 0 < metric_reader("device_idle")(ctx) < 100
