"""Latency-distribution stats + the bench artifact's structural gate.

Three layers, all deterministic (no engine runs, no wall clocks):

  * ``EngineStats.percentile`` / the ``p50_/p99_ttft_s`` and
    ``p50_/p99_itl_s`` accessors — unit pins on hand-built histories
    (nearest-rank semantics: the ceil(q/100*n)-th order statistic, so a
    pinned history has ONE right answer, no interpolation ambiguity);
  * ``ServingEngine._note_tokens`` — the per-host-sync recording rule
    that feeds those histories (first observation is the TTFT sample and
    contributes no ITL; later windows spread the observed gap over the
    tokens that arrived in them), pinned on hand-fed timestamps;
  * ``benchmarks.serving_bench.validate_bench`` — the schema gate run
    before ``BENCH_serving.json`` is written: a malformed artifact must
    fail the bench step in CI, not upload silently.
"""
import math

import numpy as np
import pytest

from benchmarks.serving_bench import BENCH_SCHEMA, validate_bench
from repro.serving.engine import EngineStats, ServingEngine


# ---------------------------------------------------------------------------
# percentile accessors
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank_pins():
    """Nearest-rank on a pinned history: p50 of 4 samples is the 2nd
    order statistic, p99 the 4th; order of insertion is irrelevant."""
    h = [4.0, 1.0, 3.0, 2.0]
    assert EngineStats.percentile(h, 50.0) == 2.0
    assert EngineStats.percentile(h, 75.0) == 3.0
    assert EngineStats.percentile(h, 99.0) == 4.0
    assert EngineStats.percentile(h, 100.0) == 4.0
    # 25% of 4 -> ceil(1.0) = 1st order statistic.
    assert EngineStats.percentile(h, 25.0) == 1.0
    # A tiny q still returns the minimum, never an index-out-of-range.
    assert EngineStats.percentile(h, 0.5) == 1.0
    assert EngineStats.percentile([7.25], 50.0) == 7.25
    assert EngineStats.percentile([7.25], 99.0) == 7.25


def test_percentile_empty_and_invalid_q():
    assert EngineStats.percentile([], 50.0) == 0.0
    assert EngineStats.percentile([], 99.0) == 0.0
    for q in (0.0, -1.0, 101.0):
        with pytest.raises(ValueError, match="percentile"):
            EngineStats.percentile([1.0], q)


def test_percentile_large_history_matches_rank_formula():
    rng = np.random.default_rng(3)
    h = rng.exponential(1.0, size=137).tolist()
    xs = sorted(h)
    for q in (50.0, 90.0, 99.0):
        want = xs[math.ceil(q / 100.0 * len(xs)) - 1]
        assert EngineStats.percentile(h, q) == want


def test_stats_properties_read_the_histories():
    s = EngineStats()
    s.ttft_history = [0.5, 0.1, 0.9, 0.3]
    s.itl_history = [0.01, 0.05, 0.02, 0.04, 0.03]
    assert s.p50_ttft_s == 0.3
    assert s.p99_ttft_s == 0.9
    assert s.p50_itl_s == 0.03
    assert s.p99_itl_s == 0.05
    empty = EngineStats()
    assert empty.p50_ttft_s == 0.0 and empty.p99_itl_s == 0.0


# ---------------------------------------------------------------------------
# _note_tokens: the recording rule behind the histories
# ---------------------------------------------------------------------------

def _bare_engine():
    """An engine skeleton with exactly the state _note_tokens touches —
    no model, no jit, so the timestamps are fully hand-controlled."""
    eng = ServingEngine.__new__(ServingEngine)
    eng.stats = EngineStats()
    eng._submit_t = {}
    eng._admit_t = {}
    eng._last_obs_t = {}
    return eng


def test_note_tokens_first_window_is_ttft_only():
    """The first observed window yields ONE TTFT sample and no ITL —
    even when decode_steps > 1 delivered several tokens at that first
    host sync (they share the sync; there is no measurable gap)."""
    eng = _bare_engine()
    eng._submit_t[7] = 10.0
    eng._note_tokens(7, 3, 10.5)
    assert eng.stats.ttft_history == [0.5]
    assert eng.stats.itl_history == []
    assert eng.stats.ttft_count == 1
    assert eng.stats.ttft_s_sum == 0.5
    assert eng._last_obs_t[7] == 10.5
    assert 7 not in eng._submit_t  # consumed: preemption cannot re-TTFT


def test_note_tokens_spreads_window_gap_over_tokens():
    """Observation granularity: a later host sync that released m tokens
    records m ITL samples of gap/m each — with decode_steps=1 every
    sample is a real host-sync gap, with K>1 the window mean."""
    eng = _bare_engine()
    eng._submit_t[1] = 0.0
    eng._note_tokens(1, 1, 1.0)   # TTFT 1.0
    eng._note_tokens(1, 1, 1.25)  # one token, gap 0.25
    eng._note_tokens(1, 4, 2.25)  # four tokens share a 1.0s window
    assert eng.stats.ttft_history == [1.0]
    assert eng.stats.itl_history == [0.25, 0.25, 0.25, 0.25, 0.25]
    assert eng.stats.p99_itl_s == 0.25


def test_note_tokens_zero_tokens_is_a_no_op():
    eng = _bare_engine()
    eng._submit_t[2] = 5.0
    eng._note_tokens(2, 0, 6.0)
    assert eng.stats.ttft_history == [] and eng.stats.itl_history == []
    assert 2 in eng._submit_t  # still waiting for its first token


def test_note_tokens_interleaved_requests_do_not_cross():
    """Per-uid last-observation clocks: interleaved requests' gaps never
    contaminate each other's histories."""
    eng = _bare_engine()
    eng._submit_t.update({1: 0.0, 2: 0.5})
    eng._note_tokens(1, 1, 1.0)
    eng._note_tokens(2, 1, 1.0)
    eng._note_tokens(1, 1, 3.0)  # uid 1 gap: 2.0
    eng._note_tokens(2, 1, 1.5)  # uid 2 gap: 0.5
    assert eng.stats.ttft_history == [1.0, 0.5]
    assert sorted(eng.stats.itl_history) == [0.5, 2.0]


# ---------------------------------------------------------------------------
# BENCH_serving.json schema gate
# ---------------------------------------------------------------------------

def _valid_bench():
    """Build the minimal dict satisfying every BENCH_SCHEMA path, typed
    from the schema itself — so the fixture can never drift from it."""
    bench: dict = {}
    dummies = {bool: True, int: 3, str: "x", dict: {"k": 1.0}, list: []}
    for path, typ in BENCH_SCHEMA:
        node = bench
        keys = path.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = dummies.get(typ, 0.25)
    return bench


def test_bench_schema_accepts_valid():
    validate_bench(_valid_bench())  # must not raise


def test_bench_schema_rejects_missing_path():
    bench = _valid_bench()
    del bench["open_loop"]["moderate"]["client_p99_ttft_s"]
    with pytest.raises(ValueError, match="client_p99_ttft_s"):
        validate_bench(bench)
    bench = _valid_bench()
    del bench["open_loop"]
    with pytest.raises(ValueError, match="open_loop"):
        validate_bench(bench)


def test_bench_schema_rejects_wrong_types():
    bench = _valid_bench()
    bench["open_loop"]["saturating"]["breaker"]["opens"] = "3"
    with pytest.raises(ValueError, match="wrong type"):
        validate_bench(bench)
    # bool is not an acceptable int/float (it would mean a counter got
    # replaced by a flag somewhere upstream).
    bench = _valid_bench()
    bench["open_loop"]["moderate"]["completed"] = True
    with pytest.raises(ValueError, match="wrong type"):
        validate_bench(bench)


def test_bench_schema_rejects_nonfinite_and_negative():
    bench = _valid_bench()
    bench["open_loop"]["moderate"]["client_p50_ttft_s"] = float("nan")
    with pytest.raises(ValueError, match="non-finite"):
        validate_bench(bench)
    bench = _valid_bench()
    bench["open_loop"]["moderate"]["goodput"]["goodput_req_s"] = -1.0
    with pytest.raises(ValueError, match="negative"):
        validate_bench(bench)


def test_bench_schema_reports_all_problems_at_once():
    bench = _valid_bench()
    del bench["sclad"]
    bench["arch"] = 7
    bench["open_loop"]["moderate"]["client_p99_itl_s"] = float("inf")
    with pytest.raises(ValueError) as e:
        validate_bench(bench)
    msg = str(e.value)
    assert "sclad" in msg and "arch" in msg and "client_p99_itl_s" in msg


def test_note_tokens_speculative_window_counts_accepted_only():
    """The speculative verify pass reports the ACCEPTED token count
    (anchor + accepted drafts) per host sync — never the proposed count —
    so each ITL window spreads the sync gap over tokens the client
    actually received.  A fully-accepted k=4 pass therefore records five
    gap/5 samples, and a fully-rejected pass one full-gap sample."""
    eng = _bare_engine()
    eng._submit_t[3] = 0.0
    eng._note_tokens(3, 3, 2.0)   # first verify pass: TTFT only
    eng._note_tokens(3, 5, 3.0)   # anchor + 4 accepted: 5 x 0.2
    eng._note_tokens(3, 1, 3.5)   # all drafts rejected: 1 x 0.5
    assert eng.stats.ttft_history == [2.0]
    assert eng.stats.itl_history == [0.2] * 5 + [0.5]
    # Had the rejected pass reported PROPOSED (5), the tail would have
    # been five phantom 0.1s samples — p99 would lie low.
    assert eng.stats.p99_itl_s == 0.5


def test_bench_schema_rejects_acceptance_rate_above_one():
    bench = _valid_bench()
    bench["spec_decode"]["repetitive"]["acceptance_rate"] = 1.5
    with pytest.raises(ValueError, match="rate > 1"):
        validate_bench(bench)
    bench = _valid_bench()
    bench["spec_decode"]["random"]["acceptance_rate"] = 1.0
    validate_bench(bench)  # inclusive upper bound: exactly 1 is legal
