"""Self-checks of the readers of the program's own spans and counters:
the ``EngineStats`` readers on hand-built window deltas, and the join of
``serve.step`` spans to the device trace on a hand-built reduction.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import shutil
from types import SimpleNamespace

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import run as R
from lib import spans, trace
from lib.spec import metric_reader

DATA = __import__("pathlib").Path(__file__).parent / "data"
COUNTERS = ("handoff_ms", "queue_wait_ms", "admit_to_first_ms",
            "prefill_left_out", "step_host_ms", "between_steps_ms")


def _stats(**kw):
    s = dict(submitted=4, handoff_s=0.2, admissions=5, queue_wait_s=1.5,
             first_tokens=4, admit_to_first_s=6.0, prefill_left_out=3,
             prefill_member_ticks=12, step_s=10.0, prefill_s=2.0,
             decode_s=7.0, engine_steps=50, between_steps_s=0.049,
             between_steps=49)
    s.update(kw)
    return SimpleNamespace(stats=s)


def test_counter_readers_by_hand():
    ctx = _stats()
    want = {"handoff_ms": 50.0, "queue_wait_ms": 300.0,
            "admit_to_first_ms": 1500.0, "prefill_left_out": 25.0,
            "step_host_ms": 20.0, "between_steps_ms": 1.0}
    for name in COUNTERS:
        assert metric_reader(name)(ctx) == pytest.approx(want[name]), name


@pytest.mark.parametrize("name,count", [
    ("handoff_ms", "submitted"), ("queue_wait_ms", "admissions"),
    ("admit_to_first_ms", "first_tokens"),
    ("prefill_left_out", "prefill_member_ticks"),
    ("step_host_ms", "engine_steps"), ("between_steps_ms", "between_steps"),
])
def test_counter_reader_without_a_count_reads_nothing(name, count):
    assert metric_reader(name)(_stats(**{count: 0})) is None


def test_counter_readers_on_a_program_without_the_counters():
    """A program that predates the counters has only the older fields
    (``admissions``, ``prefill_s``, ``decode_s`` among them)."""
    old = {"admissions": 5, "prefill_s": 2.0, "decode_s": 7.0,
           "decode_steps": 40, "prefill_tokens": 9000}
    ctx = SimpleNamespace(stats=old)
    assert all(metric_reader(n)(ctx) is None for n in COUNTERS)


def _red(ops, host, window_ns):
    st = np.array([a for a, _ in ops], np.int64)
    du = np.array([b - a for a, b in ops], np.int64)
    return trace.Reduction(window_ns / 1e9, [trace.DeviceOps(
        ["fusion"] * len(ops), st, du)], host=host)


def test_idle_outside_steps_by_hand():
    # Device busy [0,100) [300,420) [800,900) of a 1000 ns window; steps
    # open [-50,200) (clipped to the window) and [350,700); a tick and an
    # admit span do not count.  Covered: [0,200) [300,700) [800,900).
    host = [("serve.step", -50, 200), ("serve.step", 350, 700),
            ("serve.tick", 100, 1000), ("serve.admit", 720, 790),
            ("bench.engine_step", 0, 1000)]
    red = _red([(0, 100), (300, 420), (800, 900)], host, 1000)
    assert spans.idle_outside_s(red) == pytest.approx(300e-9)
    # Idle overall is 680 ns: 380 of it inside steps.
    assert red.window_s - red.busy_s == pytest.approx(680e-9)
    # A step past the window's end is clipped to it.
    red = _red([(0, 100)], [("serve.step", 900, 1500)], 1000)
    assert spans.idle_outside_s(red) == pytest.approx(800e-9)
    # No step span (a program without spans): nothing to read.
    red = _red([(0, 100)], [("bench.engine_step", 0, 500)], 1000)
    assert spans.idle_outside_s(red) is None


def test_trace_dir_is_where_the_run_records():
    assert spans.trace_dir("granite-3-8b.pp2.code") == \
        R.CACHE / "trace" / "granite-3-8b.pp2.code"


def test_idle_between_steps_reads_the_trace_once_per_run(tmp_path,
                                                         monkeypatch):
    """The reader re-reads the run's trace directory with the program's
    spans kept, once however often it is asked; on the recorded chip
    trace, which holds no ``serve.step`` span, it reads nothing."""
    shutil.copytree(DATA, tmp_path / "tiny.cell")
    monkeypatch.setattr(spans, "TRACE_ROOT", tmp_path)
    calls = []
    real = trace.reduce

    def counted(logdir, keep_host=()):
        calls.append((logdir, tuple(keep_host)))
        return real(logdir, keep_host=keep_host)

    monkeypatch.setattr(trace, "reduce", counted)
    ctx = SimpleNamespace(workload={"name": "tiny.cell"},
                          trace=real(str(DATA)))
    read = metric_reader("idle_between_steps")
    assert read(ctx) is None and read(ctx) is None
    assert calls == [(str(tmp_path / "tiny.cell"), spans.KEEP_HOST)]
    assert "serve." in spans.KEEP_HOST
    assert read(SimpleNamespace(workload={"name": "tiny.cell"},
                                trace=None)) is None
