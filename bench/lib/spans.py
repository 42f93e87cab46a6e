"""The program's own spans on the device trace's clock.

The serving path writes ``serve.*`` host spans (``TraceAnnotation``) into
the profiler's trace; the harness's reduction keeps only its own and
JAX's dispatch spans.  This module reads the run's trace once more with
``serve.`` kept, once per run, and joins the spans to the device's
operations: device idle time with no ``serve.step`` open is time the
chip waited outside the scheduler's steps (an empty engine, or the
front door's pump between ticks).
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from lib import trace

#: Where ``run.py`` records a cell's trace: ``<checkout>/.bench_cache/trace``.
TRACE_ROOT = Path(__file__).resolve().parents[2] / ".bench_cache" / "trace"
KEEP_HOST = ("bench.", "Pjit", "PjRt", "Execute", "serve.")
STEP_SPAN = "serve.step"


def trace_dir(workload: str) -> Path:
    return TRACE_ROOT / workload


def reduction(ctx) -> Optional[trace.Reduction]:
    """The run's trace with the program's spans, read once per run and
    kept on the run's ``ctx``; None for an untraced run."""
    if ctx.trace is None:
        return None
    if getattr(ctx, "program_trace", None) is None:
        t = time.perf_counter()
        ctx.program_trace = trace.reduce(
            str(trace_dir(ctx.workload["name"])), keep_host=KEEP_HOST)
        print(f"bench: trace read again for the program's spans in "
              f"{time.perf_counter() - t:.1f}s", file=sys.stderr, flush=True)
    return ctx.program_trace


def idle_outside_s(red: trace.Reduction, span: str = STEP_SPAN
                   ) -> Optional[float]:
    """Seconds of the window in which no operation ran on the chip and no
    ``span`` was open, averaged over the chips; None when the window holds
    no such span (a program without it)."""
    w = int(red.window_s * 1e9)
    spans = np.asarray([(max(s, 0), min(e, w)) for name, s, e in red.host
                        if name == span and e > 0 and s < w], np.int64)
    if len(spans) == 0 or not red.chips:
        return None
    idle = []
    for c in red.chips:
        both = trace.DeviceOps([], np.r_[c.start, spans[:, 0]],
                               np.r_[c.dur, spans[:, 1] - spans[:, 0]])
        idle.append(w - int(np.sum(np.diff(red._merged(both), axis=1))))
    return float(np.mean(idle)) / 1e9
