"""Reduction of a profiler trace to device busy time, kernel time and
the longest idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran.  The host plane ``/host:CPU`` holds the
benchmark's ``TraceAnnotation`` spans on the same clock.  Everything is
clipped to the host span ``WINDOW_SPAN``, which the harness opens around
the measured traffic.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: An op event's name is its HLO instruction (``%name.N = shape op(...)``);
#: its key is the instruction name without the ``.N``.
_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = ")
#: Ops that contain others (a scanned layer stack is one ``while`` whose
#: body's ops are events of their own); they are not counted.
CONTAINERS = ("while", "conditional", "call")
#: Host spans that name what the host was doing during an idle gap, most
#: specific first.
_GAP_LABEL_SKIP = (WINDOW_SPAN,)


@dataclass
class DeviceOps:
    """One chip's operations inside the window: the instruction name of
    each (``op_key``), its start and duration in ns from the window's
    start."""
    names: List[str]
    start: np.ndarray
    dur: np.ndarray


def op_key(event_name: str) -> str:
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


@dataclass
class Reduction:
    window_s: float
    chips: List[DeviceOps]
    host: List[Tuple[str, int, int]] = field(default_factory=list)

    # -- busy / idle ---------------------------------------------------------
    def _merged(self, ops: DeviceOps) -> np.ndarray:
        if len(ops.start) == 0:
            return np.zeros((0, 2), np.int64)
        order = np.argsort(ops.start, kind="stable")
        s, e = ops.start[order], (ops.start + ops.dur)[order]
        out = []
        cs, ce = int(s[0]), int(e[0])
        for a, b in zip(s[1:].tolist(), e[1:].tolist()):
            if a > ce:
                out.append((cs, ce))
                cs, ce = a, b
            elif b > ce:
                ce = b
        out.append((cs, ce))
        return np.asarray(out, np.int64)

    @property
    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the chips."""
        tot = [float(np.sum(np.diff(self._merged(c), axis=1)))
               for c in self.chips]
        return float(np.mean(tot)) / 1e9 if tot else 0.0

    def kernel_s(self, pattern: str) -> Optional[float]:
        """Summed device time of the operations whose name matches
        ``pattern``, averaged over the chips; None where none matches."""
        rx = re.compile(pattern)
        tot, hit = [], False
        for c in self.chips:
            sel = [i for i, n in enumerate(c.names) if rx.search(n)]
            hit = hit or bool(sel)
            tot.append(float(np.sum(c.dur[sel])) if sel else 0.0)
        return float(np.mean(tot)) / 1e9 if hit else None

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time, by name, on the
        first chip."""
        if not self.chips:
            return []
        c = self.chips[0]
        acc: Dict[str, float] = {}
        for name, d in zip(c.names, c.dur.tolist()):
            acc[name] = acc.get(name, 0.0) + d / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest stretches with no operation on the first chip,
        each named by the innermost host span around its middle."""
        if not self.chips:
            return []
        m = self._merged(self.chips[0])
        w = int(self.window_s * 1e9)
        bounds = np.concatenate([[0], m.ravel(), [w]]).reshape(-1, 2)
        gaps = [(int(a), int(b)) for a, b in bounds if b > a]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) // 2
            inside = [(e - s, name) for name, s, e in self.host
                      if s <= mid <= e and name not in _GAP_LABEL_SKIP]
            label = min(inside)[1] if inside else "no host span"
            out.append([f"host:{label}", (b - a) / 1e9])
        return out


def reduce(logdir: str, keep_host: Sequence[str] = ("bench.", "Pjit",
                                                     "PjRt", "Execute")
           ) -> Reduction:
    """Read the newest trace under ``logdir`` and clip it to the window
    span.  Raises when the trace has no window span or no device plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no trace under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    host, win = [], None
    for pl in pd.planes:
        if not pl.name.startswith("/host:CPU"):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                name = ev.name
                if name == WINDOW_SPAN:
                    win = (int(ev.start_ns), int(ev.start_ns
                                                 + ev.duration_ns))
                elif name.startswith(tuple(keep_host)):
                    host.append((name, int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns)))
    if win is None:
        raise RuntimeError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = win
    chips = []
    for pl in pd.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        names, st, du = [], [], []
        for ln in pl.lines:
            if ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= w0 or s >= w1:
                    continue
                key = op_key(ev.name)
                if key in CONTAINERS:
                    continue
                s, e = max(s, w0), min(e, w1)
                names.append(key)
                st.append(s - w0)
                du.append(e - s)
        chips.append(DeviceOps(names, np.asarray(st, np.int64),
                               np.asarray(du, np.int64)))
    if not chips:
        raise RuntimeError(
            f"trace has no device plane; planes: "
            f"{[pl.name for pl in pd.planes]}")
    host = [(n, s - w0, e - w0) for n, s, e in host if e > w0 and s < w1]
    return Reduction((w1 - w0) / 1e9, chips, host)
