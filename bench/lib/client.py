"""The benchmark's client: an open loop timed from each request's
scheduled arrival.

Adapted from the program's ``serving/openloop.py`` ``drive``, with two
changes: latency is measured from when a request was due, not from when
the client got round to sending it (so a stall that delays later sends
counts against them), and each record keeps its send lag, the client's
own lateness.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

from jax.profiler import TraceAnnotation


@dataclass
class Record:
    """Client-side outcome of one request.  Times are perf_counter
    seconds relative to the window start."""
    index: int
    prompt_len: int
    max_new_tokens: int
    due_s: float                    # scheduled send
    sent_s: Optional[float] = None
    token_s: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    #: pending | completed | rejected | error | unfinished
    status: str = "pending"
    error: Optional[str] = None

    @property
    def failed(self) -> bool:
        return self.status in ("rejected", "error", "unfinished")

    @property
    def ttft_s(self) -> float:
        """Due time to first token; ``inf`` when there was none."""
        return self.token_s[0] - self.due_s if self.token_s \
            else float("inf")

    @property
    def gaps_s(self) -> List[float]:
        return [b - a for a, b in zip(self.token_s, self.token_s[1:])]


async def _consume(stream, rec: Record, t0: float) -> None:
    async for tok in stream:
        now = time.perf_counter() - t0
        with TraceAnnotation("bench.deliver"):
            rec.token_s.append(now)
            rec.tokens.append(int(tok))


async def _submit(frontend, req):
    with TraceAnnotation("bench.submit"):
        return await frontend.submit(req.prompt,
                                     max_new_tokens=req.max_new_tokens)


async def open_loop(frontend, requests: Sequence, t0: float,
                    deadline_s: float) -> List[Record]:
    """Send each request at ``t0 + arrival_s`` whatever happened before,
    and wait for all of them until ``t0 + deadline_s``.  A rejection is
    final (the open-loop client is the load balancer's view); a request
    without its last token by the deadline is cancelled and
    ``unfinished``."""
    from repro.serving.frontend import RejectedError

    recs = [Record(r.index, len(r.prompt), r.max_new_tokens, r.arrival_s)
            for r in requests]
    streams = {}

    async def one(req, rec: Record) -> None:
        delay = t0 + req.arrival_s - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        rec.sent_s = time.perf_counter() - t0
        try:
            stream = await _submit(frontend, req)
        except RejectedError as e:
            rec.status, rec.error = "rejected", f"{e.kind}: {e}"
            return
        streams[rec.index] = stream
        try:
            await _consume(stream, rec, t0)
            if rec.status == "pending":
                rec.status = "completed"
        except Exception as e:  # a stream that ends in an error
            rec.status, rec.error = "error", f"{type(e).__name__}: {e}"

    tasks = [asyncio.ensure_future(one(q, r))
             for q, r in zip(requests, recs)]
    await asyncio.wait(tasks, timeout=max(
        0.0, t0 + deadline_s - time.perf_counter()))
    for rec in recs:
        if rec.status == "pending":
            rec.status = "unfinished"
            if rec.index in streams:
                await streams[rec.index].aclose()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs


def run(frontend, body: Callable, on_start: Callable[[], float]):
    """Start ``frontend``; ``on_start()`` marks the start of the load and
    returns the window's start as a perf_counter time ``t0`` (later by the
    mix's lead-in); run ``body(t0)``; stop the frontend, cancelling
    anything left, and return what ``body`` did."""
    async def main():
        await frontend.start()
        try:
            return await body(on_start())
        finally:
            await frontend.stop(drain=False)
    return asyncio.run(main())
