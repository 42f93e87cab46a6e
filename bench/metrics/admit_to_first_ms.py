"""Scheduler: mean time from a request's first admission to its first
token at a host sync: its chunked prefill, and the ticks it waits beside
other prompts' chunks (``EngineStats.admit_to_first_s`` over
``first_tokens``), over the traffic."""


def read(ctx):
    n = ctx.stats.get("first_tokens", 0)
    return 1000.0 * ctx.stats["admit_to_first_s"] / n if n else None
