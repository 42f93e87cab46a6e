"""Scheduler: prefill time over prefill plus decode time (the engine's
host-clock step intervals, each ending in a host sync), over the
traffic."""


def read(ctx):
    p, d = ctx.stats["prefill_s"], ctx.stats["decode_s"]
    return 100.0 * p / (p + d) if p + d > 0 else None
