"""Scheduler: host time per engine step outside the timed prefill and
decode intervals: admission, block growth, input assembly, table upload
and token emission (``EngineStats.step_s - prefill_s - decode_s`` over
``engine_steps``), over the traffic."""


def read(ctx):
    s = ctx.stats
    n = s.get("engine_steps", 0)
    return 1000.0 * (s["step_s"] - s["prefill_s"] - s["decode_s"]) / n \
        if n else None
