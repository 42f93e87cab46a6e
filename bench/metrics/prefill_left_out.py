"""Scheduler: share of prefill ticks in which a request mid-prompt ran no
chunk, because the step ran the other (first or continuation) cohort
(``EngineStats.prefill_left_out`` over ``prefill_member_ticks``), over
the traffic."""


def read(ctx):
    n = ctx.stats.get("prefill_member_ticks", 0)
    return 100.0 * ctx.stats["prefill_left_out"] / n if n else None
