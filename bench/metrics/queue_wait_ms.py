"""Scheduler: mean time from entering the engine's queue (or re-entering
it after a preemption) to an admission onto a lane
(``EngineStats.queue_wait_s`` over ``admissions``), over the traffic."""


def read(ctx):
    s = ctx.stats
    if "queue_wait_s" not in s or not s["admissions"]:
        return None
    return 1000.0 * s["queue_wait_s"] / s["admissions"]
