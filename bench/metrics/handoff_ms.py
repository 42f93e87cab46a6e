"""Front door: mean time from the front door accepting a request to the
pump handing it to the engine (``EngineStats.handoff_s`` over
``submitted``), over the traffic."""


def read(ctx):
    n = ctx.stats.get("submitted", 0)
    return 1000.0 * ctx.stats["handoff_s"] / n if n else None
