"""Front door: mean time from the end of an engine step that left work
pending to the start of the next: the pump's hand-offs, token dispatch
and the event loop's other work (``EngineStats.between_steps_s`` over
``between_steps``), over the traffic."""


def read(ctx):
    n = ctx.stats.get("between_steps", 0)
    return 1000.0 * ctx.stats["between_steps_s"] / n if n else None
