"""Model steps: host-clock time per decode step (dispatch to host sync),
over the traffic."""


def read(ctx):
    s = ctx.stats
    return 1000.0 * s["decode_s"] / s["decode_steps"] \
        if s["decode_steps"] else None
