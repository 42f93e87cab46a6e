"""Model steps: host-clock prefill time per thousand prompt tokens
prefilled, over the traffic."""


def read(ctx):
    s = ctx.stats
    return 1000.0 * s["prefill_s"] / (s["prefill_tokens"] / 1000.0) \
        if s["prefill_tokens"] else None
