"""Whole step: model FLOPs of the decode passes served (every matrix
product, the head, attention over each pass's context) over the decode
time (the engine's host-clock decode intervals) times peak bf16 FLOP/s."""


def read(ctx):
    d = ctx.stats["decode_s"]
    if d <= 0:
        return None
    w = ctx.work
    flops = sum(w.decode_token_flops(ctx.m, c)
                for c in w.decode_contexts(ctx, ctx.phase_s))
    return 100.0 * flops / (d * ctx.peak["bf16_flops"])
