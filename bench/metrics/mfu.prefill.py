"""Whole step: model FLOPs of the prompts prefilled (every token through
the layers with causal attention, the head once) over the prefill time
(the engine's host-clock prefill intervals) times peak bf16 FLOP/s."""


def read(ctx):
    p = ctx.stats["prefill_s"]
    if p <= 0:
        return None
    w = ctx.work
    flops = sum(w.prefill_flops(ctx.m, n)
                for n in w.prefilled_prompts(ctx, ctx.phase_s))
    return 100.0 * flops / (p * ctx.peak["bf16_flops"])
