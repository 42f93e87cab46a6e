"""Kernels: the paged flash-prefill kernel's share of its roofline.  The
least time is, per chunk of each prompt prefilled in the traced window
(chunks of the deployment's ``prefill_chunk``), the larger of its causal
attention FLOPs over peak and its bytes (cached K/V read once, new q/k/v
read, new K/V and the output written) over peak bandwidth; the kernel's
time is the summed device time of its events in the trace."""
#: The kernel's events: the HLO instruction of its ``pallas_call`` takes
#: the name of the jitted wrapper, ``paged_flash_prefill``.
PATTERN = r"^paged_flash_prefill$"


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(PATTERN)
    if not t:
        return None
    w, cap = ctx.work, ctx.settings["prefill_chunk"]
    least = w.least_time(
        (w.prefill_attn_work(ctx.m, s, n)
         for p in w.prefilled_prompts(ctx, ctx.trace.window_s)
         for s, n in w.chunks(p, cap)), ctx.peak)
    return 100.0 * least / t
