"""Kernels: the paged flash-decode kernel's share of its roofline.  The
least time is what the decode passes served in the traced window need:
each reads K and V of every position it sees, all layers, plus its
query and output (bandwidth-bound), over peak HBM bandwidth; the
kernel's time is the summed device time of its events in the trace."""
#: The kernel's events: the HLO instruction of its ``pallas_call`` takes
#: the name of the jitted wrapper, ``paged_flash_decode``.
PATTERN = r"^paged_flash_decode$"


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(PATTERN)
    if not t:
        return None
    w = ctx.work
    least = w.least_time((w.decode_attn_work(ctx.m, c) for c in
                          w.decode_contexts(ctx, ctx.trace.window_s)),
                         ctx.peak)
    return 100.0 * least / t
