"""Client-side time to first token, from each request's scheduled arrival,
nearest-rank median over every request that arrived in the window.  A
request that failed or never produced a token sorts above all others; if
it is the median sample, the metric reads the drain deadline, measured
from the window's start."""
import math

from lib.stats import nearest_rank


def read(ctx):
    v = nearest_rank([r.ttft_s for r in ctx.records], 50.0)
    return 1000.0 * (ctx.deadline_s if math.isinf(v) else v)
