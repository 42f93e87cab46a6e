"""Client-side gap between consecutive tokens of one request, nearest-rank
99th percentile over every gap of the window's requests."""
from lib.stats import nearest_rank


def read(ctx):
    gaps = [g for r in ctx.records for g in r.gaps_s]
    return 1000.0 * nearest_rank(gaps, 99.0) if gaps else None
