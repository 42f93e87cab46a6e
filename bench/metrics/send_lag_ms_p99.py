"""The client's own lateness: actual minus scheduled send, nearest-rank
99th percentile.  A starved generator is not a fast server."""
from lib.stats import nearest_rank


def read(ctx):
    lag = [r.sent_s - r.due_s for r in ctx.records if r.sent_s is not None]
    return 1000.0 * nearest_rank(lag, 99.0) if lag else None
