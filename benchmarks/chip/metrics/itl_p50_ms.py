"""Median of every gap between consecutive output tokens of a request,
for the tokens returned in the window."""
from stats import inter_token_gaps, percentile


def read(ctx):
    p = percentile(inter_token_gaps(ctx.served, ctx.window[0], ctx.window[1]), 50)
    return None if p is None else 1e3 * p
