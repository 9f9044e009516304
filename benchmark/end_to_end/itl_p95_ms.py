"""Gap between streamed tokens: 95th percentile over all gaps of all
requests that ended inside the window, ms."""

from stats import gaps, percentile


def read(ctx):
    all_gaps = []
    for a in ctx["answers"]:
        all_gaps += gaps(a.arrivals, until=ctx["t1"])
    p = percentile(all_gaps, 95)
    return None if p is None else 1e3 * p
