"""Time from the moment a request was due to its first streamed token,
50th percentile over the requests of the window that were answered,
ms."""

from stats import percentile


def read(ctx):
    ttft = [a.arrivals[0] - a.due for a in ctx["answers"] if not a.failed]
    p = percentile(ttft, 50)
    return None if p is None else 1e3 * p
