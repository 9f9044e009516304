"""Router: what the router adds before the first token. Client-side
time from send to first chunk, minus the engine's own TTFT for the
same request id (`--request-log`), median, ms."""

from stats import percentile


def read(ctx):
    engine = {f"cmpl-{r['request_id']}": r["ttft_s"]
              for r in ctx["request_log"] if r.get("ttft_s") is not None}
    added = [a.arrivals[0] - a.sent - engine[a.request_id]
             for a in ctx["answers"]
             if not a.failed and a.request_id in engine]
    p = percentile(added, 50)
    return None if p is None else 1e3 * p
