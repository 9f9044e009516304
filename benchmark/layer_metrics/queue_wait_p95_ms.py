"""HTTP server and scheduler admission: the engine request log's queue
wait (admission to scheduling) of the window's requests, 95th
percentile, ms."""

from stats import percentile


def read(ctx):
    mine = {a.request_id for a in ctx["answers"] if a.request_id}
    waits = [r["queue_wait_s"] for r in ctx["request_log"]
             if f"cmpl-{r['request_id']}" in mine
             and r.get("queue_wait_s") is not None]
    p = percentile(waits, 95)
    return None if p is None else 1e3 * p
