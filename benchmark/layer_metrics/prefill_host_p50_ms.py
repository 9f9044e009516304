"""Engine: the host-observed duration of a request's prefill call
(`prefill_s` of the engine's request log), the window's requests,
median, ms. With `queue_wait_p95_ms` and `router_added_p50_ms` it
splits TTFT from inside."""

from stats import percentile


def read(ctx):
    mine = {a.request_id for a in ctx["answers"] if a.request_id}
    took = [r["prefill_s"] for r in ctx["request_log"]
            if f"cmpl-{r['request_id']}" in mine
            and r.get("prefill_s") is not None]
    p = percentile(took, 50)
    return None if p is None else 1e3 * p
