"""Scheduler: mean of `ome_engine_decode_step_seconds` over the window
(delta of `_sum` over delta of `_count`), ms: a decode step's
completion time as the scheduler observes it where it learns the step
ended."""


def read(ctx):
    def delta(suffix):
        key = "ome_engine_decode_step_seconds" + suffix
        return (ctx["metrics_after"].get(key, 0.0)
                - ctx["metrics_before"].get(key, 0.0))

    count = delta("_count")
    if count <= 0:
        return None
    return 1e3 * delta("_sum") / count
