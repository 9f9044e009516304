"""Scheduler: mean of `ome_engine_batch_occupancy_ratio` (occupied
decode slots over `--max-slots`) sampled twice a second through the
window, %."""


def read(ctx):
    vals = [s["ome_engine_batch_occupancy_ratio"]
            for s in ctx["gauge_samples"]
            if "ome_engine_batch_occupancy_ratio" in s]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals)
