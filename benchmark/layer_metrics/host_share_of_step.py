"""Scheduler: the host's share of a decode step, from the deltas of
`ome_engine_step_phase_seconds_sum` over the window: (dispatch +
host_sample + mask_apply) over those plus device_wait, %."""

PHASES = ("dispatch", "host_sample", "mask_apply", "device_wait")


def read(ctx):
    def delta(phase):
        key = f'ome_engine_step_phase_seconds_sum{{phase="{phase}"}}'
        return (ctx["metrics_after"].get(key, 0.0)
                - ctx["metrics_before"].get(key, 0.0))

    d = {p: delta(p) for p in PHASES}
    total = sum(d.values())
    if total <= 0:
        return None
    return 100.0 * (total - d["device_wait"]) / total
