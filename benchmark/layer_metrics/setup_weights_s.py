"""Engine: start-up spent on weights and device state: the
checkpoint or the random init, then slab / pool / ring allocation and
the scheduler, seconds: the server's
`ome_engine_startup_phase_seconds` gauges for `weights` and `engine`
(`engine/serve.py: main`, phases that tile process creation to ready),
read from the scrape taken after warm-up. None where the program
publishes no such gauge or the run measured no set-up."""

PHASES = ("weights", "engine")


def read(ctx):
    if not ctx.get("setup_s"):
        return None
    took = [ctx["metrics_before"].get(
        f'ome_engine_startup_phase_seconds{{phase="{p}"}}') for p in PHASES]
    if any(t is None for t in took):
        return None
    return float(sum(took))
