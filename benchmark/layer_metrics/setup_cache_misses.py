"""Engine: programs compiled, not loaded, before the window:
`ome_engine_compile_events_total{outcome="cache_miss"}` (entries
written to the persistent compile cache) in the scrape taken after
warm-up. 0 on a warm machine; what it is tells a cold `setup_s` from a
slow one. None where the program has no such counter or the run
measured no set-up."""


def read(ctx):
    if not ctx.get("setup_s"):
        return None
    return ctx["metrics_before"].get(
        'ome_engine_compile_events_total{outcome="cache_miss"}')
