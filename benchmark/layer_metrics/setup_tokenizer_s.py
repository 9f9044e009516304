"""Engine: start-up between the last weight and the open
port: the tokenizer, then the HTTP server and its socket, seconds: the server's
`ome_engine_startup_phase_seconds` gauges for `tokenizer` and `listen`
(`engine/serve.py: main`, phases that tile process creation to ready),
read from the scrape taken after warm-up. None where the program
publishes no such gauge or the run measured no set-up."""

PHASES = ("tokenizer", "listen")


def read(ctx):
    if not ctx.get("setup_s"):
        return None
    took = [ctx["metrics_before"].get(
        f'ome_engine_startup_phase_seconds{{phase="{p}"}}') for p in PHASES]
    if any(t is None for t in took):
        return None
    return float(sum(took))
