"""Engine: seconds of compiling and loading programs before the
window: `ome_engine_compile_seconds_total` summed over every stage
(trace, lowering, compile, cache load, the ledger's introspection),
start-up and serving together, from the scrape taken after warm-up.
Seconds of work on the threads that compiled (the admission thread's
prefill and the scheduler's decode may overlap), not of wall. None
where the program has no such counter or the run measured no set-up."""

import re


_LABEL = re.compile(r'(\w+)="([^"]*)"')


def family(samples, name):
    """[(labels, value)] of one metric family of a scrape, whose keys
    are `name{labels}` with the labels verbatim."""
    out = []
    for key, value in samples.items():
        base, _, rest = key.partition("{")
        if base == name:
            out.append((dict(_LABEL.findall(rest)), value))
    return out


def read(ctx):
    if not ctx.get("setup_s"):
        return None
    rows = family(ctx["metrics_before"], "ome_engine_compile_seconds_total")
    if not rows:
        return None
    return float(sum(value for _, value in rows))
